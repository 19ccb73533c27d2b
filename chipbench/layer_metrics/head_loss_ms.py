"""``head_loss_ms``: milliseconds of a traced step under ``ht.lm.head_loss``:
the final norm, the product with the tied embedding and the cross-entropy,
forward and backward.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.lm.head_loss")
