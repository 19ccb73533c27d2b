"""``norm_ms``: milliseconds of a traced step under ``ht.lm.norm``, the two
RMSNorms of every block (the final norm is ``head_loss_ms``'s, QK norms are
``attention_proj_ms``'s).  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.lm.norm")
