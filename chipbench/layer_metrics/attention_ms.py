"""``attention_ms``: milliseconds of a traced step under ``ht.attention``, the
scores-softmax-values part of attention (the flash kernels), without the
projections.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.attention")
