"""``window_attention_ms``: milliseconds of a traced step under
``ht.attention.window``, the scores-softmax-values part of the windowed
attention layers (the flash kernels' windowed sweeps; forward, recomputed
forward and backward), without the projections and the rotation.  A global
layer's part is ``attention_ms``'s (``ht.attention``).  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.attention.window")
