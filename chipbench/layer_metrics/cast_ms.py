"""``cast_ms``: milliseconds of a traced step under ``ht.lm.cast``: the
matrices brought to the activations' dtype, forward and in every
recomputation, where XLA did not fuse the conversion into the product that
reads it.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.lm.cast")
