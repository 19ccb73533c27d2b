"""``kda_prepare_ms``: milliseconds of a traced step under ``ht.kda.prepare``,
the chunk-local part of the delta rule (the Pallas kernels at the cell's
shapes); with ``kda_recur_ms`` it is ``kda_ms``.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.kda.prepare")
