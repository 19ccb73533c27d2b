"""``lightning_ms``: milliseconds of a traced step under ``ht.lightning``, the
chunked decayed linear attention of the lightning layers (the chunks' parts,
the state's scan, forward, recomputed forward and backward), without the
projections and the output norm and gate (``ht.lightning.gate``).  Layer:
model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.lightning")
