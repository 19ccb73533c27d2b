"""``optimizer_ms``: milliseconds of a traced step under ``ht.optim.update``:
the non-finite guard and AdamW over every parameter.  Layer: trainers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.optim.update")
