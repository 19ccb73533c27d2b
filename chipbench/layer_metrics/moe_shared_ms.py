"""``moe_shared_ms``: milliseconds of a traced step under ``ht.moe.shared``:
the shared expert that every token of an expert layer goes through.
Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.moe.shared")
