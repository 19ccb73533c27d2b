"""``kda_proj_ms``: milliseconds of a traced step whose innermost scope is
``ht.kda.proj``: the Kimi Delta Attention layers' input and output projections
and the residual sum, without what the scope holds further in (convolutions,
gates, the kernel).  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    found = scopes.by_layer(ctx.trace).get("ht.kda.proj")
    return None if found is None else 1e3 * found
