"""``kda_ms``: milliseconds of a traced step under ``ht.kda``, the chunked
gated delta rule of the Kimi Delta Attention layers (forward, recomputed
forward and backward), without projections, convolutions and gates.
Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.kda")
