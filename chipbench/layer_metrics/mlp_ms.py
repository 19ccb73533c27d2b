"""``mlp_ms``: milliseconds of a traced step under ``ht.mlp``, the dense
feed-forward layers with their residual sums (forward, recomputed forward and
backward).  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.mlp")
