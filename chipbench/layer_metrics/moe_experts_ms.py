"""``moe_experts_ms``: milliseconds of a traced step under ``ht.moe.experts``,
the grouped products of the expert layers (forward, recomputed forward and
backward), the products themselves by their kernels' name: XLA:TPU runs
``jax.lax.ragged_dot`` as ``ragged-dot-none.N`` and keeps no scope on it.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.moe.experts", ops=("ragged-dot",))
