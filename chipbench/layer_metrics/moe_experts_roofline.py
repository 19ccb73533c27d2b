"""``moe_experts_roofline``: per cent of its roofline that the experts' grouped
products reach: 6 operations for every expert parameter and every row that
the window's steps really routed to the experts held (the job's ``moe_rows``
counter, not the expectation) at the bf16 peak, over the time under
``ht.moe.experts`` and in the ``ragged-dot`` kernels (which carry no scope).
Compute-bound.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    rows = ctx.counters.get("moe_rows")
    if not rows or not ctx.samples:
        return None
    d, width = ctx.config["hidden_size"], ctx.config["moe_intermediate_size"]
    return scopes.kernel_share(ctx, "moe_experts", flop=6 * 3 * d * width * rows / len(ctx.samples),
                               ops=("ragged-dot",))
