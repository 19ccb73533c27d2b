"""``moe_dropped_rows_per_job``: rows routed to an expert held that no expert
computed, per step, from the job's ``moe_dropped_rows`` counter over the
window.  Expected 0: the sorted dispatch has no capacity.  Layer: model layers."""


def read(ctx):
    dropped = ctx.counters.get("moe_dropped_rows")
    return dropped / len(ctx.samples) if dropped is not None and ctx.samples else None
