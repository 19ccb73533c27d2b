"""``moe_rows_per_job``: rows routed to the experts held in one step, all
expert layers together, from the job's ``moe_rows`` counter over the window.
Layer: model layers."""


def read(ctx):
    rows = ctx.counters.get("moe_rows")
    return rows / len(ctx.samples) if rows is not None and ctx.samples else None
