"""``moe_load_max_over_mean``: the fullest held expert's rows over the mean
held expert's, averaged over the window's steps and expert layers (the job's
``moe_fullest_expert_rows``, ``moe_rows`` and ``moe_expert_layers`` counters).
1 is an even load.  Layer: model layers."""


def read(ctx):
    c = ctx.counters
    rows, layers = c.get("moe_rows"), c.get("moe_expert_layers")
    if not rows or not layers or "moe_fullest_expert_rows" not in c:
        return None
    return c["moe_fullest_expert_rows"] / (rows / ctx.config["num_experts"])
