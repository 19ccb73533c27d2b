"""``moonlight_16b_a3b_train_4x8k_mfu``: per cent of the chip's bf16 peak that
a training step reaches: the model operations of
``jobs/moonlight_train_step.py``'s ``work()`` (6 a parameter a token, the
shared experts among them, the causal pairs latent attention keeps, the
experts; recomputation not counted) at the peak rate, over the device-busy
time of a traced step.  The routed experts' part is counted at the rows that
the window's steps really routed to the experts held (the job's ``moe_rows``
counter), as ``moe_experts_roofline`` counts it.  Layer: trainers."""

import dataclasses

from chipbench.harness import roofline


def read(ctx):
    rows = ctx.counters.get("moe_rows")
    if not rows or not ctx.samples:
        return None
    d, width = ctx.config["hidden_size"], ctx.config["moe_intermediate_size"]
    expected = ctx.work["kernels"]["moe_experts"]["flop"]
    counted = 6 * 3 * d * width * rows / len(ctx.samples)
    work = {**ctx.work, "flop": ctx.work["flop"] - expected + counted}
    return roofline.job_share(dataclasses.replace(ctx, work=work))
