"""``collective_ms_per_job``: device time inside all-gather, all-to-all,
all-reduce, reduce-scatter and collective-permute operations per job, mean
over the chips.  0 on one chip.  Layer: comm."""

import statistics

from chipbench.harness import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = tr.window(ctx.trace)
    per_chip = statistics.fmean(tr.collective_time(d, lo, hi)[0] for d in ctx.trace.devices)
    return per_chip / len(tr.jobs(ctx.trace)) / 1e6
