"""``launches_per_job``: program executions on the device per job, from the
trace's "XLA Modules" line, mean over the chips.  Layer: estimators."""

import statistics

from chipbench.harness import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = tr.window(ctx.trace)
    per_chip = statistics.fmean(tr.launches(d, lo, hi) for d in ctx.trace.devices)
    return per_chip / len(tr.jobs(ctx.trace))
