"""``launch_gap_us``: median gap on the device between the end of one program
and the start of the next inside a job, all chips pooled.  Layer: dispatch."""

import statistics

from chipbench.harness import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    spans = tr.jobs(ctx.trace)
    gaps = [g for d in ctx.trace.devices for g in tr.launch_gaps(d, spans)]
    return statistics.median(gaps) / 1e3 if gaps else None
