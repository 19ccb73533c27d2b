"""A job's share of its roofline: the least time the chips could take for it
over the time they were busy with it."""

from __future__ import annotations

import statistics

from . import trace as tr


def least_seconds(work: dict, peaks: dict, chips: int) -> tuple:
    """``(seconds, which bound)``: the larger of operations over the peak
    rate and bytes over the peak bandwidth, the work spread evenly over the
    chips."""
    compute = work["flop"] / chips / peaks["bf16_flops_per_s"]
    memory = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def job_share(ctx):
    """Per cent of the roofline, from the traced jobs' device-busy time."""
    if ctx.trace is None or not ctx.trace.devices or ctx.peaks is None:
        return None
    lo, hi = tr.window(ctx.trace)
    busy = statistics.fmean(tr.total(tr.busy(d, lo, hi)) for d in ctx.trace.devices)
    if busy <= 0:
        return None
    least, _ = least_seconds(ctx.work, ctx.peaks, ctx.chips)
    return 100.0 * least / (busy / 1e9 / len(tr.jobs(ctx.trace)))
