"""``strong_scaling_eff``: per cent of perfect scaling, ``t1 / (chips * tN)``:
``t1`` is the same job's median on a one-device communicator, taken after the
window of the traced run where the traffic mix sets
``scaling_reference_jobs``; ``tN`` is the window's median.  Layer: comm."""

import statistics


def read(ctx):
    t1 = ctx.counters.get("scaling_reference_job_s")
    if t1 is None or not ctx.samples:
        return None
    return 100.0 * t1 / (ctx.chips * statistics.median(ctx.samples))
