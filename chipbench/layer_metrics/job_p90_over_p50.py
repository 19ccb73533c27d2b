"""``job_p90_over_p50``: the 90th percentile of ``job_s`` over its median in
the window that ran with tracing off.  A traced run's window holds at least
three jobs and as many more as fit into what the trace left of ``--seconds``;
under ten jobs the percentile is an interpolation between the slowest few and
reads coarsely.  Layer: dispatch."""

from chipbench.harness.window import quantiles


def read(ctx):
    if not ctx.samples:
        return None
    q = quantiles(ctx.samples)
    return q["p90"] / q["p50"]
