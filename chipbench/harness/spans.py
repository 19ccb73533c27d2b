"""From the program's own spans in a trace to where a job's host time goes.

While a profile records, heat_tpu annotates its dispatch layer (``core/
_cache.py``, ``core/_operations.py``, ``linalg/basics.py``): one span
``ht.dispatch.<kind>`` (local, binary, reduce, cum, matmul) from the entry of
a dispatch helper to its return, and inside it one ``ht.dispatch.launch``
around the call of the cached program, which is jax's jit call and holds the
runtime's launch.  ``harness/trace`` keeps every ``ht.`` annotation, so over
one traced job ``J`` (a ``bench.job`` span), with ``Q`` the union of the launch
spans inside it and ``D`` the union of the kind spans, the job's host time
splits three ways, exactly:

    jit_call        the length of Q
    dispatch_py     the length of D less Q: plan, cache lookup, hooks, wrap
    above_dispatch  the length of J less D and Q: library code above the
                    dispatch layer, jnp's op-by-op calls, the job's last wait

and the launches the program cache never saw are the programs that ran on the
chip during ``J`` (mean over chips) less the launch spans that start in ``J``.
A trace with no program span in it (a program older than the spans) reads
0, 0, the whole job, and every launch; a trace with no device plane or no job
reads nothing.
"""

from __future__ import annotations

import statistics

from . import trace as tr

DISPATCH = "ht.dispatch."
LAUNCH = "ht.dispatch.launch"


def job_split(trace, job) -> tuple:
    """``(jit_call_ns, dispatch_py_ns, above_dispatch_ns, launch_spans)`` of one job."""
    lo, hi = job.start, job.end
    inside = [e for e in trace.host
              if e.name.startswith(DISPATCH) and e.end > lo and e.start < hi]
    launch = [e for e in inside if e.name == LAUNCH]
    calls = tr.merge(tr.clip([(e.start, e.end) for e in launch], lo, hi))
    covered = tr.merge(tr.clip([(e.start, e.end) for e in inside], lo, hi))
    return (tr.total(calls), tr.total(tr.subtract(covered, calls)),
            tr.total(tr.subtract([(lo, hi)], covered)),
            sum(1 for e in launch if lo <= e.start < hi))


def per_job(ctx):
    """The four metrics' values, each the mean over the traced jobs, or
    ``None`` where no job of the trace put a program on a chip."""
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    jobs = tr.jobs(trace)
    on_chip = [statistics.fmean(tr.launches(d, j.start, j.end) for d in trace.devices)
               for j in jobs]
    if not any(on_chip):
        return None
    splits = [job_split(trace, j) for j in jobs]
    return {
        "jit_call_ms_per_job": statistics.fmean(s[0] for s in splits) / 1e6,
        "dispatch_py_ms_per_job": statistics.fmean(s[1] for s in splits) / 1e6,
        "above_dispatch_ms_per_job": statistics.fmean(s[2] for s in splits) / 1e6,
        "uncached_launches_per_job": statistics.fmean(
            n - s[3] for n, s in zip(on_chip, splits)),
    }


def read(ctx, metric: str):
    values = per_job(ctx)
    return None if values is None else values[metric]
