"""The closed loop: one caller, the next job after the last has finished."""

from __future__ import annotations

import sys
import time
import traceback

import jax
import numpy as np

from . import trace as tr


def timed(job, state) -> tuple:
    """One job from the call to ``block_until_ready`` on all it returns,
    inside the annotation the trace reduction finds jobs by."""
    with jax.profiler.TraceAnnotation(tr.JOB_SPAN):
        t0 = time.perf_counter()
        out = jax.block_until_ready(job(state))
        return time.perf_counter() - t0, out


class Window:
    """Samples of ``job_s`` and the count of jobs that raised."""

    def __init__(self):
        self.samples, self.failed, self.last = [], 0, None

    @property
    def attempted(self) -> int:
        return len(self.samples) + self.failed

    def run(self, job, state, seconds: float = 0.0, jobs: int = 0) -> "Window":
        """Jobs back to back until ``seconds`` have passed and ``jobs`` ran."""
        until = time.perf_counter() + seconds
        done = 0
        while done < jobs or time.perf_counter() < until:
            done += 1
            try:
                dt, self.last = timed(job, state)
                self.samples.append(dt)
            except Exception:
                traceback.print_exc(file=sys.stderr)
                self.failed += 1
                if self.failed > 3:
                    raise
        return self


def quantiles(samples) -> dict:
    p25, p50, p75, p90 = (float(v) for v in np.percentile(samples, [25, 50, 75, 90]))
    return {"n": len(samples), "min": min(samples), "p25": p25, "p50": p50, "p75": p75,
            "p90": p90, "max": max(samples)}
