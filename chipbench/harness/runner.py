"""One run of one cell: set-up, warm-up, the window, the check, the result.

``run_cell`` is what ``run.py`` calls on the chip and what the tests call on
the CPU with tiny configurations.  It prints its account of the run on
earlier lines (each starts with ``#``) and returns the object that ``run.py``
prints as the last line.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import tempfile
import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh

from . import device, roofline
from . import trace as tr
from .window import Window, quantiles, timed

GIB = 2.0 ** 30
# the least the ordinary window of a traced run holds, however long the
# trace took to record and reduce
MIN_WINDOW_JOBS = 3


@dataclass
class Context:
    """What a layer-metric reader may read.  The cell, configuration and
    traffic mix are here for the readers later PRs add, which may not edit
    this file."""

    cell: dict
    config: dict
    traffic: dict
    chips: int
    work: dict
    samples: list
    trace: object = None
    peaks: object = None
    counters: dict = field(default_factory=dict)


def _entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def _record(job, state, n_jobs: int, into: Window) -> tr.Trace:
    """Trace ``n_jobs`` jobs, reduce the trace and remove it."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    where = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(where, profiler_options=options)
        try:
            into.run(job, state, jobs=n_jobs)
        finally:
            jax.profiler.stop_trace()
        into.last = None  # or the last traced job's results would count in the peak
        return tr.load_xplane(tr.find_xplane(where))
    finally:
        shutil.rmtree(where, ignore_errors=True)


def _on_one_chip(job, config, traffic, seed, comm, n_jobs: int) -> float:
    """Median ``job_s`` of the same job on a one-device communicator over the
    first chip, built as ``MPI_SELF`` is."""
    from heat_tpu.core.communication import Communication

    alone = Communication(Mesh(np.asarray(comm.mesh.devices.flat[:1]), (comm.axis,)), comm.axis)
    state = job.setup(config, traffic, seed, alone)
    # no result outlives its job here: the first chip also holds its share of
    # the cell's own operands; the first job warms up
    samples = [timed(job.job, state)[0] for _ in range(n_jobs + 1)]
    return statistics.median(samples[1:])


def run_cell(manifest, name: str, *, seed: int, seconds: float, trace: bool,
             comm=None, peaks=None, cache_dir=None, t_start=None, config=None,
             traffic=None, keep_trace=None, say=print) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    import heat_tpu as ht
    from heat_tpu.core import _cache

    cell = manifest.cell(name)
    config = manifest.config(cell) if config is None else config
    traffic = manifest.traffic(cell) if traffic is None else traffic
    job = manifest.job(traffic["job"])
    comm = ht.get_comm() if comm is None else comm
    devices = list(comm.mesh.devices.flat)
    work = job.work(config, traffic, len(devices))

    # set-up: the data from the seed, then every shape the window will use
    t_ready = time.perf_counter()
    state = job.setup(config, traffic, seed, comm)
    jax.block_until_ready(jax.tree.leaves(vars(state)))
    t_data = time.perf_counter()
    peak_data = device.memory_peak_bytes(devices)
    warm_s = Window().run(job.job, state, jobs=traffic["warmup_jobs"]).samples
    peak_warm = device.memory_peak_bytes(devices)
    misses, files = _cache.cache_stats()["misses"], _entries(cache_dir)
    setup_s = time.perf_counter() - t_start
    say(f"# cell={name} job={traffic['job']} seed={seed} chips={len(devices)} "
        f"setup_s={setup_s:.3f}: before_data={t_ready - t_start:.3f} "
        f"data={t_data - t_ready:.3f} warmup_jobs={[round(s, 4) for s in warm_s]} "
        f"compile_cache_entries={files}")

    # the measured window; in a traced run the first jobs of it are traced
    t_window = time.perf_counter()
    traced, recorded = Window(), None
    if trace:
        recorded = _record(job.job, state, traffic["traced_jobs"], traced)
        if keep_trace:
            tr.save(recorded, keep_trace)
        lead = tr.clock_lead(recorded)
        say(f"# trace: the device's clock is {lead} ns ahead of the host's"
            if lead is not None else "# trace: device and host clocks not matched")
        recorded = tr.calibrate(recorded)
    left = seconds - (time.perf_counter() - t_window)
    window = Window().run(job.job, state, seconds=left, jobs=MIN_WINDOW_JOBS if trace else 1)
    peak_window = device.memory_peak_bytes(devices)
    counters = {
        "program_cache_misses": _cache.cache_stats()["misses"] - misses,
        "compile_cache_files_added": _entries(cache_dir) - files,
    }
    ok, facts = job.check(state, window.last)
    window.last = None
    if trace and traffic.get("scaling_reference_jobs"):
        counters["scaling_reference_job_s"] = _on_one_chip(
            job, config, traffic, seed, comm, traffic["scaling_reference_jobs"])
    failed = window.failed + traced.failed
    q = quantiles(window.samples)
    say("# window " + " ".join(f"{k}={v:.6g}" for k, v in q.items()))
    _say_derived(say, work, q["p50"], len(devices), peaks)
    say(f"# peak_bytes after_data={peak_data} after_warmup={peak_warm} "
        f"after_window={peak_window} data_below_window={peak_data < peak_window}")
    say(f"# counters {json.dumps(counters)} compile_cache_entries_at_end={_entries(cache_dir)}")
    say(f"# check correct={ok} {json.dumps(facts)}")
    if traced.samples:
        say(f"# traced jobs={len(traced.samples)} job_s_p50={statistics.median(traced.samples):.6g} "
            f"against {q['p50']:.6g} in the window that followed, tracing off")

    ctx = Context(cell, config, traffic, len(devices), work, window.samples,
                  recorded, peaks, counters)
    if trace:
        values = {m["name"]: manifest.reader(m["name"])(ctx)
                  for m in manifest.metrics("per_layer", name)}
        for metric in [k for k, v in values.items() if v is None]:
            say(f"# left out: the reader of {metric} found nothing to read")
    else:
        # the CPU reports no memory peak: the metric is then left out
        values = {"job_s": q["p50"], "setup_s": setup_s,
                  "peak_hbm_gib": peak_window / GIB if peak_window else None}
    units = {m["name"]: m["unit"]
             for m in manifest.metrics("per_layer" if trace else "end_to_end", name)}
    result = {
        "correct": bool(ok) and failed == 0,
        "attempted": window.attempted + traced.attempted,
        "failed": failed,
        "metrics": {k: {"value": values[k], "unit": u}
                    for k, u in units.items() if values.get(k) is not None},
        "device": {**device.describe(devices), "memory_peak_bytes": peak_window},
    }
    if recorded is not None and recorded.devices:
        busy_s, window_s = tr.busy_seconds(recorded)
        result["device"].update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = tr.breakdown(recorded)
    return result


def _say_derived(say, work: dict, job_s: float, chips: int, peaks) -> None:
    """Numbers a reader wants that follow from ``job_s``: not metrics."""
    parts = [f"{k.replace('_per_job', '_per_s')}={v / job_s:.6g}"
             for k, v in work["derived"].items()]
    if work["flop"]:
        parts.append(f"tflops_per_chip={work['flop'] / job_s / chips / 1e12:.6g}")
    if peaks is not None:
        least, bound = roofline.least_seconds(work, peaks, chips)
        parts.append(f"roofline_s={least:.6g} bound={bound} "
                     f"share_of_job_s={least / job_s:.4f}")
    say("# derived " + " ".join(parts))
