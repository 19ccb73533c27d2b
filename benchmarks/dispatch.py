"""Op-dispatch microbenchmark: program-cache latency, hit rate, donation.

The zero-copy dispatch claim, measured (ISSUE 1 acceptance):

- ``dispatch_cached_latency_us`` — wall time of a repeated same-signature
  binary op through the sharding-keyed program cache (one compiled
  executable per ``(op, avals, split)``, output sharding compiled in);
- ``dispatch_eager_reference_latency_us`` — the SEED dispatch tail for the
  same op (eager jnp call + post-hoc ``comm.shard`` placement + metadata
  recompute), timed side by side so the speedup is self-contained;
- ``dispatch_overhead_us`` / ``dispatch_eager_reference_overhead_us`` —
  the same two paths with the compiled-program floor (a pre-built jitted
  add on the raw arrays, timed in-run) subtracted: pure Python dispatch
  cost, independent of how fast this host executes the op itself.  The
  seed measured ~230-470 us/op here; the cached path ~50 us/op
  (interleaved A/B on the 8-device host mesh, 2026-08-03);
- ``recompilations_100_ops`` / ``cache_hit_rate`` — program-cache misses
  across 100 repeated same-signature ops after warmup (target: 0 / ≥0.99);
- ``resplit_inplace_latency_us`` vs ``resplit_copy_latency_us`` and the
  peak-RSS of a large in-place redistribution with the source buffer
  donated vs the copying form.

Run: python benchmarks/dispatch.py [--out PATH] [--size N] [--reps R]
Writes a ``scripts/bench_compare.py``-consumable payload (committed
capture: ``BENCH_DISPATCH.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _time_interleaved(fns, sync, reps, batch=20):
    """Per-round per-call wall times (µs) of each fn, measured in
    INTERLEAVED rounds so drifting host load hits every path equally (the
    round-5 lesson: ordered one-shot timings produced phantom winners).
    Each round dispatches ``batch`` calls and syncs once.  Returns a list
    of per-round sample lists, one per fn."""
    samples = [[] for _ in fns]
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            out = None
            for _ in range(batch):
                out = fn()
            sync(out)
            samples[i].append((time.perf_counter() - t0) / batch * 1e6)
    return samples


def _mins(samples):
    return [min(s) for s in samples]


def _paired_delta(a, b):
    """Median of the PER-ROUND differences a_i − b_i: the two paths ran
    back-to-back each round, so host-load swings cancel pairwise — the
    robust estimator of pure overhead above a measured floor."""
    d = sorted(x - y for x, y in zip(a, b))
    return max(d[len(d) // 2], 0.0)


def _time_op(fn, sync, reps):
    return _mins(_time_interleaved([fn], sync, reps))[0]


def _rotated_hook_gate(floor_fn, off_fn, off2_fn, on_fn, sync, reps):
    """The shared measurement of the hook gates (flightrec, memledger):
    rotated pairwise rounds hardened for cpu-quota-throttled hosts.
    (1) the three hook states ROTATE through the round positions (the
    later path in a round is systematically slower as quota decays, and a
    fixed order biases the delta positive); (2) an off-vs-off NULL in the
    same rounds sets the noise floor — a measurement cannot assert a
    regression below its own noise; (3) the on-vs-off paired deltas must
    shift WHOLESALE (q25 > 0) before a gate may fail: a real regression
    taxes every round, symmetric scheduler noise cannot.  Returns
    ``(off_above_floor_us, added_us, noise_floor_us, consistent,
    added_pct)``."""
    s_floor, s_off, s_off2, s_on = [], [], [], []
    rotation = [(off_fn, s_off), (off2_fn, s_off2), (on_fn, s_on)]
    for i in range(reps):
        order = rotation[i % 3:] + rotation[: i % 3]
        for fn, out_samples in [(floor_fn, s_floor)] + order:
            t0 = time.perf_counter()
            out = None
            for _ in range(20):
                out = fn()
            sync(out)
            out_samples.append((time.perf_counter() - t0) / 20 * 1e6)
    off_oh = max(_paired_delta(s_off, s_floor), 1.0)
    added_us = _paired_delta(s_on, s_off)
    d_null = sorted(a - b for a, b in zip(s_off2, s_off))
    noise_us = abs(d_null[len(d_null) // 2])
    d_on = sorted(a - b for a, b in zip(s_on, s_off))
    consistent = d_on[len(d_on) // 4] > 0.0
    return off_oh, added_us, noise_us, consistent, added_us / off_oh * 100.0


def _peak_rss_subprocess(mode: str, size: int) -> float:
    """Peak RSS (MB) of one resplit of a (size, size) f32 array, measured in
    a fresh process so allocator history doesn't pollute the peak."""
    code = f"""
import os, resource, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import heat_tpu as ht
x = ht.zeros(({size}, {size}), split=0)
x += 1.0  # touch every page
# memory_budget=0 pins the MONOLITHIC path regardless of any
# HEAT_TPU_RESPLIT_BUDGET / process default in the inherited env —
# these rows are labeled monolithic and must measure it
if {mode!r} == "inplace":
    x.resplit_(1, memory_budget=0)       # donating path
    out = x
else:
    out = x.resplit(1, memory_budget=0)  # copying path (source stays live)
ht.utils.profiler.sync(out)
print(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0)
"""
    try:
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, timeout=300
        )
        return float(r.stdout.strip().splitlines()[-1])
    except Exception:
        return float("nan")


def _peak_rss_resplit(shape, budget_bytes, mode: str) -> dict:
    """Budgeted-resplit peak-RSS capture in a fresh process: build a 3-d
    f32 array split 0, touch every page, record the pre-transfer RSS
    high-water mark (``base``, source included), resplit to split 1 under
    ``budget_bytes`` (``mode='budgeted'``) or monolithically
    (``mode='copy'``/``'inplace'``), and report the post-transfer peak plus
    the plan shape read back from the telemetry counters."""
    code = f"""
import json, os, resource, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import heat_tpu as ht
from heat_tpu.utils import profiler
shape, budget, mode = {tuple(shape)!r}, {int(budget_bytes)}, {mode!r}
x = ht.zeros(shape, split=0)
x += 1.0  # touch every page
jax.block_until_ready(x._parray)
base_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
profiler.reset_counters()
if mode == "budgeted":
    x.resplit_(1, memory_budget=budget)
    out = x
elif mode == "inplace":
    # memory_budget=0 pins the monolithic path even when the inherited env
    # carries HEAT_TPU_RESPLIT_BUDGET — the comparison row must not stream
    x.resplit_(1, memory_budget=0)
    out = x
else:
    out = x.resplit(1, memory_budget=0)
jax.block_until_ready(out._parray)
c = profiler.counters()
print(json.dumps({{
    "base_mb": base_mb,
    "peak_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
    "tiles": c.get("comm.resplit.tiles", 0),
    "peak_tile_bytes": c.get("comm.resplit.peak_tile_bytes", 0),
    "resplit_bytes": c.get("comm.resplit.bytes", 0),
}}))
"""
    r = None
    try:
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, timeout=600
        )
        return json.loads(r.stdout.strip().splitlines()[-1])
    except Exception as exc:
        # surface the capture's own diagnostics: a NaN payload without them
        # reads as "planner fell back to monolithic?" when the subprocess
        # actually died of an import error / OOM kill / timeout
        print(f"resplit RSS capture ({mode}) failed: {exc!r}", file=sys.stderr)
        if r is not None:
            print(f"  returncode={r.returncode}", file=sys.stderr)
            if r.stderr:
                print(r.stderr[-2000:], file=sys.stderr)
        return {"base_mb": float("nan"), "peak_mb": float("nan"), "tiles": 0,
                "peak_tile_bytes": 0, "resplit_bytes": 0}


def _overlap_capture(steps: int, warmup: int, budget: str) -> dict:
    """Rotated-pairwise DASO sync comparison, measured in a fresh process:
    two overlapped-sync DASO arms share one process — ``monolithic`` pins
    the single-bucket plan (budget 0), ``bucketed`` splits the sync under
    ``budget`` — and their steps are interleaved in alternating AB/BA order
    so scheduler drift cancels.  Per step: wall time (with the mpdryrun
    lockstep ``comm.Wait(loss)`` fence) and the guarded blocking-wait
    seconds (``comm.allreduce.wait`` + ``comm.Wait.wait`` histograms, which
    is what ``scripts/stepprof.py`` attributes too); overlap fraction =
    1 − wait/step.  Also captured: the per-arm ``comm.allreduce.bytes``
    deltas (the byte-invariance contract) and the steady-state program-
    cache stats after warmup (the zero-recompile contract)."""
    code = f"""
import json, os, statistics, sys, time
os.environ.pop("HEAT_TPU_GRAD_BUCKET_BYTES", None)  # arms pin their own plans
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import numpy as np
import heat_tpu as ht
from heat_tpu.utils import profiler, telemetry

steps, warmup, budget = {int(steps)}, {int(warmup)}, {budget!r}
telemetry.enable()  # arms the wait observer guard_blocking feeds

def build(bucket_budget):
    model = ht.nn.Sequential(
        ht.nn.Flatten(), ht.nn.Linear(128, 512), ht.nn.ReLU(),
        ht.nn.Linear(512, 128),
    )
    daso = ht.optim.DASO(
        ht.optim.DataParallelOptimizer("sgd", lr=0.05),
        total_local_comm_size=2,
        warmup_steps=0, global_skip=1, stale_steps=0,
        overlap_sync=True, grad_bucket_bytes=bucket_budget,
    )
    daso.init(model, key=jax.random.key(3))
    return daso

def mse(pred, y):
    return jax.numpy.mean((pred - y) ** 2)

def wait_s():
    return (telemetry.histogram("comm.allreduce.wait").total
            + telemetry.histogram("comm.Wait.wait").total)

comm = ht.communication.get_comm()
rng = np.random.default_rng(11)

def step(daso):
    x = jax.numpy.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    y = jax.numpy.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    w0, t0 = wait_s(), time.perf_counter()
    loss = daso.step(mse, x, y)
    comm.Wait(loss)  # lockstep fence: the wait lands in comm.Wait.wait
    return time.perf_counter() - t0, wait_s() - w0

# budget 0 parses to None -> the forced single-bucket (monolithic) plan
arms = [("monolithic", build(0)), ("bucketed", build(budget))]
for _, d in arms:
    for _ in range(warmup):
        step(d)
profiler.reset_cache_stats()
rows = {{name: [] for name, _ in arms}}
bytes_delta = {{name: 0 for name, _ in arms}}
for i in range(steps):
    for name, d in (arms if i % 2 == 0 else arms[::-1]):
        c0 = profiler.counters().get("comm.allreduce.bytes", 0)
        rows[name].append(step(d))
        bytes_delta[name] += (
            profiler.counters().get("comm.allreduce.bytes", 0) - c0
        )
stats = profiler.cache_stats()

def med_overlap(rs):
    return statistics.median(1.0 - min(w, dt) / dt for dt, w in rs)

print(json.dumps({{
    "overlap": {{k: round(med_overlap(v), 4) for k, v in rows.items()}},
    "step_ms": {{k: round(statistics.median(dt for dt, _ in v) * 1e3, 3)
                for k, v in rows.items()}},
    "wait_ms": {{k: round(statistics.median(w for _, w in v) * 1e3, 3)
                for k, v in rows.items()}},
    "allreduce_bytes": bytes_delta,
    "n_buckets": {{name: d._overlap_state()[1].n_buckets for name, d in arms}},
    "steady_cache_misses": stats["misses"],
    "steady_cache_hits": stats["hits"],
}}))
"""
    r = None
    try:
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600,
        )
        return json.loads(r.stdout.strip().splitlines()[-1])
    except Exception as exc:
        print(f"overlap capture failed: {exc!r}", file=sys.stderr)
        if r is not None:
            print(f"  returncode={r.returncode}", file=sys.stderr)
            if r.stderr:
                print(r.stderr[-2000:], file=sys.stderr)
        return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write payload JSON here")
    ap.add_argument("--size", type=int, default=256, help="square op size")
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--skip-rss", action="store_true",
                    help="skip the subprocess peak-memory captures")
    ap.add_argument("--telemetry-gate", type=float, default=None, metavar="PCT",
                    help="exit 4 if telemetry-on adds more than PCT%% to the "
                         "dispatch cost above the compiled-program floor "
                         "(the CI telemetry lane's 5%% overhead contract)")
    ap.add_argument("--flightrec-gate", type=float, default=None, metavar="PCT",
                    help="exit 6 if the armed flight recorder adds more than "
                         "PCT%% to the dispatch cost above the compiled-program "
                         "floor (the ISSUE 7 crash-durable-ring overhead "
                         "contract; same pairwise methodology as the "
                         "telemetry gate)")
    ap.add_argument("--monitor-gate", type=float, default=None, metavar="PCT",
                    help="exit 7 if dispatch under an ARMED + actively "
                         "scraped /metrics monitor costs more than PCT%% "
                         "above the unscraped dispatch cost over the "
                         "compiled floor (the ISSUE 11 live-endpoint "
                         "contract; same pairwise methodology as the "
                         "telemetry gate — the monitor adds NO hot-path "
                         "hook, so this measures pure scrape-thread "
                         "interference)")
    ap.add_argument("--memledger-gate", type=float, default=None, metavar="PCT",
                    help="exit 8 if the armed device-memory ledger adds more "
                         "than PCT%% to the dispatch cost above the "
                         "compiled-program floor (the ISSUE 14 per-buffer "
                         "registration overhead contract; same rotated "
                         "pairwise methodology + off-vs-off noise floor + "
                         "q25 wholesale-shift guard as the flightrec gate; "
                         "the disarmed path stays ONE module-global load by "
                         "construction)")
    ap.add_argument("--resplit-gate", action="store_true",
                    help="run the budgeted-resplit peak-RSS gate: exit 5 when "
                         "the chunked pipeline's peak RSS exceeds "
                         "base + destination + budget + one tile (+ slack)")
    ap.add_argument("--resplit-out", default=None, metavar="PATH",
                    help="write the resplit-gate payload here "
                         "(committed capture: BENCH_RESPLIT.json)")
    ap.add_argument("--resplit-shape", type=int, nargs=3, default=(1024, 1024, 16),
                    metavar=("R", "C", "D"),
                    help="3-d f32 array for the resplit gate (split 0 -> 1, "
                         "tiled along axis 2); default 64 MB")
    ap.add_argument("--resplit-budget-mb", type=float, default=16.0,
                    help="per-step byte budget for the gate")
    ap.add_argument("--resplit-slack-mb", type=float, default=48.0,
                    help="allocator/runtime slack added to the gate bound "
                         "(XLA CPU working memory + per-plan compile spikes "
                         "are not byte-exact; 48 MB keeps the gate below the "
                         "64 MB whole-array-staging regression it exists to "
                         "catch)")
    ap.add_argument("--overlap-gate", action="store_true",
                    help="run the ISSUE 16 overlapped-sync gate: exit 9 "
                         "unless the bucketed lookahead-1 DASO sync beats "
                         "the single-bucket (monolithic) sync on median "
                         "compute/comm overlap fraction in a rotated "
                         "pairwise short training loop, with byte-identical "
                         "comm.allreduce.bytes and zero steady-state "
                         "recompiles")
    ap.add_argument("--overlap-out", default=None, metavar="PATH",
                    help="write the overlap-gate payload here "
                         "(committed capture: BENCH_OVERLAP.json)")
    ap.add_argument("--overlap-steps", type=int, default=24,
                    help="measured rotated step pairs for the overlap gate")
    ap.add_argument("--overlap-warmup", type=int, default=6,
                    help="per-arm warmup steps (compiles the bucket "
                         "programs) before the overlap gate measures")
    ap.add_argument("--overlap-budget", default="256K",
                    help="grad-bucket budget of the bucketed arm (K/M/G "
                         "suffixes; the monolithic arm always pins the "
                         "single-bucket plan)")
    args = ap.parse_args(argv)

    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.utils import profiler, telemetry

    # the contract rows below are measured with telemetry OFF regardless of
    # how the job armed the env (HEAT_TPU_TELEMETRY=1 in the CI telemetry
    # lane): the committed BENCH_DISPATCH payload is a telemetry-off
    # capture, and the on-vs-off question has its own section below
    telemetry_armed = telemetry.enabled()
    telemetry.disable()

    comm = ht.communication.get_comm()
    n_dev = comm.size
    platform = comm.mesh.devices.flat[0].platform
    sync = profiler.sync
    n = args.size

    x = ht.random.randn(n, n, split=0)
    y = ht.random.randn(n, n, split=0)

    # --- compiled-program floor ---------------------------------------- #
    # a pre-built jitted (add + placement) on the raw arrays: the fastest
    # any dispatch layer could possibly go on this host.  Subtracted from
    # the measured paths to isolate pure Python dispatch overhead.
    j1, j2 = x._jarray, y._jarray
    floor_prog = jax.jit(lambda a, b: comm.shard(jnp.add(a, b), 0))

    # the seed dispatch, measured in-process: _FORCE_SLOW routes _binary_op
    # through its general path, which is the pre-cache implementation
    # preserved verbatim (metadata recompute + eager jnp op + post-hoc
    # placement + full wrap)
    from heat_tpu.core import _operations

    def eager_reference():
        _operations._FORCE_SLOW = True
        try:
            return x + y
        finally:
            _operations._FORCE_SLOW = False

    floor_prog(j1, j2)
    _ = x + y  # build + compile the cached program once
    eager_reference()
    s_floor, s_cached, s_eager = _time_interleaved(
        [lambda: floor_prog(j1, j2), lambda: x + y, eager_reference],
        sync,
        args.reps,
    )
    floor_us, cached_us, eager_us = (min(s_floor), min(s_cached), min(s_eager))
    overhead_us = _paired_delta(s_cached, s_floor)
    eager_overhead_us = _paired_delta(s_eager, s_floor)

    # --- telemetry-on dispatch overhead (ISSUE 3 contract) ------------- #
    # same interleaved paired-delta methodology: cached dispatch with the
    # telemetry hook disarmed vs armed, each vs the compiled floor.  The
    # toggle is the raw _operations module global (exactly what enable()/
    # disable() poke) so both timed paths carry identical toggle cost.
    from heat_tpu.core import _operations as _ops

    telemetry.enable()   # arm the recording machinery (ring etc.)

    def cached_tel_off():
        _ops._TELEMETRY = None
        return x + y

    def cached_tel_on():
        _ops._TELEMETRY = telemetry
        return x + y

    cached_tel_on()
    cached_tel_off()
    s_floor2, s_tel_off, s_tel_on = _time_interleaved(
        [lambda: floor_prog(j1, j2), cached_tel_off, cached_tel_on],
        sync,
        args.reps,
    )
    _ops._TELEMETRY = None
    telemetry.disable()
    # the ADDED cost is the direct pairwise on-vs-off delta (same round,
    # back-to-back): host-load swings cancel without routing through the
    # floor twice; the floor delta only normalizes it into a percentage
    tel_off_oh = max(_paired_delta(s_tel_off, s_floor2), 1.0)
    tel_added_us = _paired_delta(s_tel_on, s_tel_off)
    tel_added_pct = tel_added_us / tel_off_oh * 100.0

    # --- flight-recorder-on dispatch overhead (ISSUE 7 contract) ------- #
    # identical methodology: cached dispatch with the flightrec hook
    # disarmed vs armed (a REAL mmap ring in a tmpdir — the armed path pays
    # the full record_dispatch cost: the coalescing per-op counter bump,
    # with ring writes deferred to full-record boundaries), paired against
    # the compiled floor in the same interleaved rounds.
    import shutil
    import tempfile

    from heat_tpu.utils import flightrec

    fr_ring_dir = tempfile.mkdtemp(prefix="bench_flightrec_")
    flightrec.enable(fr_ring_dir, rank=0)

    def cached_fr_off():
        _ops._FLIGHTREC = None
        return x + y

    def cached_fr_on():
        _ops._FLIGHTREC = flightrec
        return x + y

    def cached_fr_off2():  # second, identical off path: the NULL measurement
        _ops._FLIGHTREC = None
        return x + y

    cached_fr_on()
    cached_fr_off()
    # rotated pairwise + null + q25 wholesale-shift guard — the shared
    # throttled-host hardening, see _rotated_hook_gate
    fr_off_oh, fr_added_us, fr_noise_us, fr_consistent, fr_added_pct = (
        _rotated_hook_gate(
            lambda: floor_prog(j1, j2), cached_fr_off, cached_fr_off2,
            cached_fr_on, sync, args.reps,
        )
    )
    _ops._FLIGHTREC = None
    flightrec.disable()
    shutil.rmtree(fr_ring_dir, ignore_errors=True)

    # --- memory-ledger-armed dispatch overhead (ISSUE 14 contract) ----- #
    # identical rotated-pairwise methodology to the flightrec gate: cached
    # dispatch with the ledger hooks disarmed vs armed, an off-vs-off null
    # in the same rounds as the noise floor, and the q25 wholesale-shift
    # guard.  What the armed path pays HERE is exactly what production's
    # hot loop pays: these 256 KiB outputs sit under the 1 MiB dispatch
    # threshold, so each dispatch is register_dispatch's COALESCED tier —
    # one call + aval byte math + a counter bump (the flightrec cost
    # class).  The full register() path (weakref + entry + provenance,
    # ~5 µs) is deliberately NOT gated at 5%: it runs only for ≥1 MiB
    # buffers, where microseconds amortize against megabyte lifetimes —
    # its correctness (and cost class) is pinned by tests/test_memledger
    # instead.  BOTH hook modules toggle (dispatch tail + _from_parts).
    ml_added_pct = ml_added_us = ml_off_oh = ml_noise_us = float("nan")
    ml_consistent = False
    if args.memledger_gate is not None:
        from heat_tpu.core import dndarray as _dnd
        from heat_tpu.utils import memledger

        memledger.enable()

        def cached_ml_off():
            _ops._MEMLEDGER = None
            _dnd._MEMLEDGER = None
            return x + y

        def cached_ml_on():
            _ops._MEMLEDGER = memledger
            _dnd._MEMLEDGER = memledger
            return x + y

        def cached_ml_off2():  # second, identical off path: the NULL
            _ops._MEMLEDGER = None
            _dnd._MEMLEDGER = None
            return x + y

        cached_ml_on()
        cached_ml_off()
        ml_off_oh, ml_added_us, ml_noise_us, ml_consistent, ml_added_pct = (
            _rotated_hook_gate(
                lambda: floor_prog(j1, j2), cached_ml_off, cached_ml_off2,
                cached_ml_on, sync, args.reps,
            )
        )
        memledger.disable()

    # --- monitor-armed dispatch overhead (ISSUE 11 contract) ----------- #
    # the /metrics endpoint adds NO hot-path hook (there is nothing to
    # poke: scrapes snapshot the registries from a server thread), so the
    # only possible cost is scrape-thread GIL/cache interference with the
    # dispatching main thread.  Measured per round, quiet vs actively
    # scraped at 10 Hz — an order of magnitude HOTTER than any sane
    # production cadence (Prometheus defaults to 15 s), but not a busy
    # loop: a busy-loop scraper measures GIL starvation of the scraper's
    # own making, not the endpoint's dispatch-path cost (measured: 5 ms
    # cadence reads ~60% on a throttled host, 100 ms reads ~0).  Each
    # state pairs against the compiled floor IN THE SAME STATE, and —
    # like the flightrec gate — a failure requires the paired deltas to
    # shift WHOLESALE (q25 > 0): a real regression taxes every round,
    # while a scrape landing inside a few timed windows cannot.
    mon_added_pct = mon_added_us = mon_off_oh = float("nan")
    mon_consistent = False
    if args.monitor_gate is not None:
        import threading as _threading
        import urllib.request as _url

        from heat_tpu.utils import monitor as _monitor

        mhost, mport = _monitor.enable()
        murl = f"http://{mhost}:{mport}/metrics"
        scraping = _threading.Event()
        stop_scraper = _threading.Event()

        def _scrape_loop():
            while not stop_scraper.wait(0.1):
                if scraping.is_set():
                    try:
                        with _url.urlopen(murl, timeout=5) as resp:
                            resp.read()
                    except Exception:
                        pass

        scr_thread = _threading.Thread(target=_scrape_loop, daemon=True)
        scr_thread.start()
        s_floor_q, s_mon_q, s_floor_s, s_mon_s = [], [], [], []
        for _ in range(args.reps):
            for active, fl, ca in (
                (False, s_floor_q, s_mon_q),
                (True, s_floor_s, s_mon_s),
            ):
                scraping.set() if active else scraping.clear()
                for fn, out_samples in (
                    (lambda: floor_prog(j1, j2), fl),
                    (lambda: x + y, ca),
                ):
                    t0 = time.perf_counter()
                    out = None
                    for _ in range(20):
                        out = fn()
                    sync(out)
                    out_samples.append((time.perf_counter() - t0) / 20 * 1e6)
        scraping.clear()
        stop_scraper.set()
        scr_thread.join(timeout=2.0)
        _monitor.disable()
        mon_off_oh = max(_paired_delta(s_mon_q, s_floor_q), 1.0)
        oh_scraped = [c - f for c, f in zip(s_mon_s, s_floor_s)]
        oh_quiet = [c - f for c, f in zip(s_mon_q, s_floor_q)]
        d_mon = sorted(a - b for a, b in zip(oh_scraped, oh_quiet))
        mon_added_us = max(d_mon[len(d_mon) // 2], 0.0)
        mon_consistent = d_mon[len(d_mon) // 4] > 0.0
        mon_added_pct = mon_added_us / mon_off_oh * 100.0

    # --- zero-recompilation across >=100 repeated same-signature ops --- #
    for _ in range(2):  # warm every signature used below
        _ = x + y, x * y, ht.exp(x), ht.sum(x, axis=0), ht.cumsum(x, axis=1)
    profiler.reset_cache_stats()
    for _ in range(25):
        _ = x + y
        _ = x * y
        _ = ht.exp(x)
        _ = ht.sum(x, axis=0)
        _ = ht.cumsum(x, axis=1)
    stats = profiler.cache_stats()
    hit_rate = profiler.cache_hit_rate()

    # --- reduction + matmul cached latencies --------------------------- #
    reduce_us = _time_op(lambda: ht.sum(x, axis=0), sync, args.reps)
    mm_a = ht.random.randn(n, n, split=0)
    mm_b = ht.random.randn(n, n, split=1)
    _ = mm_a @ mm_b
    matmul_us = _time_op(lambda: mm_a @ mm_b, sync, args.reps)

    # --- in-place donation surfaces ------------------------------------ #
    z = ht.random.randn(n, n, split=0)
    z += 1.0  # warm the donating program
    iadd_us = _time_op((lambda: z.__iadd__(1.0)), sync, max(args.reps // 2, 5))
    prog_alias = "unknown"
    try:
        from heat_tpu.core import _cache as _c

        table = z.comm.__dict__["_compiled_programs"][_c._DISPATCH_SLOT]
        donating = [v for k, v in table.items() if k[0] == "binary" and k[4]]
        hlo = donating[-1][0].lower(z._jarray, 1.0).compile().as_text()
        prog_alias = "input_output_alias" in hlo
    except Exception:
        pass

    # both variants alternate 0→1 and 1→0 so each per-call figure is the
    # same direction mix
    # memory_budget=0 pins the monolithic path throughout: these rows are
    # labeled monolithic and must not silently stream under an inherited
    # HEAT_TPU_RESPLIT_BUDGET / process default
    r = ht.random.randn(n, n, split=0)
    r.resplit_(1, memory_budget=0)  # warm both directions
    r.resplit_(0, memory_budget=0)

    def flip():
        r.resplit_(1 if r.split == 0 else 0, memory_budget=0)
        return r

    rc0 = ht.random.randn(n, n, split=0)
    rc1 = rc0.resplit(1, memory_budget=0)
    copy_state = [0]

    def copy_flip():
        copy_state[0] ^= 1
        return (
            rc0.resplit(1, memory_budget=0)
            if copy_state[0]
            else rc1.resplit(0, memory_budget=0)
        )

    # batch=1 (sync every call): in-place resplits form a serial dependency
    # chain, so batching would let only the copy variant overlap transfers
    resplit_us, resplit_copy_us = _mins(
        _time_interleaved([flip, copy_flip], sync, args.reps, batch=1)
    )

    rss_inplace = rss_copy = float("nan")
    if not args.skip_rss:
        rss_size = 2048
        rss_inplace = _peak_rss_subprocess("inplace", rss_size)
        rss_copy = _peak_rss_subprocess("copy", rss_size)

    # --- budgeted-resplit peak-RSS gate (ISSUE 6) ---------------------- #
    # the memory contract of the chunked pipeline, measured: beyond the
    # source (inside base) and the preallocated destination, the transient
    # working set is at most budget + one tile.  The monolithic copy path
    # is captured side by side as the comparison row.
    resplit_gate_ok = True
    resplit_payload = None
    if args.resplit_gate or args.resplit_out:
        shape = tuple(args.resplit_shape)
        budget = int(args.resplit_budget_mb * 1024 * 1024)
        # ONE unit everywhere: MiB, matching ru_maxrss/1024 (base_mb/peak_mb)
        # and budget_mb — mixing in decimal MB here loosened the bound by
        # ~4 MB and understated the reported transient by ~3 MiB
        arr_mb = (shape[0] * shape[1] * shape[2] * 4) / 2**20
        bud = _peak_rss_resplit(shape, budget, "budgeted")
        mono = _peak_rss_resplit(shape, 0, "copy")
        tile_mb = bud["peak_tile_bytes"] / 2**20
        # base already contains the source; the destination is a hard
        # requirement of ANY resplit, so the gate bound is
        # base + |dst| + budget + one tile + allocator slack
        allowed_mb = (
            bud["base_mb"] + arr_mb + args.resplit_budget_mb + tile_mb
            + args.resplit_slack_mb
        )
        transient_mb = bud["peak_mb"] - bud["base_mb"] - arr_mb
        resplit_payload = {
            "metric": "resplit_budgeted_transient_mb",
            "value": round(transient_mb, 1),
            "unit": "MB above source+destination (bound: budget + one tile)",
            "vs_baseline": None,
            "extra": {
                "platform": platform,
                "n_devices": n_dev,
                "array_shape": list(shape),
                "array_mb": round(arr_mb, 1),
                "budget_mb": args.resplit_budget_mb,
                "tiles": bud["tiles"],
                "peak_tile_mb": round(tile_mb, 1),
                "gate_allowed_peak_rss_mb": round(allowed_mb, 1),
                "budgeted_base_rss_mb_snapshot": round(bud["base_mb"], 1),
                "budgeted_peak_rss_mb_snapshot": round(bud["peak_mb"], 1),
                "monolithic_copy_peak_rss_mb_snapshot": round(mono["peak_mb"], 1),
                "monolithic_copy_transient_mb_snapshot": round(
                    mono["peak_mb"] - mono["base_mb"] - arr_mb, 1
                ),
                "resplit_bytes_accounted": bud["resplit_bytes"],
                "slack_mb": args.resplit_slack_mb,
                "provenance": "benchmarks/dispatch.py --resplit-gate, fresh "
                              "subprocess per capture (allocator history "
                              "cannot pollute the peak)",
            },
        }
        print(json.dumps(resplit_payload, indent=1))
        if bud["tiles"] < 2:
            resplit_gate_ok = False
            print(
                f"RESPLIT GATE: expected a chunked plan, got tiles={bud['tiles']}"
                " (planner fell back to monolithic?)",
                file=sys.stderr,
            )
        if not (bud["peak_mb"] <= allowed_mb):  # NaN-safe: fails on nan
            resplit_gate_ok = False
            print(
                f"RESPLIT GATE: budgeted resplit peaked at {bud['peak_mb']:.0f} MB"
                f" > allowed {allowed_mb:.0f} MB (base {bud['base_mb']:.0f}"
                f" + dst {arr_mb:.0f} + budget {args.resplit_budget_mb:.0f}"
                f" + tile {tile_mb:.0f} + slack {args.resplit_slack_mb:.0f})",
                file=sys.stderr,
            )
        if args.resplit_out:
            with open(args.resplit_out, "w") as fh:
                json.dump(resplit_payload, fh, indent=1)
        if not args.resplit_gate:
            resplit_gate_ok = True  # capture-only run: report, don't gate

    # --- overlapped-sync gate (ISSUE 16) ------------------------------- #
    # the perf contract of the bucketed lookahead-1 sync, measured: same
    # bytes on the wire, zero steady-state recompiles, and MORE of the
    # step hidden behind compute than the single-bucket sync manages.
    overlap_gate_ok = True
    overlap_payload = None
    if args.overlap_gate or args.overlap_out:
        cap = _overlap_capture(
            args.overlap_steps, args.overlap_warmup, args.overlap_budget
        )
        if not cap:
            overlap_gate_ok = False
            print("OVERLAP GATE: capture subprocess failed", file=sys.stderr)
        else:
            ov = cap["overlap"]
            ab = cap["allreduce_bytes"]
            overlap_payload = {
                "metric": "daso_sync_overlap_gain",
                "value": round(ov["bucketed"] - ov["monolithic"], 4),
                "unit": "overlap fraction gained (bucketed - monolithic, "
                        "median over rotated pairs; 1 - wait/step)",
                "vs_baseline": None,
                "extra": {
                    "platform": platform,
                    "n_devices": n_dev,
                    "overlap_monolithic": ov["monolithic"],
                    "overlap_bucketed": ov["bucketed"],
                    "step_ms_snapshot": cap["step_ms"],
                    "wait_ms_snapshot": cap["wait_ms"],
                    "allreduce_bytes": ab,
                    "n_buckets": cap["n_buckets"],
                    "bucket_budget": args.overlap_budget,
                    "measured_steps_per_arm": args.overlap_steps,
                    "steady_cache_misses": cap["steady_cache_misses"],
                    "steady_cache_hits": cap["steady_cache_hits"],
                    "provenance": "benchmarks/dispatch.py --overlap-gate, "
                                  "fresh subprocess, rotated AB/BA step "
                                  "pairs on the host mesh",
                },
            }
            print(json.dumps(overlap_payload, indent=1))
            if cap["n_buckets"].get("bucketed", 0) < 2:
                overlap_gate_ok = False
                print(
                    f"OVERLAP GATE: expected a multi-bucket plan, got "
                    f"{cap['n_buckets']} (budget {args.overlap_budget})",
                    file=sys.stderr,
                )
            if ab.get("monolithic") != ab.get("bucketed") or not ab.get("bucketed"):
                overlap_gate_ok = False
                print(
                    f"OVERLAP GATE: comm.allreduce.bytes must be byte-"
                    f"identical across arms, got {ab} (the telescoped "
                    f"stage accounting broke)",
                    file=sys.stderr,
                )
            if cap["steady_cache_misses"] != 0:
                overlap_gate_ok = False
                print(
                    f"OVERLAP GATE: {cap['steady_cache_misses']} steady-state "
                    f"recompiles after warmup (contract: 0)",
                    file=sys.stderr,
                )
            if not (ov["bucketed"] > ov["monolithic"]):
                overlap_gate_ok = False
                print(
                    f"OVERLAP GATE: bucketed sync hides no more comm than "
                    f"monolithic (overlap {ov['bucketed']:.3f} vs "
                    f"{ov['monolithic']:.3f})",
                    file=sys.stderr,
                )
            if args.overlap_out:
                with open(args.overlap_out, "w") as fh:
                    json.dump(overlap_payload, fh, indent=1)
        if not args.overlap_gate:
            overlap_gate_ok = True  # capture-only run: report, don't gate

    # Row-name scheme (scripts/bench_compare.py infers direction by name):
    # the TRACKED contract rows are the host-portable ratios (*_speedup,
    # higher-better); absolute µs figures carry a *_snapshot suffix — no
    # latency/overhead fragment — so they are reported but never flagged:
    # they swing ±2x between hosts and runs, and a same-payload comparison
    # must not fail CI on scheduler noise.
    payload = {
        "metric": "dispatch_overhead_speedup",
        "value": round(max(eager_overhead_us, 1.0) / max(overhead_us, 1.0), 3),
        "unit": "x (seed dispatch overhead / cached dispatch overhead)",
        "vs_baseline": None,
        "extra": {
            "platform": platform,
            "n_devices": n_dev,
            "op_size": n,
            "dispatch_walltime_speedup": round(eager_us / cached_us, 3)
            if cached_us
            else None,
            "recompilations_100_ops": stats["misses"],
            "cache_hits_100_ops": stats["hits"],
            "cache_hit_rate": round(hit_rate, 4),
            "iadd_donation_aliased": prog_alias,
            "dispatch_floor_us_snapshot": round(floor_us, 2),
            "dispatch_cached_us_snapshot": round(cached_us, 2),
            "dispatch_seed_path_us_snapshot": round(eager_us, 2),
            "dispatch_cost_above_floor_us_snapshot": round(max(overhead_us, 1.0), 2),
            "seed_cost_above_floor_us_snapshot": round(
                max(eager_overhead_us, 1.0), 2
            ),
            "reduce_cached_us_snapshot": round(reduce_us, 2),
            "matmul_cached_us_snapshot": round(matmul_us, 2),
            "iadd_donating_us_snapshot": round(iadd_us, 2),
            "resplit_inplace_us_snapshot": round(resplit_us, 2),
            "resplit_copy_us_snapshot": round(resplit_copy_us, 2),
            "resplit_peak_rss_mb_inplace": round(rss_inplace, 1),
            "resplit_peak_rss_mb_copy": round(rss_copy, 1),
            # *_snapshot / no overhead-latency fragment: reported, never
            # flagged by bench_compare — the gate below owns the contract
            "telemetry_off_above_floor_us_snapshot": round(tel_off_oh, 2),
            "telemetry_on_added_us_snapshot": round(tel_added_us, 2),
            "telemetry_on_added_dispatch_pct": round(tel_added_pct, 1),
            "flightrec_off_above_floor_us_snapshot": round(fr_off_oh, 2),
            "flightrec_on_added_us_snapshot": round(fr_added_us, 2),
            "flightrec_on_added_dispatch_pct": round(fr_added_pct, 1),
            "flightrec_noise_floor_us_snapshot": round(fr_noise_us, 2),
            # NaN-guarded like the monitor rows below: a run without
            # --memledger-gate must not write the invalid `NaN` token
            "memledger_off_above_floor_us_snapshot": round(ml_off_oh, 2)
            if ml_off_oh == ml_off_oh else None,
            "memledger_on_added_us_snapshot": round(ml_added_us, 2)
            if ml_added_us == ml_added_us else None,
            "memledger_on_added_dispatch_pct": round(ml_added_pct, 1)
            if ml_added_pct == ml_added_pct else None,
            "memledger_noise_floor_us_snapshot": round(ml_noise_us, 2)
            if ml_noise_us == ml_noise_us else None,
            # NaN-guarded (x == x): a run without --monitor-gate must not
            # write the invalid-strict-JSON `NaN` token into the payload
            "monitor_quiet_above_floor_us_snapshot": round(mon_off_oh, 2)
            if mon_off_oh == mon_off_oh else None,
            "monitor_scraped_added_us_snapshot": round(mon_added_us, 2)
            if mon_added_us == mon_added_us else None,
            "monitor_scraped_added_dispatch_pct": round(mon_added_pct, 1)
            if mon_added_pct == mon_added_pct else None,
            "provenance": "benchmarks/dispatch.py on the host mesh "
                          "(seed row = the pre-cache dispatch path, forced "
                          "via _FORCE_SLOW and measured in-run, interleaved)",
        },
    }
    print(json.dumps(payload, indent=1))
    # hits >= 100 guards the guard: misses==0 alone would also hold if every
    # signature fell through to the eager path (counted as "slow", not hits)
    ok = stats["misses"] == 0 and hit_rate >= 0.99 and stats["hits"] >= 100
    if not ok:
        print(f"WARNING: cache contract violated: {stats}", file=sys.stderr)
    gate_ok = True
    if args.telemetry_gate is not None and tel_added_pct > args.telemetry_gate:
        gate_ok = False
        print(
            f"TELEMETRY GATE: enabled telemetry adds {tel_added_pct:.1f}% "
            f"({tel_added_us:.2f} us) to the dispatch cost above floor "
            f"({tel_off_oh:.1f} us; limit {args.telemetry_gate:.1f}%)",
            file=sys.stderr,
        )
    flightrec_gate_ok = True
    if (
        args.flightrec_gate is not None
        and fr_added_pct > args.flightrec_gate
        and fr_added_us > fr_noise_us
        and fr_consistent
    ):
        flightrec_gate_ok = False
        print(
            f"FLIGHTREC GATE: the armed flight recorder adds {fr_added_pct:.1f}% "
            f"({fr_added_us:.2f} us) to the dispatch cost above floor "
            f"({fr_off_oh:.1f} us; limit {args.flightrec_gate:.1f}%, in-run "
            f"off-vs-off noise floor {fr_noise_us:.2f} us)",
            file=sys.stderr,
        )
    memledger_gate_ok = True
    if (
        args.memledger_gate is not None
        and ml_added_pct > args.memledger_gate
        and ml_added_us > ml_noise_us
        and ml_consistent
    ):
        memledger_gate_ok = False
        print(
            f"MEMLEDGER GATE: the armed device-memory ledger adds "
            f"{ml_added_pct:.1f}% ({ml_added_us:.2f} us) to the dispatch "
            f"cost above floor ({ml_off_oh:.1f} us; limit "
            f"{args.memledger_gate:.1f}%, in-run off-vs-off noise floor "
            f"{ml_noise_us:.2f} us, wholesale shift confirmed)",
            file=sys.stderr,
        )
    monitor_gate_ok = True
    if (
        args.monitor_gate is not None
        and mon_added_pct > args.monitor_gate
        and mon_consistent
    ):
        monitor_gate_ok = False
        print(
            f"MONITOR GATE: an actively scraped /metrics endpoint adds "
            f"{mon_added_pct:.1f}% ({mon_added_us:.2f} us) to the dispatch "
            f"cost above floor ({mon_off_oh:.1f} us; limit "
            f"{args.monitor_gate:.1f}%, wholesale shift confirmed)",
            file=sys.stderr,
        )
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=1)
    if telemetry_armed:
        # the CI telemetry lane uploads this run's own spans as an artifact
        telemetry.enable()
        flushed = telemetry.flush()
        if flushed:
            print(f"telemetry flushed to {flushed}", file=sys.stderr)
    if not ok:
        return 3
    if not gate_ok:
        return 4
    if not resplit_gate_ok:
        return 5
    if not flightrec_gate_ok:
        return 6
    if not monitor_gate_ok:
        return 7
    if not memledger_gate_ok:
        return 8
    if not overlap_gate_ok:
        return 9
    return 0


if __name__ == "__main__":
    sys.exit(main())
