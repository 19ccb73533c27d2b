"""The reduction from a profiler trace to busy time, idle share, gaps,
collectives by name and launches per job: on intervals written out by hand,
on a trace recorded on the v5e and kept under ``fixtures/``, and on a trace
this test records on the CPU (which has host annotations and no device)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench.harness import manifest, runner  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402
from chipbench.harness.window import Window  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
E = tr.Event


# ---------------------------------------------------------------------- #
# interval arithmetic
# ---------------------------------------------------------------------- #
def test_merge_total_clip():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)] and tr.total(merged) == 6
    assert tr.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert tr.clip(merged, 3, 5) == []


def test_subtract_and_intersect():
    a, b = [(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]
    assert tr.subtract(a, b) == [(0, 2), (4, 8), (22, 29)]
    assert tr.intersect(a, b) == [(2, 4), (8, 10), (20, 22), (29, 30)]
    assert tr.total(tr.subtract(a, b)) + tr.total(tr.intersect(a, b)) == tr.total(a)
    assert tr.subtract(a, []) == a and tr.subtract([], b) == []


def test_nested_self_times_and_leaves():
    ops = [E("while", 0, 100), E("fusion.1", 10, 40), E("dot.2", 40, 90), E("copy.3", 120, 130)]
    got = {ev.name: (self_ns, leaf) for ev, self_ns, leaf in tr.nested(ops)}
    assert got == {"while": (20, False), "fusion.1": (30, True), "dot.2": (50, True),
                   "copy.3": (10, True)}
    dev = tr.DeviceTrace(0, [], ops)
    assert [e.name for e in tr.leaves(dev)] == ["fusion.1", "dot.2", "copy.3"]
    # busy is the union of every operation, the parent's own time included
    assert tr.busy(dev, 0, 200) == [(0, 100), (120, 130)]


@pytest.mark.parametrize("name, kind", [
    ("all-gather.1", ("all-gather", None)),
    ("all-gather-start.12", ("all-gather", "-start")),
    ("all-gather-done.12", ("all-gather", "-done")),
    ("%all-to-all.3", ("all-to-all", None)),
    ("all-reduce", ("all-reduce", None)),
    ("reduce-scatter.7", ("reduce-scatter", None)),
    ("collective-permute-done.2", ("collective-permute", "-done")),
    ("fusion.4", None), ("dot.1", None), ("all-gathered.1", None), ("copy-start.1", None),
])
def test_collectives_by_name(name, kind):
    assert tr.collective_kind(name) == kind


def test_collective_time_and_the_exposed_part():
    ops = [
        E("all-gather.1", 0, 30),                   # synchronous, nothing beside it
        E("dot.1", 30, 100),
        E("all-to-all-start.2", 100, 102), E("fusion.3", 102, 140),
        E("all-to-all-done.2", 140, 150),           # hidden behind fusion.3 from 102 to 140
    ]
    dev = tr.DeviceTrace(0, [], ops)
    assert tr.collectives(dev) == [("all-gather", 0, 30), ("all-to-all", 100, 150)]
    assert tr.collective_time(dev, 0, 200) == (80, 30 + 2 + 10)
    assert tr.collective_time(dev, 20, 120) == (30, 10 + 2)
    alone = tr.DeviceTrace(0, [], [E("dot.1", 0, 50)])
    assert tr.collective_time(alone, 0, 100) == (0, 0)


def _two_job_trace():
    """Two jobs of 100 ns; chip 0 runs two programs per job, chip 1 one."""
    host = [E("bench.job", 0, 100), E("ht.matmul", 5, 60), E("ht.resplit", 60, 95),
            E("bench.job", 100, 200), E("ht.matmul", 105, 160), E("ht.resplit", 160, 195)]
    chip0 = tr.DeviceTrace(
        0,
        [E("jit_matmul(1)", 10, 50), E("jit_reshard(2)", 70, 90),
         E("jit_matmul(1)", 110, 150), E("jit_reshard(2)", 170, 190)],
        [E("dot.1", 10, 50), E("all-to-all.1", 70, 90), E("dot.1", 110, 150),
         E("all-to-all.1", 170, 190)])
    chip1 = tr.DeviceTrace(
        1, [E("jit_matmul(1)", 10, 40), E("jit_matmul(1)", 110, 140)],
        [E("dot.1", 10, 40), E("dot.1", 110, 140)])
    return tr.Trace([chip0, chip1], host)


def test_window_busy_idle_launches_gaps():
    trace = _two_job_trace()
    assert tr.window(trace) == (0, 200) and len(tr.jobs(trace)) == 2
    busy_s, window_s = tr.busy_seconds(trace)
    assert window_s == pytest.approx(200e-9) and busy_s == pytest.approx((120 + 60) / 2 * 1e-9)
    assert tr.idle_share(trace) == pytest.approx(0.7)  # the worst chip, chip 1
    chip0, chip1 = trace.devices
    assert tr.launches(chip0, 0, 200) == 4 and tr.launches(chip1, 0, 200) == 2
    # gaps inside a job only: 50 -> 70 and 150 -> 170, never 90 -> 110 across jobs
    assert tr.launch_gaps(chip0, tr.jobs(trace)) == [20, 20]
    assert tr.launch_gaps(chip1, tr.jobs(trace)) == []
    assert tr.span_ms(trace, "ht.matmul") == pytest.approx(55e-6)
    assert tr.span_ms(trace, "ht.nothing") is None


def test_idle_is_labelled_by_the_innermost_host_span():
    trace = _two_job_trace()
    segments = tr.host_segments(trace, 0, 200)
    assert segments[:4] == [(0, 5, "bench.job"), (5, 60, "ht.matmul"), (60, 95, "ht.resplit"),
                            (95, 100, "bench.job")]
    assert tr.total([(s, e) for s, e, _ in segments]) == 200
    idle = tr.idle_by_label(trace)
    # chip 0 idles 5-10 and 50-60 inside ht.matmul, chip 1 5-10 and 40-60; both jobs; mean
    assert idle["ht.matmul"] == pytest.approx((2 * 15 + 2 * 25) / 2 * 1e-9)
    assert idle["ht.resplit"] == pytest.approx((2 * 15 + 2 * 35) / 2 * 1e-9)
    assert sum(idle.values()) == pytest.approx((80 + 140) / 2 * 1e-9)
    outside = tr.host_segments(tr.Trace([], [E("ht.x", 10, 20)]), 0, 30)
    assert outside == [(0, 10, tr.OUTSIDE), (10, 20, "ht.x"), (20, 30, tr.OUTSIDE)]


def test_breakdown_names_programs_and_is_capped():
    got = tr.breakdown(_two_job_trace(), top=2)
    assert got["device_ops"] == [["jit_matmul:dot.1", pytest.approx(70e-9)],
                                 ["jit_reshard:all-to-all.1", pytest.approx(20e-9)]]
    assert [name for name, _ in got["idle_gaps"]] == ["ht.resplit", "ht.matmul"]
    assert json.dumps(got)


def test_readers_on_the_two_job_trace():
    bench = manifest.Manifest(REPO)
    ctx = runner.Context({}, {}, {}, 2, {"flop": 2 * 197e12 * 45e-9, "bytes": 1}, [0.1] * 20,
                         trace=_two_job_trace(),
                         peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                         counters={"program_cache_misses": 1, "compile_cache_files_added": 2,
                                   "scaling_reference_job_s": 0.15})
    want = {"launches_per_job": 1.5, "launch_gap_us": 0.02, "recompiles_in_window": 3,
            "job_p90_over_p50": 1.0, "collective_ms_per_job": 10e-6,
            "collective_exposed_share": 100.0, "strong_scaling_eff": 75.0,
            "matmul_ms": 55e-6, "resplit_ms": 35e-6, "device_idle_share": 70.0,
            # least time 45 ns a job over (120 + 60) / 2 / 2 = 45 ns busy a job
            "matmul_roofline": 100.0, "kmeans_fit_roofline": 100.0}
    assert set(want) == {m["name"] for m in bench.data["per_layer"]}
    for name, value in want.items():
        assert bench.reader(name)(ctx) == pytest.approx(value), name


@pytest.mark.parametrize("samples, want", [
    ([6.1, 6.85, 6.97], (6.85 + 0.8 * (6.97 - 6.85)) / 6.85),  # the four-chip cell's three jobs
    ([0.5], 1.0),
    ([], None),
], ids=["three_jobs", "one_job", "no_job"])
def test_job_p90_over_p50_reads_any_window(samples, want):
    """A traced run's window holds three jobs in the four-chip cell, and the
    driver refuses a line that lacks a metric the cell lists."""
    ctx = runner.Context({}, {}, {}, 4, {}, samples)
    got = manifest.Manifest(REPO).reader("job_p90_over_p50")(ctx)
    assert got == (pytest.approx(want) if want is not None else None)


# ---------------------------------------------------------------------- #
# a trace the profiler writes here: host annotations, no device plane
# ---------------------------------------------------------------------- #
def test_load_xplane_recorded_on_the_cpu():
    def job(_):
        with jax.profiler.TraceAnnotation("ht.matmul"):
            out = jnp.ones((64, 64)) @ jnp.ones((64, 64))
        with jax.profiler.TraceAnnotation("other.span"):
            return (out + 1,)

    window = Window()
    trace = runner._record(job, None, 3, window)
    assert len(window.samples) == 3 and trace.devices == []
    assert [e.name for e in trace.host] == ["bench.job", "ht.matmul"] * 3
    lo, hi = tr.window(trace)
    assert (hi - lo) / 1e9 == pytest.approx(sum(window.samples), rel=0.5)
    assert all(lo <= e.start <= e.end <= hi for e in trace.host)
    again = tr.from_json(json.loads(json.dumps(tr.to_json(trace))))
    assert again == trace


# ---------------------------------------------------------------------- #
# traces recorded on the v5e by PR 22 (``run.py --keep-trace``), raw: the
# numbers below are what the chip runs printed for the same traces
# ---------------------------------------------------------------------- #
def _fixture(name):
    raw = tr.load(os.path.join(FIXTURES, name))
    return raw, tr.calibrate(raw)


def test_fixtures_are_small():
    sizes = [os.path.getsize(os.path.join(FIXTURES, f)) for f in os.listdir(FIXTURES)]
    assert len(sizes) == 4 and sum(sizes) < 300_000


def test_recorded_kmeans_fit_one_chip():
    raw, trace = _fixture("v5e_1chip_kmeans_fit.json.gz")
    # the device's clock read 1.47 ms ahead of the host's: uncalibrated, the
    # first program of every job seems to start before its job does
    assert tr.clock_lead(raw) == -1466061.0 and tr.clock_lead(trace) == 0
    lo, hi = tr.window(trace)
    (chip,) = trace.devices
    assert tr.launches(raw.devices[0], lo, hi) == 12 and tr.launches(chip, lo, hi) == 15
    assert len(tr.jobs(trace)) == 3  # 5 programs a fit, one of them the while_loop
    busy_s, window_s = tr.busy_seconds(trace)
    assert (busy_s, window_s) == (pytest.approx(0.813585589), pytest.approx(0.828973035))
    assert tr.idle_share(trace) == pytest.approx(0.0185620585)
    # the ops line nests: a while is no leaf, and self times add up to busy time
    names = {ev.name.split(".")[0] for ev, _, leaf in tr.nested(chip.ops) if not leaf}
    assert names == {"while"}
    self_s = sum(self_ns for ev, self_ns, _ in tr.nested(chip.ops) if lo <= ev.start < hi) / 1e9
    assert self_s == pytest.approx(busy_s, rel=1e-3)
    assert tr.collective_time(chip, lo, hi) == (0, 0)
    top = tr.breakdown(trace)
    assert top["device_ops"][0] == ["jit_prog:fusion.37", pytest.approx(0.196573094)]
    assert top["idle_gaps"][0] == ["ht.cluster.KMeans.fit", pytest.approx(0.015087097)]
    assert len(top["device_ops"]) == 10


def test_recorded_eager_job_one_chip():
    raw, trace = _fixture("v5e_1chip_lloyd_eager_1job.json.gz")
    assert tr.clock_lead(raw) == -559442.0
    (chip,), (job,) = trace.devices, tr.jobs(trace)
    # 100 steps of 30 launches, and two before the first step
    assert tr.launches(chip, job.start, job.end) == 3002 == len(raw.issued)
    gaps = tr.launch_gaps(chip, [job])
    assert len(gaps) == 3001 and sorted(gaps)[1500] == pytest.approx(289538.0)
    assert tr.idle_share(trace) == pytest.approx(0.98477314)
    idle = tr.idle_by_label(trace)
    assert idle["ht.spatial.cdist"] == pytest.approx(0.332388873)
    assert sum(idle.values()) == pytest.approx(0.98477314 * (job.end - job.start) / 1e9)
    # no program starts before the runtime issued it, and the closest follows at once
    waits = [m.start - h for m, h in zip(chip.modules, trace.issued)]
    assert min(waits) == 0 and sorted(waits)[1500] < 100_000


def test_recorded_matmul_resplit_four_chips():
    raw, trace = _fixture("v5e_4chip_matmul_resplit.json.gz")
    assert tr.clock_lead(raw) == -5171785.0 and len(raw.issued) == 4 * 10
    lo, hi = tr.window(trace)
    assert [tr.launches(d, lo, hi) for d in trace.devices] == [10, 10, 10, 10]
    for chip in trace.devices:
        assert {kind for kind, _, _ in tr.collectives(chip)} == {"all-gather"}
        whole, exposed = tr.collective_time(chip, lo, hi)
        # one synchronous all-gather before the dot: nothing hides it
        assert whole == exposed == pytest.approx(189.33e6, rel=1e-3)
    busy_s, window_s = tr.busy_seconds(trace)
    assert (busy_s, window_s) == (pytest.approx(1.11694160625), pytest.approx(44.301667148))
    assert tr.idle_share(trace) == pytest.approx(0.9747887379)
    assert tr.span_ms(trace, "ht.matmul") == pytest.approx(114.231145)
    assert tr.span_ms(trace, "ht.resplit") == pytest.approx(4149.9091035)
    # the resplit put no program on any chip: the chips idle inside its span
    assert tr.breakdown(trace)["idle_gaps"][0] == ["ht.resplit", pytest.approx(41.08293256625)]


@pytest.mark.parametrize("name, fixture, samples", [
    ("kmeans_fit_n2e26", "v5e_1chip_kmeans_fit.json.gz", [0.276] * 60),
    ("lloyd_eager_n2e26", "v5e_1chip_lloyd_eager_1job.json.gz", [0.95] * 9),
    # three jobs fill the window while the resplit takes seconds
    ("matmul_resplit_n40960_4chip", "v5e_4chip_matmul_resplit.json.gz", [4.1, 4.28, 4.3]),
])
def test_every_metric_a_cell_lists_reads_from_its_recorded_trace(name, fixture, samples):
    """What the driver's first check refused: a traced line (the four-chip
    cell's) without one of the metrics the manifest lists for the cell."""
    bench = manifest.Manifest(REPO)
    cell = bench.cell(name)
    ctx = runner.Context(cell, {}, {}, cell["chips"], {"flop": 1e12, "bytes": 1e9}, samples,
                         trace=_fixture(fixture)[1],
                         peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                         counters={"program_cache_misses": 0, "compile_cache_files_added": 0,
                                   "scaling_reference_job_s": 0.37})
    listed = bench.metrics("per_layer", name)
    assert len(listed) >= 5
    for metric in listed:
        assert isinstance(bench.reader(metric["name"])(ctx), (int, float)), metric["name"]


def test_recorded_mixed_programs_four_chips():
    raw, trace = _fixture("v5e_4chip_mixed_programs.json.gz")
    # chip 0 also ran one-chip programs, so programs and issues do not pair
    # up: the clocks stay as recorded rather than matched by guesswork
    assert [len(d.modules) for d in raw.devices] == [72, 51, 51, 51]
    assert tr.clock_lead(raw) is None and trace is raw
    for chip in trace.devices:
        kinds = [kind for kind, _, _ in tr.collectives(chip)]
        assert sorted(set(kinds)) == ["all-gather", "all-reduce"] and len(kinds) == 12
    lo, hi = tr.window(trace)
    assert tr.collective_time(trace.devices[3], lo, hi)[0] == pytest.approx(28436853.0)
    assert tr.idle_share(trace) == pytest.approx(0.97991192337)
