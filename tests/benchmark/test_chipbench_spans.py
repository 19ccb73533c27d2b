"""The reduction from the program's own spans (``ht.dispatch.<kind>``,
``ht.dispatch.launch``) to where a job's host time goes: on intervals written
out by hand, on the trace PR 22 recorded (which holds no program span), and on
one job of a trace recorded on the v5e with the spans in the program, kept
under ``fixtures_spans/``."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench.harness import manifest, runner, spans  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "fixtures", "v5e_1chip_lloyd_eager_1job.json.gz")
NEW_DIR = os.path.join(HERE, "fixtures_spans")
NEW = os.path.join(NEW_DIR, "v5e_1chip_lloyd_eager_spans_1job.json.gz")
METRICS = ("jit_call_ms_per_job", "dispatch_py_ms_per_job", "above_dispatch_ms_per_job",
           "uncached_launches_per_job")
E = tr.Event


def _ctx(trace):
    return runner.Context({}, {}, {}, 1, {}, [], trace=trace)


def _by_hand():
    """Two jobs of 1000 ns on two chips.  Job 1: a binary op (100-300, its
    launch 150-280), a reduce on a path with no cached program (400-450), a
    matmul whose 1-D branch nests a second kind span (500-700, launches
    520-560 and 600-690), a tile program's launch under no kind span
    (800-850); the benchmark's own span and the resplit's lie over them and
    count for nothing.  Job 2: one local op that began in job 1."""
    host = [
        E("bench.job", 0, 1000),
        E("ht.update", 50, 720),
        E("ht.dispatch.binary", 100, 300), E("ht.dispatch.launch", 150, 280),
        E("ht.dispatch.reduce", 400, 450),
        E("ht.dispatch.matmul", 500, 700), E("ht.dispatch.launch", 520, 560),
        E("ht.dispatch.matmul", 590, 695), E("ht.dispatch.launch", 600, 690),
        E("ht.comm.resplit", 780, 900), E("ht.dispatch.launch", 800, 850),
        E("ht.dispatch.local", 990, 1100), E("ht.dispatch.launch", 995, 1090),
        E("bench.job", 1000, 2000),
    ]
    host.sort(key=lambda e: (e.start, -e.end))
    chip0 = tr.DeviceTrace(0, [E(f"jit_p({i})", t, t + 5) for i, t in enumerate(
        (160, 410, 420, 530, 610, 810, 900, 1010, 1500))], [])
    chip1 = tr.DeviceTrace(1, [E(f"jit_p({i})", t, t + 5) for i, t in enumerate(
        (160, 410, 530, 610, 810, 1010, 1500))], [])
    return tr.Trace([chip0, chip1], host)


def test_job_split_by_hand():
    trace = _by_hand()
    first, second = tr.jobs(trace)
    # launches: 130 + 40 + 90 + 50, and 5 of the launch that began at 995
    # kinds less launches: (200 - 130) + 50 + (200 - 130) + 5 of 990-1000
    assert spans.job_split(trace, first) == (315, 195, 1000 - 315 - 195, 5)
    assert spans.job_split(trace, second) == (90, 10, 900, 0)
    for job in (first, second):
        assert sum(spans.job_split(trace, job)[:3]) == job.end - job.start


def test_per_job_by_hand():
    got = spans.per_job(_ctx(_by_hand()))
    assert got == {
        "jit_call_ms_per_job": pytest.approx((315 + 90) / 2 / 1e6),
        "dispatch_py_ms_per_job": pytest.approx((195 + 10) / 2 / 1e6),
        "above_dispatch_ms_per_job": pytest.approx((490 + 900) / 2 / 1e6),
        # job 1: (7 + 5) / 2 programs a chip less 5 launch spans; job 2: 2 less 0
        "uncached_launches_per_job": pytest.approx((1 + 2) / 2),
    }


@pytest.mark.parametrize("trace", [
    None,
    tr.Trace([], [E("bench.job", 0, 100), E("ht.dispatch.launch", 10, 20)]),  # the CPU: no device plane
    tr.Trace([tr.DeviceTrace(0, [], [])], [E("bench.job", 0, 100)]),  # no program in any job
    tr.Trace([tr.DeviceTrace(0, [E("jit_p(1)", 10, 20)], [])], []),  # no job
], ids=["no_trace", "no_device_plane", "no_launch", "no_job"])
def test_nothing_to_read(trace):
    assert spans.per_job(_ctx(trace)) is None
    for metric in METRICS:
        assert manifest.Manifest(REPO).reader(metric)(_ctx(trace)) is None


def test_a_trace_with_no_program_span_reads_the_whole_job():
    """What PR 22 recorded, and what a parent commit older than the spans
    gives: numbers, not nothing."""
    trace = tr.calibrate(tr.load(OLD))
    (job,) = tr.jobs(trace)
    assert spans.per_job(_ctx(trace)) == {
        "jit_call_ms_per_job": 0.0, "dispatch_py_ms_per_job": 0.0,
        "above_dispatch_ms_per_job": (job.end - job.start) / 1e6,
        "uncached_launches_per_job": 3002.0,
    }


@pytest.mark.parametrize("metric", METRICS)
def test_reader_files_read_what_the_harness_computes(metric):
    ctx = _ctx(_by_hand())
    assert manifest.Manifest(REPO).reader(metric)(ctx) == spans.per_job(ctx)[metric]


# ---------------------------------------------------------------------- #
# one job of the trace of ``lloyd_eager_n2e26`` recorded on the v5e by PR 25
# (``run.py --trace 1 --keep-trace``), raw
# ---------------------------------------------------------------------- #
def test_the_new_fixture_is_small():
    assert os.listdir(NEW_DIR) == [os.path.basename(NEW)]
    assert os.path.getsize(NEW) < 250_000


def test_recorded_eager_job_with_program_spans():
    trace = tr.calibrate(tr.load(NEW))
    (chip,), (job,) = trace.devices, tr.jobs(trace)
    assert tr.launches(chip, job.start, job.end) == 3002
    call, py, above, n_spans = spans.job_split(trace, job)
    assert call + py + above == job.end - job.start  # to the nanosecond
    assert call > 0 and py > 0 and above > 0
    got = spans.per_job(_ctx(trace))
    assert all(isinstance(got[m], float) for m in METRICS)
    # what the chip run's whole trace gave for this job: 12 cached programs a
    # step, and jax's call takes eight times heat_tpu's own Python around it
    assert got == {"jit_call_ms_per_job": 303.381391, "dispatch_py_ms_per_job": 36.944537,
                   "above_dispatch_ms_per_job": 707.385759,
                   "uncached_launches_per_job": 1802.0}
    uncached = got["uncached_launches_per_job"]
    assert uncached == 3002 - n_spans and uncached == int(uncached) and 0 <= uncached <= 3002
    # every launch span lies in a kind span (one chip: no tile program runs)
    kinds = tr.merge([(e.start, e.end) for e in trace.host
                      if e.name.startswith(spans.DISPATCH) and e.name != spans.LAUNCH])
    launch = tr.merge([(e.start, e.end) for e in trace.host if e.name == spans.LAUNCH])
    assert tr.subtract(launch, kinds) == []
    # and the idle time is now charged to the program's spans by name
    idle = tr.idle_by_label(trace)
    assert idle[spans.LAUNCH] > 0 and any(
        name.startswith(spans.DISPATCH) and name != spans.LAUNCH for name in idle)
