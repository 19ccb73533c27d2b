"""The readers that account for the whole of a traced step (PR 36): the outer
scopes by innermost name, what runs under no name, what ``jax.checkpoint`` runs
again.  On events written out by hand, on a step of
``smallthinker_21b_a3b_train_1x16k`` recorded on the v5e with the scopes in the
program (``fixtures_scopes/``, a directory of its own: ``fixtures/`` is counted
by older tests), and on the fit's trace recorded before any scope."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench.harness import coverage, manifest, runner, scopes  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = manifest.Manifest(REPO)
E = tr.Event
STEP = "smallthinker_21b_a3b_train_1x16k"
NEW = ("shortconv_proj_ms", "attention_proj_ms", "mlp_ms", "embed_ms", "kda_prepare_ms", "kda_recur_ms",
       "norm_ms", "cast_ms", "block_other_ms", "unscoped_ms", "recompute_ms")
# parts of another metric, and the one that cuts across the layers: not in the sum
NOT_IN_THE_SUM = {"kda_prepare_ms", "kda_recur_ms", "recompute_ms"}


def _read(name, trace):
    return BENCH.reader(name)(runner.Context({}, {}, {}, 1, {}, [], trace=trace))


# ---------------------------------------------------------------------- #
# events written out by hand
# ---------------------------------------------------------------------- #
FWD, BWD = "jvp(ht.lm.block)/jit(run)", "transpose(jvp(ht.lm.block))/jit(run)/checkpoint"
AGAIN = BWD + "/rematted_computation"
# (operation, nanoseconds, scope) of one job; a second program runs beside the step
JOB = [
    ("fusion.1", 2, "jvp(ht.lm.cast)"), ("fusion.2", 4, "jvp(ht.lm.embed)"),
    ("fusion.3", 1, FWD + "/ht.lm.cast"), ("fusion.4", 3, FWD + "/ht.lm.norm"),
    ("fusion.5", 10, FWD + "/ht.shortconv.proj"), ("fusion.6", 5, FWD + "/ht.shortconv.proj/ht.shortconv"),
    ("fusion.7", 6, FWD + "/ht.mlp"),
    ("fusion.8", 20, FWD + "/ht.kda.proj/ht.kda/ht.kda.prepare/_forward_kernel"),
    ("fusion.9", 8, FWD + "/ht.kda.proj/ht.kda/ht.kda.recur/while/body"),
    ("fusion.10", 7, FWD + "/ht.attention.proj"), ("fusion.11", 9, FWD + "/ht.attention.proj/ht.attention.window"),
    ("ragged-dot-none.3", 15, FWD + "/while/body/jit(_sorted_rows)"), ("convert.1", 2, FWD),
    ("fusion.12", 1, AGAIN + "/ht.lm.cast"), ("fusion.13", 3, AGAIN + "/ht.lm.norm"),
    ("fusion.14", 6, AGAIN + "/ht.mlp"), ("fusion.15", 10, AGAIN + "/ht.kda.proj/ht.kda/ht.kda.recur"),
    ("fusion.16", 12, BWD + "/ht.mlp"), ("copy.7", 3, BWD), ("fusion.17", 4, AGAIN),
    ("fusion.18", 5, "ht.optim.update"),
    ("copy-done.4", 2, ""), ("multiply_convert_fusion.2", 6, ""),
]
BATCH = ("fusion.1", 3, "jit(searchsorted)/while/body")
WANT = {"shortconv_proj_ms": 10, "attention_proj_ms": 7, "mlp_ms": 24, "embed_ms": 4, "kda_prepare_ms": 20,
        "kda_recur_ms": 18, "norm_ms": 6, "cast_ms": 4, "block_other_ms": 9, "unscoped_ms": 11, "recompute_ms": 24}


def _step_trace():
    ops, modules, jobs = [], [], []
    for start in (0, 1000):
        t = start
        modules.append(E("jit_batch(1)", t, t + BATCH[1]))
        for name, ns, scope in [BATCH] + JOB:
            ops.append(E(name, t, t + ns, scope))
            t += ns
        modules.append(E("jit_step(2)", start + BATCH[1], t))
        jobs.append(E("bench.job", start, start + 1000))
    return tr.Trace([tr.DeviceTrace(0, modules, ops)], jobs)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_on_a_step_written_out_by_hand(name):
    assert _read(name, _step_trace()) == pytest.approx(WANT[name] * 1e-6)
    assert _read(name, None) is None and _read(name, tr.Trace([], [E("bench.job", 0, 1)])) is None


def test_the_readers_by_innermost_scope_and_the_unscoped_rest_are_the_busy_time():
    trace = _step_trace()
    older = ("shortconv_ms", "window_attention_ms", "kda_ms", "moe_experts_ms", "optimizer_ms")
    assert [_read(n, trace) * 1e6 for n in older] == [pytest.approx(v) for v in (5, 9, 38, 15, 5)]
    total = sum(_read(n, trace) for n in older + tuple(n for n in NEW if n not in NOT_IN_THE_SUM))
    busy_s, _ = tr.busy_seconds(trace)
    assert total * 1e-3 == pytest.approx(busy_s / 2)
    # ``scopes.by_layer`` files a grouped product under the block's name since the
    # block has one: the reader of what a block runs itself leaves the products out
    assert scopes.by_layer(trace)["ht.lm.block"] == pytest.approx((9 + 15) * 1e-9)


def test_what_the_checkpoint_runs_again_and_the_rest_split_the_busy_time():
    trace = _step_trace()
    ctx = runner.Context({}, {}, {}, 1, {}, [], trace=trace)
    again = coverage.milliseconds(ctx, coverage.recomputed)
    once = coverage.milliseconds(ctx, lambda ev: not coverage.recomputed(ev))
    assert (again + once) * 1e-3 == pytest.approx(tr.busy_seconds(trace)[0] / 2, rel=1e-12)
    assert coverage.recomputed(E("op", 0, 1, "transpose(jvp(jit(run)))/checkpoint/rematted_computation/ht.mlp"))
    assert not coverage.recomputed(E("op", 0, 1, "transpose(jvp(jit(run)))/checkpoint/ht.mlp"))


@pytest.mark.parametrize("event, want", [
    (E("fusion.1", 0, 1, FWD + "/ht.attention.proj/ht.attention"), "ht.attention"),
    (E("fusion.1", 0, 1, "transpose(jvp(ht.lm.head_loss))/while/body"), "ht.lm.head_loss"),
    (E("ragged-dot-none.12", 0, 1, FWD + "/while/body/jit(_sorted_rows)"), coverage.GROUPED),
    (E("ragged-dot-none.12", 0, 1, ""), coverage.GROUPED),
    (E("copy.3", 0, 1, "jvp(jit(run))/while/body"), ""), (E("copy-done.3", 0, 1, ""), ""),
])
def test_the_layer_an_operation_is_counted_under(event, want):
    assert coverage.innermost(event) == want


# ---------------------------------------------------------------------- #
# a step recorded on the chip with the scopes in the program
# ---------------------------------------------------------------------- #
# ``run.py --trace 1 --keep-trace`` of the cell from the committed files of PR 36 (seed
# 3600000202), cut to its first traced job; milliseconds as that chip run's reduction gives them
RECORDED = {"attention_proj_ms": 96.28472, "embed_ms": 8.653179, "norm_ms": 4.250721, "cast_ms": 4.869183,
            "block_other_ms": 11.676284, "unscoped_ms": 12.189135, "recompute_ms": 73.970644,
            "shortconv_proj_ms": None, "mlp_ms": None, "kda_prepare_ms": None, "kda_recur_ms": None}


@pytest.fixture(scope="module")
def recorded_step():
    raw = tr.load(os.path.join(HERE, "fixtures_scopes", "v5e_1chip_smallthinker_step.json.gz"))
    assert tr.clock_lead(raw) == -1261381.0
    return tr.calibrate(raw)


def test_the_recorded_step_is_one_job_and_small(recorded_step):
    assert os.listdir(os.path.join(HERE, "fixtures_scopes")) == ["v5e_1chip_smallthinker_step.json.gz"]
    assert os.path.getsize(os.path.join(HERE, "fixtures_scopes", "v5e_1chip_smallthinker_step.json.gz")) < 1_000_000
    (chip,) = recorded_step.devices
    assert len(tr.jobs(recorded_step)) == 1 and len(chip.modules) == 3  # batch, step, tally
    assert tr.busy_seconds(recorded_step) == (pytest.approx(0.542112626), pytest.approx(0.545500978))
    assert {coverage.innermost(e) for e in chip.ops} == {
        "", coverage.GROUPED, "ht.attention", "ht.attention.proj", "ht.attention.window", "ht.lm.block", "ht.lm.cast",
        "ht.lm.embed", "ht.lm.head_loss", "ht.lm.norm", "ht.moe.combine", "ht.moe.dispatch", "ht.moe.experts",
        "ht.moe.route", "ht.optim.update"}


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_on_the_recorded_step(recorded_step, name):
    found = _read(name, recorded_step)
    assert found is None if RECORDED[name] is None else found == pytest.approx(RECORDED[name])
    # a scope the cell's model lacks reads nothing, so the manifest lists it for other cells alone
    listed = {m["name"] for m in BENCH.metrics("per_layer", STEP)}
    assert (name in listed) == (RECORDED[name] is not None)


def test_the_cells_metrics_by_innermost_scope_and_the_unscoped_rest_are_the_busy_time(recorded_step):
    """Every ``*_ms`` metric the manifest lists for the cell but the one that cuts
    across the layers: each operation is counted once, and none is left out."""
    parts = [m["name"] for m in BENCH.metrics("per_layer", STEP)
             if m["unit"] == "ms" and m["name"] not in NOT_IN_THE_SUM | {"collective_ms_per_job"}]
    assert len(parts) == 12
    total = sum(_read(name, recorded_step) for name in parts)
    busy_ms = 1e3 * tr.busy_seconds(recorded_step)[0]
    assert total == pytest.approx(busy_ms, rel=1e-2) and total == pytest.approx(busy_ms, abs=1e-3)
    assert _read("unscoped_ms", recorded_step) < 0.03 * busy_ms


def test_what_the_checkpoint_ran_again_and_the_rest_are_the_recorded_steps_busy_time(recorded_step):
    ctx = runner.Context({}, {}, {}, 1, {}, [], trace=recorded_step)
    again = coverage.milliseconds(ctx, coverage.recomputed)
    once = coverage.milliseconds(ctx, lambda ev: not coverage.recomputed(ev))
    (chip,) = recorded_step.devices
    self_ns = sum(ns for _, ns, _ in tr.nested(chip.ops))
    assert again + once == pytest.approx(self_ns / 1e6, rel=1e-12) and self_ns == 1e9 * tr.busy_seconds(recorded_step)[0]
    # by layer: which forward passes the step pays twice
    by_layer = {}
    for ev, ns, _ in tr.nested(chip.ops):
        if coverage.recomputed(ev):
            by_layer[coverage.innermost(ev)] = by_layer.get(coverage.innermost(ev), 0.0) + ns / 1e6
    assert {k: round(v, 1) for k, v in by_layer.items() if v > 1} == {
        "ht.attention.window": 27.1, "ht.attention.proj": 25.7, "ht.attention": 15.9, "ht.lm.head_loss": 1.6,
        "ht.lm.cast": 1.2, "ht.lm.block": 1.1}


# ---------------------------------------------------------------------- #
# a trace recorded before any scope
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_on_the_fit_recorded_before_any_scope(name):
    trace = tr.calibrate(tr.load(os.path.join(HERE, "fixtures", "v5e_1chip_kmeans_fit.json.gz")))
    found = _read(name, trace)
    if name != "unscoped_ms":
        assert found is None
    else:  # all of it: a number, so the cell's traced line on that trace stays whole
        lo, hi = tr.window(trace)
        self_ns = sum(ns for ev, ns, _ in tr.nested(trace.devices[0].ops) if lo <= ev.start < hi)
        assert found == pytest.approx(self_ns / 1e6 / 3) and found == pytest.approx(271.2, rel=2e-3)
