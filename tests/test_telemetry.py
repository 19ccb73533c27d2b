"""Telemetry layer (ISSUE 3): spans, byte accounting, histograms, export —
plus the profiler satellite fixes (provider namespacing, exception-safe
timer, weakref pruning)."""

import gc
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.utils import profiler, telemetry

# NOT mp-marked: these tests toggle global telemetry state and write rank
# files into tmp dirs — under the SPMD lane's shared tmp_path both ranks
# would race on rank{k}.jsonl sets and counter totals.  The multi-rank
# telemetry path is covered by the dryrun's per-rank export + merge check
# (scripts/multiprocess_dryrun.py, asserted in test_multiprocess.py).


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disarmed with empty rings/counters and leaves the
    process the same way (telemetry state is global by design)."""
    telemetry.disable()
    telemetry.reset()
    profiler.reset_counters()
    yield
    telemetry.disable()
    telemetry.reset()
    profiler.reset_counters()


def _ring_names():
    return [r[0] for r in telemetry._ring]


class TestSpans:
    def test_span_records_nesting_and_self_time(self):
        telemetry.enable()
        with telemetry.span("outer", kind="test"):
            with telemetry.span("inner"):
                pass
        recs = {r[0]: r for r in telemetry._ring}
        assert set(recs) == {"outer", "inner"}
        name, ts, dur, self_s, depth, attrs = recs["outer"]
        assert depth == 0 and attrs == {"kind": "test"}
        assert recs["inner"][4] == 1  # nested depth
        # parent self-time excludes the child's wall time
        assert recs["outer"][3] <= recs["outer"][2]
        assert recs["inner"][2] <= recs["outer"][2]

    def test_span_survives_exceptions_and_tags_error(self):
        telemetry.enable()
        with pytest.raises(ValueError):
            with telemetry.span("boom"):
                raise ValueError("x")
        (rec,) = list(telemetry._ring)
        assert rec[0] == "boom" and rec[5]["error"] == "ValueError"

    def test_disabled_span_is_shared_null_object(self):
        assert telemetry.span("a") is telemetry.span("b")
        with telemetry.span("nope", anything=1) as s:
            s.set(more=2)  # null span absorbs attribute updates too
        assert len(telemetry._ring) == 0

    def test_span_attrs_set_midway(self):
        telemetry.enable()
        with telemetry.span("s", a=1) as s:
            s.set(b=2)
        (rec,) = list(telemetry._ring)
        assert rec[5] == {"a": 1, "b": 2}

    def test_disabled_noop_under_jit_tracing(self):
        """Satellite: span() inside a traced function must be a no-op when
        disabled and must not break tracing when enabled."""

        def f(a):
            with telemetry.span("traced.block"):
                return a * 2

        out = jax.jit(f)(jnp.ones(4))
        np.testing.assert_allclose(np.asarray(out), 2.0)
        assert len(telemetry._ring) == 0  # disabled: nothing recorded

        telemetry.enable()
        out = jax.jit(f)(jnp.ones(8))  # fresh shape -> fresh trace
        np.testing.assert_allclose(np.asarray(out), 2.0)
        assert "traced.block" in _ring_names()  # recorded at trace time

    def test_ring_is_bounded(self):
        telemetry.enable()
        for i in range(telemetry._ring.maxlen + 100):
            telemetry.record_event("e", 1e-6)
        assert len(telemetry._ring) == telemetry._ring.maxlen


class TestCollectiveAccounting:
    def _fresh_comm(self):
        # a fresh Communication => fresh program caches => the collectives
        # genuinely re-stage (byte accounting happens at trace time)
        return ht.core.communication.Communication(ht.communication.get_comm().mesh)

    def test_resplit_bytes_and_calls(self):
        m = ht.reshape(ht.arange(64, dtype=ht.float32, split=0), (8, 8))
        m.resplit_(1)
        c = profiler.counters()
        assert c["comm.resplit.calls"] >= 1
        p = m.comm.size
        # (p-1)/p of the global payload crosses the wire
        assert c["comm.resplit.bytes"] >= int(64 * 4 * (p - 1) / p)

    def test_noop_resplit_not_counted(self):
        """A resplit to the sharding the array already carries moves no
        bytes and must not inflate the redistribution traffic metric."""
        x = ht.zeros((16, 16), split=0)
        x._jarray  # force canonical placement
        before = profiler.counters().get("comm.resplit.calls", 0)
        _ = x.comm.resplit(x._jarray, 0)
        _ = x.comm.resplit(x._jarray, 0, donate=True)
        c = profiler.counters()
        assert c.get("comm.resplit.calls", 0) == before
        assert c.get("comm.resplit.bytes", 0) == 0

    def test_allreduce_traffic_factor(self):
        comm = self._fresh_comm()
        p = comm.size
        prog = comm.shard_map(lambda v: comm.Allreduce(v), ((1, 0),), (1, None))
        out = prog(jnp.ones(8 * p, jnp.float32))
        np.testing.assert_allclose(np.asarray(out)[:1], p)  # p ones summed
        c = profiler.counters()
        assert c["comm.Allreduce.calls"] == 1
        # per-shard payload 8*4 bytes x ring factor 2(p-1)/p
        assert c["comm.Allreduce.bytes"] == int(round(8 * 4 * 2 * (p - 1) / p))

    def test_summa_matmul_shows_send_bytes(self):
        """Acceptance: per-collective calls/bytes for a SUMMA matmul."""
        comm = self._fresh_comm()
        rng = np.random.default_rng(0)
        a = ht.array(rng.standard_normal((32, 32)).astype(np.float32), split=0, comm=comm)
        b = ht.array(rng.standard_normal((32, 32)).astype(np.float32), split=0, comm=comm)
        out = ht.linalg.matmul_summa(a, b)
        np.testing.assert_allclose(out.numpy(), a.numpy() @ b.numpy(), atol=1e-4)
        c = profiler.counters()
        assert c["comm.Send.calls"] >= 1  # the K-block ring rotation
        assert c["comm.Send.bytes"] > 0

    def test_scan_exscan_prod_attribution(self):
        """Scan/Exscan account under their own names (not the shared
        helper), and Allreduce-prod accounts ONCE with scan+psum cost."""
        comm = self._fresh_comm()
        p = comm.size
        nb = 8 * 4  # per-shard payload bytes below
        x = jnp.ones(8 * p, jnp.float32)
        _ = comm.shard_map(lambda v: comm.Exscan(v), ((1, 0),), (1, 0))(x)
        _ = comm.shard_map(lambda v: comm.Scan(v), ((1, 0),), (1, 0))(x)
        _ = comm.shard_map(lambda v: comm.Allreduce(v, "prod"), ((1, 0),), (1, None))(x)
        c = profiler.counters()
        logp = max(p - 1, 0).bit_length()
        assert c["comm.Exscan.calls"] == 1
        assert c["comm.Exscan.bytes"] == int(round(nb * (logp + 1)))
        assert c["comm.Scan.calls"] == 1  # Exscan's inner scan not re-counted
        assert c["comm.Scan.bytes"] == int(round(nb * logp))
        assert c["comm.Allreduce.calls"] == 1
        assert c["comm.Allreduce.bytes"] == int(round(nb * (2 * (p - 1) / p + logp)))

    def test_gather_fallback_counter(self):
        """Satellite: gather-based collectives count under
        comm.gather_fallback.<name> even below the warn threshold."""
        comm = self._fresh_comm()
        p = comm.size
        prog = comm.shard_map(lambda v: comm.Gather(v), ((1, 0),), (1, 0))
        _ = prog(jnp.arange(8 * p, dtype=jnp.float32))
        c = profiler.counters()
        assert c["comm.gather_fallback.Gather"] >= 1
        assert c["comm.Gather.calls"] >= 1

    def test_payload_nbytes_on_tracers(self):
        from heat_tpu.core.communication import _payload_nbytes

        assert _payload_nbytes(jnp.ones((4, 2), jnp.float32)) == 32

        seen = {}

        def f(v):
            seen["n"] = _payload_nbytes(v)  # v is a tracer here
            return v

        jax.jit(f)(jnp.ones((4, 2), jnp.float32))
        assert seen["n"] == 32


class TestHistogram:
    def test_summary_and_quantiles(self):
        h = telemetry.Histogram("lat")
        for v in [1e-5] * 50 + [1e-3] * 40 + [1e-1] * 10:
            h.observe(v)
        s = h.summary()
        assert s["count"] == 100
        assert s["min_s"] == pytest.approx(1e-5)
        assert s["max_s"] == pytest.approx(1e-1)
        assert s["p50_s"] <= s["p90_s"] <= s["p99_s"] <= s["max_s"] * 1.6
        # p50 sits in the 10us decade, p99 near the top
        assert s["p50_s"] < 1e-3
        assert s["p99_s"] > 1e-2

    def test_bounded_memory_and_degenerate_values(self):
        h = telemetry.Histogram("x")
        n_slots = len(h.counts)
        for i in range(10000):
            h.observe(i * 1e-7)
        h.observe(float("nan"))
        h.observe(-1.0)
        h.observe(1e9)  # overflow bin
        assert len(h.counts) == n_slots
        assert h.count == 10003

    def test_observe_helper_and_report_section(self):
        telemetry.observe("unit.lat_s", 0.01)
        telemetry.observe("unit.lat_s", 0.02)
        rep = telemetry.report()
        assert rep["histograms"]["unit.lat_s"]["count"] == 2


class TestReportAndExport:
    def test_report_merges_counters_hists_spans(self):
        telemetry.enable()
        profiler.counter_inc("unit.events", 3)
        telemetry.observe("unit.lat_s", 0.5)
        with telemetry.span("unit.work"):
            pass
        rep = telemetry.report()
        assert rep["enabled"] is True
        assert rep["counters"]["unit.events"] == 3
        assert "cache.hits" in rep["counters"]  # cache.* provider rides along
        assert rep["histograms"]["unit.lat_s"]["count"] == 1
        assert any(r["name"] == "unit.work" for r in rep["top_spans"])

    def test_flush_and_cli_merge(self, tmp_path):
        telemetry.enable()
        with telemetry.span("unit.flushme", tag="t"):
            pass
        telemetry.observe("unit.lat_s", 0.002)
        profiler.counter_inc("unit.flush_counter", 7)
        d = str(tmp_path / "tel")
        path = telemetry.flush(d)
        assert path is not None and os.path.exists(path)
        lines = [json.loads(line) for line in open(path)]
        types = {rec["type"] for rec in lines}
        assert {"meta", "span", "counters", "hist"} <= types
        assert len(telemetry._ring) == 0  # flush drains the ring

        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "telemetry_report",
            os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "scripts", "telemetry_report.py"),
        )
        trep = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trep)
        merged = trep.merge_files(trep.find_rank_files(d))
        assert any(r["name"] == "unit.flushme" for r in merged["span_summary"])
        assert merged["counters"]["unit.flush_counter"] == 7
        assert merged["histograms"]["unit.lat_s"]["count"] == 1
        text = trep.render(merged)
        assert "unit.flushme" in text and "unit.flush_counter" in text
        # two-rank merge: fake a second rank's file, counters must SUM
        second = [dict(rec, rank=1) for rec in lines]
        with open(os.path.join(d, "rank1.jsonl"), "w") as fh:
            for rec in second:
                fh.write(json.dumps(rec) + "\n")
        merged2 = trep.merge_files(trep.find_rank_files(d))
        assert merged2["ranks"] == [0, 1]
        assert merged2["counters"]["unit.flush_counter"] == 14
        assert merged2["histograms"]["unit.lat_s"]["count"] == 2
        # CLI entry point end to end
        out_json = str(tmp_path / "merged.json")
        assert trep.main([d, "--json", out_json]) == 0
        assert json.load(open(out_json))["ranks"] == [0, 1]
        # a SECOND flush of the same rank appends a fresh cumulative
        # histogram snapshot — within one rank the last snapshot must win
        # (summing would double-count every observation)
        telemetry.observe("unit.lat_s", 0.002)
        telemetry.flush(d)
        merged3 = trep.merge_files(trep.find_rank_files(d))
        # rank0 now has 2 observations (last snapshot), fake rank1 has 1
        assert merged3["histograms"]["unit.lat_s"]["count"] == 3

    def test_flush_without_dir_is_none(self):
        telemetry.enable()
        env_dir = os.environ.pop("HEAT_TPU_TELEMETRY_DIR", None)
        saved = telemetry._flush_dir
        telemetry._flush_dir = None
        try:
            assert telemetry.flush() is None
        finally:
            telemetry._flush_dir = saved
            if env_dir is not None:
                os.environ["HEAT_TPU_TELEMETRY_DIR"] = env_dir


class TestIOInstrumentation:
    def test_checkpoint_bytes_fsync_and_span(self, tmp_path):
        telemetry.enable()
        x = ht.arange(64, dtype=ht.float32, split=0)
        ht.save_array_checkpoint(x, str(tmp_path / "ckpt"))
        c = profiler.counters()
        assert c["io.bytes_written"] > 64 * 4  # chunks + meta + LATEST tmp
        assert c["io.fsync.calls"] >= 4  # files + dir fsyncs
        names = _ring_names()
        assert "io.save_array_checkpoint" in names
        back = ht.load_array_checkpoint(str(tmp_path / "ckpt"))
        np.testing.assert_allclose(back.numpy(), x.numpy())
        assert "io.load_array_checkpoint" in _ring_names()

    def test_pytree_checkpoint_counts_bytes(self, tmp_path):
        from heat_tpu.core import io as htio

        telemetry.enable()
        tree = {"w": jnp.ones((8, 8), jnp.float32)}
        htio.save_checkpoint(tree, str(tmp_path / "t.npz"))
        c = profiler.counters()
        assert c["io.bytes_written"] > 0
        assert "io.save_checkpoint" in _ring_names()


class TestOptimInstrumentation:
    def test_eager_step_histogram_and_guard_provider(self):
        telemetry.enable()
        opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
        params = {"w": jnp.ones(4, jnp.float32)}
        grads = {"w": jnp.full(4, 0.5, jnp.float32)}
        params = opt.step(params, grads)
        rep = telemetry.report()
        assert rep["histograms"]["optim.step_dispatch_s"]["count"] == 1
        assert any(r["name"] == "optim.step" for r in rep["top_spans"])
        # guard counters surface under the instance's unique provider key
        assert rep["counters"][f"{opt.profiler_key}.steps"] == 1
        assert rep["counters"][f"{opt.profiler_key}.skipped_steps"] == 0

    def test_daso_step_histogram(self):
        if len(jax.devices()) % 2:
            pytest.skip("DASO needs an even device count")
        telemetry.enable()
        from heat_tpu.optim.dp_optimizer import DASO, DataParallelOptimizer

        daso = DASO(DataParallelOptimizer("sgd", lr=0.1), warmup_steps=0)
        daso.init(ht.nn.Sequential(ht.nn.Linear(8, 4)), key=jax.random.key(0))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
        daso.step(lambda pred, t: jnp.mean((pred - t) ** 2), x, y)
        rep = telemetry.report()
        assert rep["histograms"]["daso.step_dispatch_s"]["count"] == 1
        assert any(r["name"] == "daso.step" for r in rep["top_spans"])

    def test_train_step_wrapper_keeps_lower(self):
        opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
        dp = ht.nn.DataParallel(ht.nn.Sequential(ht.nn.Linear(8, 4)), optimizer=opt)
        params = dp.init(jax.random.key(0))
        state = opt.init_state(params)
        step = dp.make_train_step(lambda p, y: jnp.mean((p - y) ** 2))
        x = jnp.zeros((16, 8), jnp.float32)
        y = jnp.zeros((16, 4), jnp.float32)
        assert "input_output_alias" in step.lower(params, state, x, y).compile().as_text()
        telemetry.enable()
        params, state, loss = step(params, state, x, y)
        rep = telemetry.report()
        assert rep["histograms"]["nn.train_step_dispatch_s"]["count"] == 1


class TestProfilerSatellites:
    def test_provider_prefix_collision_regression(self):
        """Satellite: a provider key that startswith the provider name must
        NOT overwrite an identically-named plain counter."""
        profiler.counter_inc("svc_total", 3)

        key = profiler.register_counter_provider("svc", lambda: {"svc_total": 7})
        try:
            c = profiler.counters()
            assert c["svc_total"] == 3  # the plain counter survives
            assert c[f"{key}.svc_total"] == 7  # the provider is namespaced
        finally:
            profiler._providers.pop(key, None)

    def test_provider_already_dotted_key_not_double_prefixed(self):
        key = profiler.register_counter_provider("dot", lambda: {"dot.x": 1, "y": 2})
        try:
            c = profiler.counters()
            assert c["dot.x"] == 1 and c["dot.y"] == 2
            assert "dot.dot.x" not in c
        finally:
            profiler._providers.pop(key, None)

    def test_timer_exception_safe(self):
        """Satellite: a raising block still records its elapsed time."""
        holder = {}
        with pytest.raises(RuntimeError):
            with profiler.timer("t", holder):
                raise RuntimeError("boom")
        assert holder["t"] >= 0.0

    def test_timer_normal_path(self):
        holder = {}
        with profiler.timer("ok", holder):
            pass
        assert holder["ok"] >= 0.0

    def test_provider_weakref_pruned_after_gc(self):
        """Satellite: a bound-method provider dies with its owner and is
        dropped at the next counters() read."""

        class Owner:
            def snapshot(self):
                return {"alive": 1}

        o = Owner()
        key = profiler.register_counter_provider("weakowner", o.snapshot)
        assert profiler.counters()[f"{key}.alive"] == 1
        assert key in profiler._providers
        del o
        gc.collect()
        c = profiler.counters()
        assert f"{key}.alive" not in c
        assert key not in profiler._providers  # pruned, not just skipped


class TestServingSLOReport:
    """ISSUE 10 satellite: scripts/telemetry_report.py renders the
    per-tenant SLO table (p50/p99 queue wait + execution latency) from
    whichever serving artifacts exist — sched.job spans, the scheduler
    journal, or both.  A journal-only dir (all a SIGKILLed serving rank
    leaves behind) is a legitimate target: exit 0, full table."""

    def _trep(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "telemetry_report_slo",
            os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "scripts", "telemetry_report.py"),
        )
        trep = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trep)
        return trep

    def _write_journal(self, d):
        recs = [
            {"type": "meta", "schema": 1, "epoch": 0, "t": 100.0},
            {"type": "submitted", "id": "a", "kind": "matmul",
             "tenant": "acme", "priority": 0, "t": 100.0},
            {"type": "dispatched", "id": "a", "seq": 1, "attempt": 1,
             "t": 100.25, "epoch": 0},
            {"type": "done", "id": "a", "exec_s": 0.5, "t": 100.75,
             "epoch": 0},
            {"type": "submitted", "id": "b", "kind": "solve",
             "tenant": "acme", "priority": 0, "t": 100.0},
            {"type": "dispatched", "id": "b", "seq": 2, "attempt": 1,
             "t": 101.0, "epoch": 0},
            {"type": "failed", "id": "b", "reason": "deadline_expired",
             "t": 101.5, "epoch": 0},
            {"type": "shed", "id": "c", "kind": "matmul",
             "tenant": "globex", "reason": "queue_full", "t": 100.1,
             "epoch": 0},
        ]
        path = os.path.join(d, "sched_journal.jsonl")
        with open(path, "w") as fh:
            for r in recs:
                fh.write(json.dumps(r) + "\n")
        return path

    def test_journal_only_dir_renders_slo_exit_zero(self, tmp_path, capsys):
        trep = self._trep()
        d = str(tmp_path)
        self._write_journal(d)
        assert trep.main([d]) == 0
        out = capsys.readouterr().out
        assert "per-tenant serving SLO" in out
        # acme: 2 jobs (1 done, 1 failed); globex: 1 shed
        acme = [l for l in out.splitlines() if l.startswith("acme")][0]
        assert acme.split()[1:5] == ["2", "1", "1", "0"]
        globex = [l for l in out.splitlines() if l.startswith("globex")][0]
        assert globex.split()[1:5] == ["1", "0", "0", "1"]
        # journal-timestamp latencies: acme queue wait p50 = 250 ms
        assert "250.0" in acme

    def test_spans_only_dir_renders_slo(self, tmp_path, capsys):
        trep = self._trep()
        d = str(tmp_path)
        spans = [
            {"type": "span", "rank": 0, "name": "sched.job", "ts": 100.0,
             "dur_s": 0.2, "self_s": 0.2, "depth": 0,
             "attrs": {"id": "a", "tenant": "acme", "kind": "matmul",
                       "outcome": "done", "queue_wait_s": 0.05,
                       "attempts": 1}},
            {"type": "span", "rank": 0, "name": "sched.job", "ts": 101.0,
             "dur_s": 0.4, "self_s": 0.4, "depth": 0,
             "attrs": {"id": "b", "tenant": "acme", "kind": "matmul",
                       "outcome": "retries_exhausted", "queue_wait_s": 0.15,
                       "attempts": 3}},
        ]
        with open(os.path.join(d, "rank0.jsonl"), "w") as fh:
            for r in spans:
                fh.write(json.dumps(r) + "\n")
        assert trep.main([d, "--timeline", "0"]) == 0
        out = capsys.readouterr().out
        assert "per-tenant serving SLO" in out
        acme = [l for l in out.splitlines() if l.startswith("acme")][0]
        # 2 jobs, 1 done, 1 failed — outcome counts from the span attrs
        assert acme.split()[1:5] == ["2", "1", "1", "0"]
        # exec p50 from span durations: 200 ms
        assert "200.0" in acme

    def test_spans_and_journal_merge(self, tmp_path, capsys):
        """Both present: outcome counts come from the journal (it alone
        knows shed jobs), latencies from the spans."""
        trep = self._trep()
        d = str(tmp_path)
        self._write_journal(d)
        with open(os.path.join(d, "rank0.jsonl"), "w") as fh:
            fh.write(json.dumps(
                {"type": "span", "rank": 0, "name": "sched.job", "ts": 100.0,
                 "dur_s": 0.125, "self_s": 0.125, "depth": 0,
                 "attrs": {"id": "a", "tenant": "acme", "kind": "matmul",
                           "outcome": "done", "queue_wait_s": 0.0625,
                           "attempts": 1}}) + "\n")
        assert trep.main([d, "--timeline", "0"]) == 0
        out = capsys.readouterr().out
        acme = [l for l in out.splitlines() if l.startswith("acme")][0]
        assert acme.split()[1:5] == ["2", "1", "1", "0"]  # journal counts
        assert "125.0" in acme and "62.5" in acme  # span latencies

    def test_spans_deduped_across_ranks_by_job_id(self, tmp_path, capsys):
        """Review finding: every rank of an SPMD serve world emits an
        identical sched.job span per job — a 2-rank dir must count each
        job ONCE, not once per rank."""
        trep = self._trep()
        d = str(tmp_path)
        span = {"type": "span", "name": "sched.job", "ts": 100.0,
                "dur_s": 0.2, "self_s": 0.2, "depth": 0,
                "attrs": {"id": "a", "tenant": "acme", "kind": "matmul",
                          "outcome": "done", "queue_wait_s": 0.05,
                          "attempts": 1}}
        for rank in (0, 1):
            with open(os.path.join(d, f"rank{rank}.jsonl"), "w") as fh:
                fh.write(json.dumps(dict(span, rank=rank)) + "\n")
        assert trep.main([d, "--timeline", "0"]) == 0
        out = capsys.readouterr().out
        acme = [l for l in out.splitlines() if l.startswith("acme")][0]
        assert acme.split()[1:5] == ["1", "1", "0", "0"]  # one job, not two

    def test_no_serving_artifacts_is_silent(self, tmp_path):
        trep = self._trep()
        d = str(tmp_path)
        with open(os.path.join(d, "rank0.jsonl"), "w") as fh:
            fh.write(json.dumps({"type": "span", "rank": 0,
                                 "name": "dispatch.local", "ts": 1.0,
                                 "dur_s": 0.1, "self_s": 0.1,
                                 "depth": 0}) + "\n")
        assert trep.slo_section([d]) == ""

    def test_corrupt_journal_degrades_to_note(self, tmp_path):
        trep = self._trep()
        d = str(tmp_path)
        with open(os.path.join(d, "sched_journal.jsonl"), "w") as fh:
            fh.write(json.dumps({"type": "meta", "schema": 99}) + "\n")
        section = trep.slo_section([d])
        assert "unreadable" in section  # named, not crashed


# ---------------------------------------------------------------------- #
# trace propagation (ISSUE 11 tentpole): contextvar-carried trace identity
# ---------------------------------------------------------------------- #
class TestTracing:
    def test_spans_carry_trace_and_parent_ids(self):
        telemetry.enable()
        with telemetry.tracing(name="t") as tid:
            with telemetry.span("outer"):
                with telemetry.span("inner"):
                    pass
        recs = {r[0]: r[5] for r in telemetry._ring}
        assert recs["outer"]["trace_id"] == tid
        assert recs["inner"]["trace_id"] == tid
        assert recs["inner"]["parent_id"] == recs["outer"]["span_id"]
        assert "parent_id" not in recs["outer"]  # the trace root

    def test_untraced_spans_carry_no_trace_keys(self):
        telemetry.enable()
        with telemetry.span("plain", kind="x"):
            pass
        (rec,) = list(telemetry._ring)
        assert rec[5] == {"kind": "x"}

    def test_tracing_scopes_and_restores(self):
        assert telemetry.current_trace_id() is None
        with telemetry.tracing(trace_id="aaaa000000000000"):
            assert telemetry.current_trace_id() == "aaaa000000000000"
            with telemetry.tracing(trace_id="bbbb000000000000"):
                assert telemetry.current_trace_id() == "bbbb000000000000"
            assert telemetry.current_trace_id() == "aaaa000000000000"
        assert telemetry.current_trace_id() is None

    def test_mint_is_deterministic_per_process_sequence(self):
        """SPMD contract: ranks executing the identical mint sequence
        derive identical ids — the id depends only on (name, counter,
        restart epoch), never on pid/time/entropy."""
        seq0 = telemetry._trace_seq
        a = telemetry.mint_trace_id("x")
        telemetry._trace_seq = seq0
        b = telemetry.mint_trace_id("x")
        assert a == b and len(a) == 16
        assert telemetry.mint_trace_id("x") != a  # counter advanced

    def test_record_event_inherits_and_parents_on_open_span(self):
        telemetry.enable()
        with telemetry.tracing(name="e") as tid:
            with telemetry.span("outer"):
                telemetry.record_event("leaf", 0.001)
        recs = {r[0]: r[5] for r in telemetry._ring}
        assert recs["leaf"]["trace_id"] == tid
        assert recs["leaf"]["parent_id"] == recs["outer"]["span_id"]

    def test_tracing_works_with_telemetry_disabled(self):
        """The contextvar is independent of the span ring: the flight
        recorder reads it even when nothing exports spans."""
        with telemetry.tracing(trace_id="cccc000000000000"):
            assert telemetry.current_trace_id() == "cccc000000000000"
        assert len(telemetry._ring) == 0

    def test_flush_exports_trace_attrs(self, tmp_path):
        telemetry.enable()
        with telemetry.tracing(name="f") as tid:
            with telemetry.span("unit.traced"):
                pass
        path = telemetry.flush(str(tmp_path))
        spans = [json.loads(line) for line in open(path)
                 if json.loads(line).get("type") == "span"]
        (rec,) = [s for s in spans if s["name"] == "unit.traced"]
        assert rec["attrs"]["trace_id"] == tid
        assert "span_id" in rec["attrs"]

    def test_guarded_wait_leaf_event_lands_in_ring(self):
        """health.guard_blocking's observed wait is BOTH a histogram
        observation and a <what>.wait leaf record — the per-step position
        the stepprof breakdown attributes from."""
        from heat_tpu.utils import health

        telemetry.enable()
        telemetry.reset()
        with telemetry.span("unit.step"):
            health.guard_blocking(lambda: time.sleep(0.002), "unit.block")
        names = _ring_names()
        assert "unit.block.wait" in names
        rep = telemetry.report()
        assert rep["histograms"]["unit.block.wait"]["count"] == 1
        # the wait counted as the step's CHILD time (not self-time)
        (step,) = [r for r in telemetry._ring if r[0] == "unit.step"]
        (wait,) = [r for r in telemetry._ring if r[0] == "unit.block.wait"]
        assert step[3] <= step[2] - wait[2] + 1e-4


class TestRingDropped:
    def test_eviction_counted_and_surfaced(self):
        telemetry.enable()
        for _ in range(telemetry._ring.maxlen + 9):
            telemetry.record_event("e", 1e-6)
        assert telemetry.ring_dropped() == 9
        rep = telemetry.report()
        assert rep["counters"]["telemetry.ring.dropped"] == 9

    def test_no_eviction_no_counter(self):
        telemetry.enable()
        telemetry.record_event("e", 1e-6)
        assert telemetry.ring_dropped() == 0
        assert "telemetry.ring.dropped" not in telemetry.report()["counters"]

    def test_reset_zeroes_the_counter(self):
        telemetry.enable()
        for _ in range(telemetry._ring.maxlen + 1):
            telemetry.record_event("e", 1e-6)
        assert telemetry.ring_dropped() == 1
        telemetry.reset()
        assert telemetry.ring_dropped() == 0

    def test_flush_exports_the_counter_and_cli_surfaces_it(self, tmp_path):
        telemetry.enable()
        for _ in range(telemetry._ring.maxlen + 3):
            telemetry.record_event("e", 1e-6)
        path = telemetry.flush(str(tmp_path))
        counters = [json.loads(line) for line in open(path)
                    if json.loads(line).get("type") == "counters"]
        assert counters[-1]["values"]["telemetry.ring.dropped"] == 3

        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "telemetry_report_drop",
            os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "scripts", "telemetry_report.py"),
        )
        trep = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trep)
        merged = trep.merge_files(trep.find_rank_files(str(tmp_path)))
        assert merged["counters"]["telemetry.ring.dropped"] == 3
        assert "telemetry.ring.dropped" in trep.render(merged)


class TestHistogramP999:
    def test_p999_present_and_monotone(self):
        h = telemetry.Histogram("t")
        for _ in range(2000):
            h.observe(1e-4)
        for _ in range(3):
            h.observe(0.5)  # the deep tail
        s = h.summary()
        assert s["p999_s"] >= s["p99_s"] >= s["p90_s"]
        # 3/2003 > 0.1% of mass: p99.9 must land in the tail bin
        assert s["p999_s"] > 0.1

    def test_empty_histogram_summary_unchanged(self):
        assert telemetry.Histogram("t").summary() == {"count": 0}
