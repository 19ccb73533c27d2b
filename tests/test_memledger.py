"""Device-memory ledger (ISSUE 14): per-buffer provenance, telescoping
live-bytes, the budget reconciliation observed from inside, and the OOM
post-mortem path."""

import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _hooks, redistribution
from heat_tpu.utils import faults, flightrec, memledger, profiler, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _ledger():
    """Armed, zeroed ledger per test; disarmed + zeroed afterwards."""
    memledger._reset_for_tests()
    memledger.enable()
    yield memledger
    memledger.disable()
    memledger._reset_for_tests()


def _nb(x):
    return x.size * x.dtype.np_dtype().itemsize


# ---------------------------------------------------------------------- #
# registry basics
# ---------------------------------------------------------------------- #
class TestRegistry:
    def test_factory_registers_and_weakref_decrements(self):
        x = ht.zeros((16, 8), dtype=ht.float32, split=0)
        assert memledger.live_bytes() == _nb(x) == 512
        peak = memledger.peak_bytes()
        del x
        gc.collect()
        assert memledger.live_bytes() == 0
        # the peak survives the death — it is a high-water mark
        assert memledger.peak_bytes() == peak == 512

    def test_provenance_fields(self):
        _x = ht.arange(64, dtype=ht.float32, split=0)
        (top,) = memledger.top_buffers(1)
        assert top["op"] == "arange"
        assert top["site"] == "factory"
        assert top["category"] == "activation"
        assert top["nbytes"] == 256

    def test_register_idempotent_per_buffer(self):
        x = ht.zeros((8, 8), dtype=ht.float32)
        before = memledger.live_bytes()
        memledger.register(x._parray, op="again", site="factory")
        memledger.register(x._parray, op="andagain", site="factory")
        assert memledger.live_bytes() == before
        # first registration's provenance wins
        assert memledger.top_buffers(1)[0]["op"] == "zeros"

    def test_tracers_never_register(self):
        import jax

        before = memledger.live_bytes()

        @jax.jit
        def f(a):
            memledger.register(a, op="traced", site="factory")
            return a * 2

        f(ht.ones((4,), dtype=ht.float32)._jarray)
        assert memledger.live_bytes() == before

    def test_consume_decrements_once_and_is_idempotent(self):
        x = ht.zeros((32,), dtype=ht.float32)
        j = x._parray
        assert memledger.live_bytes() == 128
        memledger.consume(j)
        assert memledger.live_bytes() == 0
        memledger.consume(j)  # double consume: no underflow
        assert memledger.live_bytes() == 0
        del x, j
        gc.collect()  # the weakref callback after consume must not double-free
        assert memledger.live_bytes() == 0

    def test_transfer_moves_entry_without_double_count(self):
        import jax.numpy as jnp

        a = jnp.ones((64,), jnp.float32)
        memledger.register(a, op="init", site="factory", category="param")
        peak0 = memledger.peak_bytes()
        b = jnp.ones((64,), jnp.float32) * 2
        memledger.transfer(a, b)
        assert memledger.live_bytes() == 256
        assert memledger.peak_bytes() == peak0  # the swap never spiked
        assert memledger.category_of(b) == "param"
        assert memledger.category_of(a) is None

    def test_disabled_register_is_noop_and_hooks_cleared(self):
        memledger.disable()
        assert _hooks.memledger is None
        _x = ht.zeros((128,), dtype=ht.float32)
        assert memledger.live_bytes() == 0
        memledger.enable()
        assert _hooks.memledger is memledger


# ---------------------------------------------------------------------- #
# categories
# ---------------------------------------------------------------------- #
class TestCategories:
    def test_explicit_kwarg_wins(self):
        import jax.numpy as jnp

        a = jnp.ones((8,), jnp.float32)
        memledger.register(a, op="x", site="factory", category="param")
        assert memledger.live_by_category() == {"param": 32}

    def test_scoped_category_override(self):
        with memledger.category("opt-state"):
            _x = ht.zeros((8,), dtype=ht.float32)
        assert memledger.live_by_category() == {"opt-state": 32}

    def test_span_inference_opt_state(self):
        telemetry.enable()
        try:
            with telemetry.span("optim.step"):
                _x = ht.zeros((8,), dtype=ht.float32)
            assert memledger.live_by_category() == {"opt-state": 32}
            (top,) = memledger.top_buffers(1)
            assert top["span"] == "optim.step"
        finally:
            telemetry.disable()

    def test_ckpt_site_is_param(self, tmp_path):
        x = ht.arange(32, dtype=ht.float32, split=0)
        ht.save_array_checkpoint(x, str(tmp_path / "ck"))
        memledger._reset_for_tests()
        back = ht.load_array_checkpoint(str(tmp_path / "ck"))
        np.testing.assert_allclose(back.numpy(), x.numpy())
        cats = memledger.live_by_category()
        assert cats.get("param", 0) >= 128
        tops = memledger.top_buffers(3)
        assert any(b["op"] == "load_array_checkpoint" and b["site"] == "ckpt"
                   for b in tops)

    def test_pytree_checkpoint_leaves_are_params(self, tmp_path):
        import jax.numpy as jnp

        tree = {"w": jnp.ones((4, 4), jnp.float32)}
        ht.core.io.save_checkpoint(tree, str(tmp_path / "t"))
        memledger._reset_for_tests()
        back = ht.core.io.load_checkpoint(tree, str(tmp_path / "t"))
        assert memledger.live_by_category().get("param") == 64
        assert back["w"].shape == (4, 4)

    def test_per_category_peaks_are_independent(self):
        with memledger.category("param"):
            x = ht.zeros((64,), dtype=ht.float32)
        del x
        gc.collect()
        with memledger.category("activation"):
            _y = ht.zeros((8,), dtype=ht.float32)
        assert memledger.peak_by_category()["param"] == 256
        assert memledger.live_by_category() == {"activation": 32}


# ---------------------------------------------------------------------- #
# dispatch tier: threshold coalescing
# ---------------------------------------------------------------------- #
class TestDispatchTier:
    def test_small_outputs_coalesce_not_register(self):
        x = ht.arange(64, dtype=ht.float32, split=0)
        base = memledger.live_bytes()
        _y = x * 2.0  # 256 B << 1 MiB threshold
        assert memledger.live_bytes() == base
        c = memledger.counters()
        assert c["mem.dispatch.small.count"] >= 1
        assert c["mem.dispatch.small.bytes"] >= 256

    def test_threshold_zero_registers_with_public_op_name(self):
        x = ht.arange(64, dtype=ht.float32, split=0)
        prev = memledger.set_dispatch_threshold(0)
        try:
            _y = x * 2.0
        finally:
            memledger.set_dispatch_threshold(prev)
        ops = {b["op"] for b in memledger.top_buffers(5)}
        assert "mul" in ops  # frame peek found the public wrapper, not _binary_op

    def test_big_dispatch_output_registers(self):
        big = ht.ones((1024, 512), dtype=ht.float32, split=0)  # 2 MiB
        out = big + big
        entry = [b for b in memledger.top_buffers(5) if b["op"] == "add"]
        assert entry and entry[0]["nbytes"] == 2 * 1024 * 1024
        assert out.shape == (1024, 512)

    def test_donated_dunder_consumes_left_operand(self):
        assert _hooks.memledger is memledger
        prev = memledger.set_dispatch_threshold(0)
        try:
            z = ht.zeros((64,), dtype=ht.float32, split=0)
            base = memledger.live_bytes()
            z += 1.0  # donating in-place: old buffer consumed, new registered
            gc.collect()
            assert memledger.live_bytes() == base
        finally:
            memledger.set_dispatch_threshold(prev)


# ---------------------------------------------------------------------- #
# resplit reconciliation — the PR 6 contract observed from inside
# ---------------------------------------------------------------------- #
class TestResplitReconciliation:
    def test_copy_resplit_adds_exactly_dst(self):
        x = ht.zeros((8, 8), dtype=ht.float32, split=0)
        base = memledger.live_bytes()
        y = x.resplit(1)
        assert y.split == 1
        assert memledger.live_bytes() - base == _nb(x)

    def test_donated_resplit_is_live_neutral(self):
        x = ht.zeros((8, 8), dtype=ht.float32, split=0)
        base = memledger.live_bytes()
        x.resplit_(1, memory_budget=0)
        gc.collect()
        assert memledger.live_bytes() == base

    def test_resplit_output_inherits_category(self):
        with memledger.category("param"):
            x = ht.zeros((8, 8), dtype=ht.float32, split=0)
        y = x.resplit(1)
        assert memledger.category_of(y._parray) == "param"

    def test_budgeted_resplit_peak_bounded_by_budget_plus_tile(self):
        """The ISSUE 6 transient contract — live-bytes during a budgeted
        resplit never exceeds src + dst + budget + one tile — asserted by
        the ledger's own exact byte math, where the RSS gate can only
        bound it from outside with allocator slack."""
        p = ht.communication.get_comm().size
        shape = (p, 64, p)
        per_slice = p * p * 4
        budget = 2 * per_slice
        src = ht.zeros(shape, dtype=ht.float32, split=0)
        plan = redistribution.plan_resplit(shape, 4, 0, 2, p, budget)
        assert plan.n_tiles > 2, plan
        base = memledger.live_bytes()
        memledger.reset_peak()
        got = src.resplit(2, memory_budget=budget)
        assert got.split == 2
        src_b = dst_b = _nb(src)
        # exact ledger bound: src + dst + budget + one tile, zero slack
        assert memledger.peak_bytes() - (base - src_b) <= (
            src_b + dst_b + budget + plan.max_tile_bytes
        )
        # and the final live set telescopes exactly: src + dst
        assert memledger.live_bytes() - base == dst_b

    def test_budgeted_donated_resplit_telescopes_to_dst_only(self):
        p = ht.communication.get_comm().size
        per_slice = p * p * 4
        src = ht.zeros((p, 16, p), dtype=ht.float32, split=0)
        base = memledger.live_bytes()
        src.resplit_(2, memory_budget=2 * per_slice)
        gc.collect()
        assert memledger.live_bytes() == base  # src consumed, dst same bytes

    def test_tile_entries_are_transient_and_die(self):
        p = ht.communication.get_comm().size
        per_slice = p * p * 4
        src = ht.zeros((p, 16, p), dtype=ht.float32, split=0)
        _got = src.resplit(2, memory_budget=2 * per_slice)
        gc.collect()
        assert memledger.live_by_category().get("transient", 0) == 0


# ---------------------------------------------------------------------- #
# gauges: profiler provider, counter_max mirror, /metrics, heartbeat
# ---------------------------------------------------------------------- #
class TestGauges:
    def test_profiler_provider_and_counter_max_mirror(self):
        _x = ht.zeros((64,), dtype=ht.float32)
        c = profiler.counters()
        assert c["mem.live_bytes"] == 256
        assert c["mem.peak_bytes"] >= 256
        assert c["mem.live_bytes.activation"] == 256

    def test_metrics_endpoint_serves_mem_gauges(self):
        from heat_tpu.utils import monitor

        _x = ht.zeros((64,), dtype=ht.float32)
        text = monitor.metrics_text()
        assert "mem_live_bytes 256" in text
        assert "mem_peak_bytes" in text
        assert "mem_live_bytes_activation 256" in text

    def test_heartbeat_carries_mem_live(self, tmp_path):
        from heat_tpu.utils import health

        _x = ht.zeros((64,), dtype=ht.float32)
        path = str(tmp_path / "rank0.json")
        health.write_heartbeat(path, 1)
        rec = json.loads(open(path).read())
        assert rec["mem_live"] == 256

    def test_monitor_heartbeat_mem_gauge(self, tmp_path):
        from heat_tpu.utils import health, monitor

        _x = ht.zeros((64,), dtype=ht.float32)
        health.write_heartbeat(str(tmp_path / "rank0.json"), 1)
        text = monitor.metrics_text(heartbeat_dir=str(tmp_path))
        assert 'heartbeat_mem_live_bytes{rank="0"} 256' in text

    def test_supervisor_staleness_line_reports_memory(self, tmp_path):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "sup_mem_test",
            os.path.join(REPO, "heat_tpu", "parallel", "supervisor.py"),
        )
        sup_mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = sup_mod
        spec.loader.exec_module(sup_mod)
        hb = tmp_path / "hb"
        hb.mkdir()
        (hb / "rank0.json").write_text(
            json.dumps({"seq": 4, "collective": "resplit", "mem_live": 4096})
        )
        (hb / "rank1.json").write_text(json.dumps({"seq": 6}))
        sup = sup_mod.Supervisor(lambda r, e, p: None, 2,
                                 heartbeat_dir=str(hb))
        msg = sup._semantic_progress(0)
        assert "seq 4 resplit" in msg
        assert "4096 B live" in msg

    def test_snapshot_device_cross_check_optional(self):
        snap = memledger.snapshot()
        assert "live_bytes" in snap and "top_buffers" in snap
        # CPU backend: memory_stats() is None, so the cross-check is absent
        assert "device_bytes_in_use" not in snap or isinstance(
            snap["device_bytes_in_use"], int
        )


# ---------------------------------------------------------------------- #
# OOM path: mem.alloc fault site, ring dump, postmortem verdict
# ---------------------------------------------------------------------- #
class TestOOMPath:
    def test_is_oom_shapes(self):
        assert memledger.is_oom(RuntimeError("RESOURCE_EXHAUSTED: oom"))
        assert memledger.is_oom(
            faults.TransientFault("injected fault at site 'mem.alloc'")
        )
        assert not memledger.is_oom(ValueError("shape mismatch"))

    def test_injected_alloc_failure_dumps_ledger_to_ring(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        try:
            park = ht.zeros((64, 64), dtype=ht.float32, split=0)
            p = ht.communication.get_comm().size
            src = ht.zeros((p, 16, p), dtype=ht.float32, split=0)
            with faults.inject("mem.alloc", fail=1):
                with pytest.raises(faults.TransientFault):
                    src.resplit_(2, memory_budget=2 * p * p * 4)
            assert park.shape == (64, 64)
        finally:
            flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        ooms = [r for r in ring["records"] if r.get("k") == "mem" and r.get("oom")]
        bufs = [r for r in ring["records"] if r.get("k") == "membuf"]
        assert ooms and ooms[0]["where"] == "comm.resplit_tiled"
        assert ooms[0]["req"] > 0
        # the dominant live buffer is the parked factory output, provenance intact
        assert bufs[0]["op"] == "zeros" and bufs[0]["nb"] == 64 * 64 * 4

    def test_monolithic_resplit_alloc_failure_dumps_too(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        try:
            x = ht.zeros((8, 8), dtype=ht.float32, split=0)
            with faults.inject("mem.alloc", fail=1):
                with pytest.raises(faults.TransientFault):
                    x.resplit(1)
        finally:
            flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        ooms = [r for r in ring["records"] if r.get("k") == "mem" and r.get("oom")]
        assert ooms and ooms[0]["where"] == "comm.resplit"

    def test_dispatch_resource_exhausted_dumps(self, tmp_path, monkeypatch):
        flightrec.enable(str(tmp_path), rank=0)
        try:
            x = ht.arange(64, dtype=ht.float32, split=0)
            _ = x * 2.0  # warm the cached program

            class FakeOOM(RuntimeError):
                pass

            def boom(*a, **k):
                raise FakeOOM(
                    "RESOURCE_EXHAUSTED: Out of memory allocating 262144 bytes"
                )

            from heat_tpu.core import _cache

            monkeypatch.setattr(
                _cache, "cached_program",
                lambda comm, key, builder: (boom, (64,), x.dtype, 0),
            )
            with pytest.raises(FakeOOM):
                _ = x * 2.0
        finally:
            flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        ooms = [r for r in ring["records"] if r.get("k") == "mem" and r.get("oom")]
        assert ooms and ooms[0]["where"] == "dispatch.binary"
        assert ooms[0]["err"] == "FakeOOM"

    def test_non_oom_errors_do_not_dump(self, tmp_path):
        # a failure mid-resplit that is NOT allocation-shaped (the
        # comm.collective fault site, message naming a different site)
        # passes through the catch without a ledger dump
        flightrec.enable(str(tmp_path), rank=0)
        try:
            x = ht.zeros((8, 8), dtype=ht.float32, split=0)
            with faults.inject("comm.collective", fail=1, exc=ValueError):
                with pytest.raises(ValueError):
                    x.resplit(1)
        finally:
            flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        ooms = [r for r in ring["records"] if r.get("k") == "mem" and r.get("oom")]
        assert not ooms

    def test_postmortem_oom_verdict_names_rank_req_and_top_buffer(self, tmp_path):
        import importlib.util

        flightrec.enable(str(tmp_path), rank=0)
        try:
            _park = ht.zeros((64, 64), dtype=ht.float32, split=0)
            p = ht.communication.get_comm().size
            src = ht.zeros((p, 16, p), dtype=ht.float32, split=0)
            with faults.inject("mem.alloc", fail=1):
                with pytest.raises(faults.TransientFault):
                    src.resplit_(2, memory_budget=2 * p * p * 4)
        finally:
            flightrec.disable()
        spec = importlib.util.spec_from_file_location(
            "pm_mem_test", os.path.join(REPO, "scripts", "postmortem.py")
        )
        pm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pm)
        verdict = pm.analyze(pm.load_rings(str(tmp_path)))
        assert verdict["verdict"] == "oom"
        assert verdict["oom"]["rank"] == 0
        assert verdict["oom"]["req_bytes"] > 0
        assert verdict["oom"]["top_buffers"][0]["op"] == "zeros"
        line = pm.summary_line(verdict)
        assert "verdict=oom" in line and "rank=0" in line and "req=" in line
        text = pm.render(verdict)
        assert "dominant live buffers" in text

    def test_oom_top_buffers_scoped_to_their_own_dump(self, tmp_path):
        """A ring holding an earlier attestation dump AND an OOM dump must
        report only the OOM dump's rows — no stale duplicates interleaved."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "pm_mem_scope", os.path.join(REPO, "scripts", "postmortem.py")
        )
        pm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pm)
        fr = flightrec.FlightRecorder(str(tmp_path / "flight_rank0.ring"), rank=0)
        # an earlier end-of-step attestation dump (stale rows)
        fr.record("mem", live=100, peak=100)
        fr.record("membuf", i=0, op="stale_buf", nb=100, cat="activation")
        # the OOM dump
        fr.record("mem", oom=1, where="comm.resplit", req=512, live=2048,
                  peak=2048, err="XlaRuntimeError")
        fr.record("membuf", i=0, op="fresh_buf", nb=2048, cat="param")
        fr.close()
        verdict = pm.analyze(pm.load_rings(str(tmp_path)))
        ops = [b.get("op") for b in verdict["oom"]["top_buffers"]]
        assert ops == ["fresh_buf"], ops

    def test_split_none_checkpoint_restore_is_param(self, tmp_path):
        x = ht.arange(32, dtype=ht.float32)  # split=None
        ht.save_array_checkpoint(x, str(tmp_path / "ck"))
        memledger._reset_for_tests()
        back = ht.load_array_checkpoint(str(tmp_path / "ck"))
        np.testing.assert_allclose(back.numpy(), x.numpy())
        assert memledger.live_by_category().get("param", 0) >= 128

    def test_oom_top_buffers_survive_interleaved_watermark_record(self, tmp_path):
        """A concurrent thread's peak-watermark ``mem`` record landing in
        the middle of the dump's unlocked append burst must NOT truncate
        the top-buffers collection (only a later OOM dump, or a restarted
        membuf index, ends it)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "pm_mem_race", os.path.join(REPO, "scripts", "postmortem.py")
        )
        pm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pm)
        fr = flightrec.FlightRecorder(str(tmp_path / "flight_rank0.ring"), rank=0)
        fr.record("mem", oom=1, where="comm.resplit", req=512, live=4096,
                  peak=4096, err="XlaRuntimeError")
        fr.record("membuf", i=0, op="big_buf", nb=4096, cat="param")
        # the racing watermark record (no oom flag) mid-burst
        fr.record("mem", live=5000, peak=5000)
        fr.record("membuf", i=1, op="small_buf", nb=128, cat="activation")
        fr.close()
        verdict = pm.analyze(pm.load_rings(str(tmp_path)))
        ops = [b.get("op") for b in verdict["oom"]["top_buffers"]]
        assert ops == ["big_buf", "small_buf"], ops

    def test_empty_oom_dump_does_not_absorb_later_attestation(self, tmp_path):
        """An OOM while every live buffer sat under the dispatch threshold
        writes zero membuf rows; a LATER dump_to_ring attestation (mem
        record tagged att=1 + its own membuf burst) must not be claimed as
        the failure's dominant buffers."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "pm_mem_empty", os.path.join(REPO, "scripts", "postmortem.py")
        )
        pm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pm)
        fr = flightrec.FlightRecorder(str(tmp_path / "flight_rank0.ring"), rank=0)
        fr.record("mem", oom=1, where="comm.resplit", req=512, live=0,
                  peak=0, err="XlaRuntimeError")  # zero membuf rows follow
        fr.record("mem", att=1, live=4096, peak=4096)  # later attestation
        fr.record("membuf", i=0, op="later_buf", nb=4096, cat="param")
        fr.close()
        verdict = pm.analyze(pm.load_rings(str(tmp_path)))
        assert verdict["verdict"] == "oom"
        assert verdict["oom"]["top_buffers"] == [], verdict["oom"]

    def test_factory_in_comprehension_gets_public_op_name(self):
        outs = ht.meshgrid(ht.arange(4, dtype=ht.float32),
                           ht.arange(3, dtype=ht.float32))
        assert len(outs) == 2
        ops = {b["op"] for b in memledger.top_buffers(10)}
        assert "meshgrid" in ops, ops
        assert not any(o.startswith("<") for o in ops), ops

    def test_alloc_check_request_sizes_dump_fallback(self, tmp_path):
        """A catch site that cannot size the failed request (passes None)
        falls back to the preceding alloc_check's recorded request —
        same-site only, so a stale request from another path never lies."""
        flightrec.enable(str(tmp_path), rank=0)
        try:
            memledger.alloc_check(4096, "somewhere.alloc")
            memledger.dump_oom(where="somewhere.alloc", req_bytes=None,
                               err="XlaRuntimeError")
            memledger.dump_oom(where="elsewhere", req_bytes=None, err="X")
        finally:
            flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        ooms = [r for r in ring["records"] if r.get("k") == "mem" and r.get("oom")]
        assert ooms[0]["req"] == 4096  # same site: sized by alloc_check
        assert ooms[1]["req"] == 0     # different site: no stale fallback

    def test_daso_init_and_resume_register_params_and_opt_state(self, tmp_path):
        """The DASO registrar (HT111's first catch) covers BOTH minting
        paths: init categorizes params + moments, and resume's re-placed
        replacements are re-registered — a resumed job keeps the ZeRO-1
        before-numbers instead of collapsing to ~0."""
        import jax
        import jax.numpy as jnp

        from heat_tpu.optim.dp_optimizer import DASO, DataParallelOptimizer

        if len(jax.devices()) % 2:
            pytest.skip("DASO needs an even device count")
        d = str(tmp_path / "daso")
        model = ht.nn.Sequential(ht.nn.Linear(8, 4))
        loss_fn = lambda pred, y: jnp.mean((pred - y) ** 2)  # noqa: E731
        rng = np.random.default_rng(0)
        xb = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        yb = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
        daso = DASO(DataParallelOptimizer("sgd", lr=0.1), warmup_steps=0,
                    global_skip=1000, checkpoint_every=2, checkpoint_dir=d)
        daso.init(model, key=jax.random.key(0))
        cats = memledger.live_by_category()
        assert cats.get("param", 0) > 0 and cats.get("opt-state", 0) > 0, cats
        for _ in range(2):
            daso.step(loss_fn, xb, yb)

        fresh = DASO(DataParallelOptimizer("sgd", lr=0.1), warmup_steps=0,
                     global_skip=1000, checkpoint_every=2, checkpoint_dir=d)
        fresh.init(model, key=jax.random.key(42))
        memledger._reset_for_tests()
        assert fresh.resume()
        gc.collect()
        cats = memledger.live_by_category()
        assert cats.get("param", 0) > 0, cats
        assert cats.get("opt-state", 0) > 0, cats
        ops = {b["op"] for b in memledger.top_buffers(10)}
        assert "daso.resume" in ops, ops

    def test_oom_outranks_straggler_heuristics(self, tmp_path):
        """An explicit OOM dump is a cause; a short stream is its symptom —
        the verdict must read oom, not straggler."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "pm_mem_test2", os.path.join(REPO, "scripts", "postmortem.py")
        )
        pm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pm)
        fr0 = flightrec.FlightRecorder(str(tmp_path / "flight_rank0.ring"), rank=0)
        fr1 = flightrec.FlightRecorder(str(tmp_path / "flight_rank1.ring"), rank=1)
        for fr in (fr0, fr1):
            fr.record_collective("Allreduce", 100, None)
        fr1.record_collective("Allreduce", 100, None)  # rank 0 falls behind...
        fr0.record("mem", oom=1, where="comm.resplit", req=4096, live=1 << 20,
                   peak=1 << 20, err="XlaRuntimeError")
        fr0.record("membuf", i=0, op="randn", nb=1 << 20, cat="param")
        fr0.close()
        fr1.close()
        verdict = pm.analyze(pm.load_rings(str(tmp_path)))
        assert verdict["verdict"] == "oom"
        assert verdict["oom"]["top_buffers"][0]["op"] == "randn"


# ---------------------------------------------------------------------- #
# report: telemetry_report memory section
# ---------------------------------------------------------------------- #
class TestMemorySection:
    def _report_mod(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "trep_mem_test", os.path.join(REPO, "scripts", "telemetry_report.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_memory_section_renders_watermarks_and_top_buffers(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        try:
            _x = ht.zeros((64, 64), dtype=ht.float32, split=0)
            memledger.dump_to_ring()
        finally:
            flightrec.disable()
        trep = self._report_mod()
        out = trep.memory_section([str(tmp_path)])
        assert "MEM-PEAK rank=0 bytes=" in out
        assert "top live buffers" in out
        assert "zeros" in out

    def test_memory_section_empty_without_mem_records(self, tmp_path):
        trep = self._report_mod()
        assert trep.memory_section([str(tmp_path)]) == ""

    def test_cli_renders_memory_section_for_ring_only_dir(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        try:
            _x = ht.zeros((64, 64), dtype=ht.float32, split=0)
            memledger.dump_to_ring()
        finally:
            flightrec.disable()
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "telemetry_report.py"),
             str(tmp_path)],
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        assert "MEM-PEAK rank=0" in r.stdout


# ---------------------------------------------------------------------- #
# ring watermark hysteresis
# ---------------------------------------------------------------------- #
class TestWatermarks:
    def test_peak_growth_writes_mem_records_with_hysteresis(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        try:
            keep = [ht.zeros((64, 64), dtype=ht.float32) for _ in range(3)]
            assert len(keep) == 3
        finally:
            flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        mems = [r for r in ring["records"] if r.get("k") == "mem"]
        assert mems, "peak growth never reached the ring"
        peaks = [r["peak"] for r in mems]
        assert peaks == sorted(peaks)
        # hysteresis: strictly growing by >5% per record
        for a, b in zip(peaks, peaks[1:]):
            assert b > a * (1 + memledger.WATERMARK_FRACTION)


# ---------------------------------------------------------------------- #
# bucketed hierarchical sync: the transient pipeline observed from inside
# ---------------------------------------------------------------------- #
class TestBucketedSyncTransients:
    """ISSUE 16 reconciliation: the overlapped sync's in-flight bucket
    averages are ledgered transients — peak ≤ budget + one bucket (the
    lookahead-1 bound), dead after consumption, and the staged
    ``comm.allreduce.bytes`` telescopes against the plan's stage factors
    exactly."""

    def _sync(self, budget):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from heat_tpu.core import collectives as coll
        from heat_tpu.core.communication import Communication

        devs = jax.devices()
        if len(devs) != 8:
            pytest.skip("needs the 8-device test mesh")
        mesh = Mesh(np.asarray(devs).reshape(4, 2), ("dcn", "ici"))
        comm = Communication(mesh, "dcn")
        sh = NamedSharding(mesh, P("dcn"))
        params = {
            f"w{j}": jax.device_put(
                jnp.ones((4, 64, 3 + j), jnp.float32), sh
            )
            for j in range(4)
        }
        leaves = jax.tree_util.tree_leaves(params)
        plan = coll.plan_grad_buckets([a.nbytes for a in leaves], budget)
        out = coll.bucketed_param_sync(comm, params, 0.5, plan=plan)
        return plan, out

    def test_transient_peak_bounded_by_budget_plus_one_bucket(self):
        budget = 6144  # bytes: forces one bucket per leaf
        plan, out = self._sync(budget)
        assert plan.n_buckets > 2
        peak = memledger.peak_by_category().get("transient", 0)
        assert peak > 0
        # lookahead-1: at most TWO buckets ever in flight
        assert peak <= budget + plan.max_bucket_bytes
        assert out is not None

    def test_buckets_die_after_consumption(self):
        _, out = self._sync(6144)
        gc.collect()
        live = memledger.live_by_category().get("transient", 0)
        assert live == 0, live
        assert out is not None  # the blended tree survives; transients died

    def test_bytes_telescope_against_plan(self):
        from heat_tpu.core import collectives as coll

        b0 = profiler.counters().get("comm.allreduce.bytes", 0)
        plan, _ = self._sync(6144)
        moved = profiler.counters().get("comm.allreduce.bytes", 0) - b0
        d, i = 4, 2
        want = int(round(
            plan.total_bytes / d * sum(coll._daso_stage_factors(d, i))
        ))
        assert moved == want

    def test_bytes_k_invariant_under_ledger(self):
        deltas = []
        for budget in (None, 6144):
            b0 = profiler.counters().get("comm.allreduce.bytes", 0)
            self._sync(budget)
            deltas.append(profiler.counters().get("comm.allreduce.bytes", 0) - b0)
        assert deltas[0] > 0 and deltas[0] == deltas[1]
