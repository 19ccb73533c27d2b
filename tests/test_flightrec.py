"""Flight recorder + cross-rank post-mortem tests (ISSUE 7 tentpole).

Covers the black box end to end, all on CPU and all fast:

- **ring format**: append/read round-trip, wrap-around keeping the last N,
  oversize-record truncation, torn-slot tolerance, tmp+rename init, the
  ``find_ring_files`` rank ordering — and the durability contract itself:
  a SIGKILL'd subprocess leaves a readable ring behind;
- **seq stamping**: every staged collective gets a monotone sequence
  number + fingerprint at the ``_account_bytes`` choke point; dispatch,
  span, checkpoint and shutdown events ride along; the latest
  ``(seq, op)`` folds into the heartbeat beacon;
- **analyzer** (``scripts/postmortem.py``, loaded standalone): the four
  verdicts (desync / straggler / clean / inconclusive), minority-rank
  naming, straggler lag + wait-histogram evidence, the seq × rank grid,
  the ``POSTMORTEM`` summary line, and the CLI exit codes;
- **wait attribution**: ``guard_blocking`` records observed wait seconds
  into ``<what>.wait`` histograms (with and without an armed deadline,
  including the full-burned-budget observation on a trip), exported
  through the existing flush and parsed back by ``load_wait_hists``;
- **signal flush**: SIGTERM/SIGINT flush the telemetry ring + msync the
  flight recorder, count under ``health.signal_flush``, and chain to the
  previous handler / default disposition;
- **supervisor harvest**: TEARDOWN analyzes + archives the rings and the
  verdict lands in ``SupervisorResult.report()`` — proven against real
  (jax-free) subprocesses.
"""

import json
import os
import signal
import struct
import subprocess
import sys
import time

import pytest

import heat_tpu as ht
from heat_tpu.parallel import supervisor as sup
from heat_tpu.utils import flightrec, health, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PM_PATH = os.path.join(REPO, "scripts", "postmortem.py")


def _load_pm():
    import importlib.util

    spec = importlib.util.spec_from_file_location("pm_under_test", PM_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


pm = _load_pm()


@pytest.fixture(autouse=True)
def _clean_state():
    flightrec.disable()
    telemetry.disable()
    telemetry.reset()
    telemetry._uninstall_signal_flush()
    yield
    flightrec.disable()
    telemetry.disable()
    telemetry.reset()
    telemetry._uninstall_signal_flush()


def _mkring(d, rank, colls, shutdown=False, **rec_kw):
    """A synthetic ring: ``colls`` is a list of (op, wire) or fingerprint
    dicts, stamped with consecutive seq numbers."""
    r = flightrec.FlightRecorder(
        os.path.join(d, f"flight_rank{rank}.ring"), rank=rank, **rec_kw
    )
    seq = 0
    for c in colls:
        seq += 1
        fields = dict(c) if isinstance(c, dict) else {"op": c[0], "wire": c[1]}
        r.record("coll", seq=seq, **fields)
    if shutdown:
        r.record("shutdown")
    r.close()
    return os.path.join(d, f"flight_rank{rank}.ring")


# ---------------------------------------------------------------------- #
# ring format
# ---------------------------------------------------------------------- #
class TestRing:
    def test_roundtrip_fields(self, tmp_path):
        p = str(tmp_path / "flight_rank3.ring")
        r = flightrec.FlightRecorder(p, slots=16, rank=3)
        r.record("coll", seq=1, op="Allreduce", wire=128)
        r.record("d", op="add")
        r.close()
        ring = flightrec.read_ring(p)
        assert ring["rank"] == 3 and ring["ev_count"] == 2
        assert [rec["k"] for rec in ring["records"]] == ["coll", "d"]
        assert ring["records"][0]["op"] == "Allreduce"
        assert ring["records"][0]["e"] == 0 and ring["records"][1]["e"] == 1
        assert all("t" in rec for rec in ring["records"])

    def test_wrap_keeps_last_n(self, tmp_path):
        p = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(p, slots=8, rank=0)
        for i in range(20):
            r.record("coll", seq=i + 1, op="Allreduce", wire=i)
        r.close()
        ring = flightrec.read_ring(p)
        assert ring["ev_count"] == 20
        assert [rec["e"] for rec in ring["records"]] == list(range(12, 20))
        assert [rec["seq"] for rec in ring["records"]] == list(range(13, 21))

    def test_oversize_record_truncated_to_identity(self, tmp_path):
        p = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(p, slots=4, slot_size=96, rank=0)
        r.record("coll", seq=1, op="Allreduce", note="x" * 500)
        r.close()
        (rec,) = flightrec.read_ring(p)["records"]
        assert rec["k"] == "coll" and rec.get("trunc") == 1
        assert "note" not in rec  # bulky attributes dropped...
        # ...but the seq stream survives: the post-mortem must never see a
        # hole where an oversize collective stamp was
        assert rec["seq"] == 1 and rec["op"] == "Allreduce"

    def test_torn_slot_skipped_not_fatal(self, tmp_path):
        p = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(p, slots=8, rank=0)
        for i in range(3):
            r.record("coll", seq=i + 1, op="Allreduce", wire=4)
        r.close()
        # corrupt the middle slot's payload bytes (a torn write)
        with open(p, "r+b") as fh:
            off = flightrec._HEADER_SIZE + 1 * r.slot_size + flightrec._LEN_SIZE
            fh.seek(off)
            fh.write(b"\xff" * 16)
        before = flightrec.slots_skipped_total()
        ring = flightrec.read_ring(p)
        assert [rec["seq"] for rec in ring["records"]] == [1, 3]
        # the hole is COUNTED, not just skipped: per-read in the ring
        # dict, cumulatively in the process counter, and surfaced as a
        # monitor gauge via counters()
        assert ring["slots_skipped"] == 1
        assert flightrec.slots_skipped_total() == before + 1
        assert flightrec.counters()["flightrec.slots.skipped"] >= 1

    def test_partial_ring_unwritten_slots_not_counted(self, tmp_path):
        # a fresh ring with 3 of 8 slots written: the 5 empty slots are
        # unwritten, not torn — they must not inflate the skip counter
        p = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(p, slots=8, rank=0)
        for i in range(3):
            r.record("coll", seq=i + 1, op="Allreduce", wire=4)
        r.close()
        ring = flightrec.read_ring(p)
        assert ring["slots_skipped"] == 0
        assert len(ring["records"]) == 3

    def test_garbage_file_raises(self, tmp_path):
        p = str(tmp_path / "flight_rank0.ring")
        with open(p, "wb") as fh:
            fh.write(b"not a ring file at all" * 10)
        with pytest.raises(ValueError, match="magic"):
            flightrec.read_ring(p)
        with open(str(tmp_path / "short.ring"), "wb") as fh:
            fh.write(b"HT")
        with pytest.raises(ValueError, match="truncated"):
            flightrec.read_ring(str(tmp_path / "short.ring"))

    def test_no_tmp_left_behind(self, tmp_path):
        r = flightrec.FlightRecorder(str(tmp_path / "flight_rank0.ring"), slots=4)
        r.close()
        assert [n for n in os.listdir(tmp_path) if ".tmp." in n] == []

    def test_find_ring_files_rank_order(self, tmp_path):
        for rank in (10, 2, 0):
            _mkring(str(tmp_path), rank, [("Allreduce", 1)], slots=4)
        (tmp_path / "flight_rankX.ring").write_bytes(b"")  # non-numeric last
        (tmp_path / "unrelated.txt").write_text("no")
        paths = flightrec.find_ring_files(str(tmp_path))
        names = [os.path.basename(p) for p in paths]
        assert names == [
            "flight_rank0.ring", "flight_rank2.ring", "flight_rank10.ring",
            "flight_rankX.ring",
        ]
        assert flightrec.find_ring_files(str(tmp_path / "missing")) == []

    def test_append_after_close_drops_not_raises(self, tmp_path):
        # disable() can race an in-flight stamp from the watchdog worker
        # thread: a record landing after close() must be dropped, never
        # raise ValueError('mmap closed') out of collective staging
        path = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(path, rank=0)
        r.record("coll", seq=1, op="Allreduce", wire=100)
        r.close()
        r.record("coll", seq=2, op="Allreduce", wire=100)  # no-op, no raise
        r.record_dispatch("add")
        r.sync()
        ring = flightrec.read_ring(path)
        assert [rec["seq"] for rec in ring["records"] if rec["k"] == "coll"] == [1]

    def test_too_small_ring_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="too small"):
            flightrec.FlightRecorder(str(tmp_path / "r.ring"), slots=0)

    def test_defensive_shape_read(self, tmp_path):
        class Hostile:
            @property
            def shape(self):
                raise RuntimeError("no shape for you")

        p = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(p, slots=4, rank=0)
        seq = r.record_collective("Allreduce", 64, Hostile())
        r.close()
        (rec,) = flightrec.read_ring(p)["records"]
        assert seq == 1 and rec["op"] == "Allreduce" and "gshape" not in rec

    def test_sigkill_leaves_readable_ring(self, tmp_path):
        """The durability contract: mmap'd pages survive SIGKILL with no
        exit handler.  The child loads flightrec STANDALONE (no jax, no
        package import) so this stays a sub-second test."""
        code = f"""
import importlib.util, os, signal
spec = importlib.util.spec_from_file_location(
    "fr", {os.path.join(REPO, 'heat_tpu', 'utils', 'flightrec.py')!r})
fr = importlib.util.module_from_spec(spec); spec.loader.exec_module(fr)
r = fr.FlightRecorder({str(tmp_path / 'flight_rank0.ring')!r}, slots=32, rank=0)
for i in range(5):
    r.record_collective("Allreduce", 100 + i)
print("armed", flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
        )
        assert p.returncode == -signal.SIGKILL and "armed" in p.stdout
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        seqs = [rec["seq"] for rec in ring["records"] if rec["k"] == "coll"]
        assert seqs == [1, 2, 3, 4, 5]


# ---------------------------------------------------------------------- #
# seq stamping at the choke point + event taxonomy
# ---------------------------------------------------------------------- #
class TestStamping:
    def test_collectives_stamped_with_fingerprint(self, tmp_path):
        path = flightrec.enable(str(tmp_path), rank=0)
        a = ht.arange(64, dtype=ht.float32, split=0)
        a.resplit(None)
        flightrec.sync()
        ring = flightrec.read_ring(path)
        colls = [r for r in ring["records"] if r["k"] == "coll"]
        assert len(colls) >= 1
        rec = colls[0]
        assert rec["op"] == "resplit" and rec["seq"] == 1
        assert rec["gshape"] == [64] and rec["dtype"] == "float32"
        assert rec["src"] == 0 and rec["wire"] > 0

    def test_seq_monotone_across_collectives(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        a = ht.reshape(ht.arange(64, dtype=ht.float32, split=0), (8, 8))
        for _ in range(3):
            a = a.resplit(1 - a.split)
        last = flightrec.last_collective()
        assert last is not None and last[0] >= 3
        ring = flightrec.read_ring(flightrec.recorder().path)
        seqs = [r["seq"] for r in ring["records"] if r["k"] == "coll"]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_concurrent_dispatch_and_flush_never_raises(self, tmp_path):
        """The lock-free ``record_dispatch`` races the per-full-append
        ``_flush_dispatch`` by design; the flush must snapshot the pending
        dict so ``json.dumps`` never iterates a dict a preempted dispatch
        thread can still mutate (the review-caught RuntimeError would have
        propagated through ``Communication._account_bytes`` and aborted
        collective staging).  Hammer both sides from threads; any raise
        fails the test, and every flushed count must land in the ring."""
        import threading

        path = flightrec.enable(str(tmp_path), rank=0, slots=4096)
        r = flightrec.recorder()
        errors = []
        stop = threading.Event()

        def dispatcher():
            i = 0
            try:
                while not stop.is_set():
                    r.record_dispatch(f"op{i % 7}")
                    i += 1
            except Exception as e:  # pragma: no cover - the failure mode
                errors.append(e)

        threads = [threading.Thread(target=dispatcher) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            for s in range(200):  # every stamp flushes the pending window
                r.record_collective("Allreduce", 64)
        finally:
            stop.set()
            for t in threads:
                t.join(10)
        assert not errors, errors
        flightrec.sync()
        ring = flightrec.read_ring(path)
        colls = [x for x in ring["records"] if x["k"] == "coll"]
        seqs = [x["seq"] for x in colls]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        d_recs = [x for x in ring["records"] if x["k"] == "d"]
        assert d_recs and all(
            isinstance(x.get("ops"), dict) or x.get("trunc") for x in d_recs
        )

    def test_dispatch_records_ride_along(self, tmp_path):
        path = flightrec.enable(str(tmp_path), rank=0)
        a = ht.arange(16, dtype=ht.float32, split=0)
        (a + a).sum()
        flightrec.sync()
        kinds = {r["k"] for r in flightrec.read_ring(path)["records"]}
        assert "d" in kinds

    def test_spans_mirrored_when_both_armed(self, tmp_path):
        path = flightrec.enable(str(tmp_path), rank=0)
        telemetry.enable()
        with telemetry.span("train.step"):
            pass
        recs = flightrec.read_ring(path)["records"]
        names = [(r["k"], r.get("name")) for r in recs if r["k"].startswith("span")]
        assert ("span", "train.step") in names
        assert ("span_end", "train.step") in names
        end = next(r for r in recs if r["k"] == "span_end")
        assert "dur" in end and "error" not in end

    def test_span_error_tagged(self, tmp_path):
        path = flightrec.enable(str(tmp_path), rank=0)
        telemetry.enable()
        with pytest.raises(RuntimeError):
            with telemetry.span("bad.step"):
                raise RuntimeError("boom")
        end = next(
            r for r in flightrec.read_ring(path)["records"] if r["k"] == "span_end"
        )
        assert end["error"] == "RuntimeError"

    def test_checkpoint_events(self, tmp_path):
        path = flightrec.enable(str(tmp_path / "fr"), rank=0)
        tree = {"w": ht.arange(8, dtype=ht.float32).larray}
        ht.save_checkpoint(tree, str(tmp_path / "ckpt"))
        ht.load_checkpoint(tree, str(tmp_path / "ckpt"))
        ops = [
            r.get("op")
            for r in flightrec.read_ring(path)["records"]
            if r["k"] == "ckpt"
        ]
        assert "save_tree" in ops and "load_tree" in ops

    def test_heartbeat_carries_seq(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        ht.arange(16, dtype=ht.float32, split=0).resplit(None)
        hb = str(tmp_path / "rank0.json")
        health.write_heartbeat(hb, step=7)
        rec = json.load(open(hb))
        assert rec["step"] == 7
        assert rec["seq"] == flightrec.last_collective()[0]
        assert rec["collective"] == "resplit"

    def test_heartbeat_without_recorder_has_no_seq(self, tmp_path):
        hb = str(tmp_path / "rank0.json")
        health.write_heartbeat(hb, step=1)
        rec = json.load(open(hb))
        assert "seq" not in rec and "collective" not in rec

    def test_disabled_is_noop_and_unhooked(self, tmp_path):
        from heat_tpu.core import _hooks

        assert _hooks.flightrec is None
        flightrec.record_event("coll", seq=1)  # must not raise
        flightrec.record_dispatch("add")
        flightrec.record_collective("Allreduce", 1)
        assert flightrec.last_collective() is None
        assert not flightrec.enabled() and flightrec.recorder() is None
        flightrec.enable(str(tmp_path), rank=0)
        assert _hooks.flightrec is flightrec
        assert telemetry._flightrec() is flightrec
        flightrec.disable()
        assert _hooks.flightrec is None
        assert telemetry._flightrec() is None

    def test_env_arm_failure_warns_not_silent(self, tmp_path, monkeypatch):
        # a silently-disarmed black box is the exact failure this module
        # exists to prevent: an unwritable dir must say so (and still not
        # kill the import path that calls this)
        blocker = tmp_path / "blocker"
        blocker.write_text("")
        monkeypatch.setenv("HEAT_TPU_FLIGHTREC_DIR", str(blocker / "sub"))
        with pytest.warns(RuntimeWarning, match="could not arm"):
            flightrec._env_arm()
        assert not flightrec.enabled()

    def test_env_arm_absent_env_is_noop(self, monkeypatch):
        monkeypatch.delenv("HEAT_TPU_FLIGHTREC_DIR", raising=False)
        flightrec._env_arm()
        assert not flightrec.enabled()

    def test_reenable_starts_fresh_ring(self, tmp_path):
        path = flightrec.enable(str(tmp_path), rank=0)
        flightrec.record_collective("Allreduce", 1)
        path2 = flightrec.enable(str(tmp_path), rank=0)
        assert path2 == path
        ring = flightrec.read_ring(path2)
        assert ring["ev_count"] == 0 and ring["records"] == []
        assert flightrec.last_collective() is None

    def test_shutdown_marker_on_finalize(self, tmp_path, monkeypatch):
        path = flightrec.enable(str(tmp_path), rank=0)
        ht.arange(8, dtype=ht.float32, split=0).resplit(None)
        # single-process jax.distributed isn't initialized; finalize must
        # still stamp the marker before its (tolerated) shutdown attempt
        ht.core.bootstrap.finalize_distributed()
        kinds = [r["k"] for r in flightrec.read_ring(path)["records"]]
        assert kinds[-1] == "shutdown"


# ---------------------------------------------------------------------- #
# analyzer verdicts
# ---------------------------------------------------------------------- #
class TestAnalyzer:
    def test_desync_names_minority(self, tmp_path):
        d = str(tmp_path)
        base = [("Allreduce", 100), ("Alltoall", 200), ("Allreduce", 100)]
        _mkring(d, 0, base)
        _mkring(d, 1, base[:2] + [("Bcast", 50)] + base[2:])
        _mkring(d, 2, base)
        v = pm.analyze_dir(d)
        assert v["verdict"] == "desync"
        assert v["first_divergent_seq"] == 3
        assert v["deviating_ranks"] == [1]
        assert v["divergence"]["1"]["op"] == "Bcast"
        assert "rank 1: Bcast" in v["detail"]
        line = pm.summary_line(v)
        assert "verdict=desync" in line and "seq=3" in line and "ranks=1" in line

    def test_desync_two_way_split_names_all(self, tmp_path):
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)])
        _mkring(d, 1, [("Bcast", 100)])
        v = pm.analyze_dir(d)
        assert v["verdict"] == "desync" and v["first_divergent_seq"] == 1
        assert v["deviating_ranks"] == [0, 1]
        assert "cannot vote" in v["detail"]

    def test_wire_bytes_difference_is_divergence(self, tmp_path):
        # same op, different payload: still a desync (the EQuARX-style
        # quantization mismatch class)
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100), ("Allreduce", 100), ("Allreduce", 100)])
        _mkring(d, 1, [("Allreduce", 100), ("Allreduce", 999), ("Allreduce", 100)])
        _mkring(d, 2, [("Allreduce", 100), ("Allreduce", 100), ("Allreduce", 100)])
        v = pm.analyze_dir(d)
        assert v["verdict"] == "desync" and v["first_divergent_seq"] == 2
        assert v["deviating_ranks"] == [1]

    def test_straggler_named_with_lag(self, tmp_path):
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)] * 6)
        _mkring(d, 1, [("Allreduce", 100)] * 2)
        _mkring(d, 2, [("Allreduce", 100)] * 6)
        v = pm.analyze_dir(d)
        assert v["verdict"] == "straggler"
        s = v["straggler"]
        assert s["rank"] == 1 and s["seq"] == 2 and s["lag"] == 4
        assert s["op"] == "Allreduce" and s["peers_at"] == 6
        assert "rank 1 stuck at seq 2" in v["detail"]
        line = pm.summary_line(v, epoch=3)
        assert "epoch=3" in line and "rank=1" in line and "lag=4" in line

    def test_collective_less_ring_is_straggler_at_seq0(self, tmp_path):
        # rank 1 armed its ring, then died/wedged before staging a single
        # collective: silently dropping it would let a clean verdict lie
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)] * 4, shutdown=True)
        _mkring(d, 1, [])
        v = pm.analyze_dir(d)
        assert v["verdict"] == "straggler"
        s = v["straggler"]
        assert s["rank"] == 1 and s["seq"] == 0 and s["op"] is None
        assert s["lag"] == 4 and s["peers_at"] == 4
        assert "staged no collectives" in v["detail"]
        text = pm.render(v, pm.load_rings(d))  # renders without a fingerprint
        assert "rank=1 seq=0" in text

    def test_missing_rank_blocks_clean(self, tmp_path):
        d = str(tmp_path)
        for k in range(2):
            _mkring(d, k, [("Allreduce", 100)] * 3, shutdown=True)
        # without world knowledge the surviving streams read clean...
        assert pm.analyze_dir(d)["verdict"] == "clean"
        # ...but the caller launched 3 ranks: rank 2's lost black box is
        # itself the finding, never hidden inside `clean`
        v = pm.analyze_dir(d, expected_ranks=[0, 1, 2])
        assert v["verdict"] == "inconclusive"
        assert v["missing_ranks"] == [2]
        assert "cannot attest clean" in v["detail"]
        assert "NO ring file: 2" in pm.render(v)

    def test_truncated_record_not_false_desync(self, tmp_path):
        # slot truncation is per-rank (payload byte lengths differ by
        # rank): a record that shed its gshape on ONE rank must not read
        # as a divergence against peers that kept theirs
        d = str(tmp_path)
        full = {"op": "Allreduce", "wire": 100, "gshape": [64, 64], "dtype": "float32"}
        shed = {"op": "Allreduce", "wire": 100, "dtype": "float32", "trunc": 1}
        _mkring(d, 0, [full, full], shutdown=True)
        _mkring(d, 1, [full, shed], shutdown=True)
        v = pm.analyze_dir(d)
        assert v["verdict"] == "clean", v

    def test_truncated_record_still_catches_real_desync(self, tmp_path):
        # tolerance is per-field, not per-record: a truncated record whose
        # SURVIVING fields differ is still a desync
        d = str(tmp_path)
        full = {"op": "Allreduce", "wire": 100, "gshape": [64, 64]}
        bad = {"op": "Bcast", "wire": 100, "trunc": 1}
        _mkring(d, 0, [full, full])
        _mkring(d, 1, [full, bad])
        v = pm.analyze_dir(d)
        assert v["verdict"] == "desync" and v["first_divergent_seq"] == 2

    def test_torn_slots_surface_in_verdict_and_render(self, tmp_path):
        # a lossy ring must never pass for a complete stream: the skip
        # count rides every verdict (and therefore the --json output)
        d = str(tmp_path)
        p0 = _mkring(d, 0, [("Allreduce", 100)] * 3, shutdown=True)
        _mkring(d, 1, [("Allreduce", 100)] * 3, shutdown=True)
        with open(p0, "r+b") as fh:
            off = (flightrec._HEADER_SIZE + 1 * flightrec.DEFAULT_SLOT_SIZE
                   + flightrec._LEN_SIZE)
            fh.seek(off)
            fh.write(b"\xff" * 16)
        v = pm.analyze_dir(d)
        assert v["slots_skipped"] == {"0": 1}
        assert "torn/unparseable" in pm.render(v)
        clean = pm.analyze(
            {1: {"rank": 1, "records": [], "slots_skipped": 0}}
        )
        assert "slots_skipped" not in clean  # intact rings stay silent

    def test_render_orders_ranks_numerically(self, tmp_path):
        # last_seq/heartbeats are str-keyed (JSON round-trip): the report
        # must still read rank 2 before rank 10 at pod scale
        d = str(tmp_path)
        for k in range(12):
            _mkring(d, k, [("Allreduce", 100)] * (1 if k == 11 else 3))
        v = pm.analyze_dir(d)
        text = pm.render(v)
        line = next(s for s in text.splitlines() if s.startswith("last staged"))
        assert line.index("rank 2:") < line.index("rank 10:")

    def test_missing_ranks_named_on_empty_dir(self, tmp_path):
        v = pm.analyze(pm.load_rings(str(tmp_path)), expected_ranks=[0, 1])
        assert v["verdict"] == "inconclusive"
        assert v["missing_ranks"] == [0, 1]
        assert "rank(s) [0, 1]" in v["detail"]

    def test_clean_requires_shutdown_markers(self, tmp_path):
        d = str(tmp_path)
        for k in range(2):
            _mkring(d, k, [("Allreduce", 100)] * 3, shutdown=True)
        v = pm.analyze_dir(d)
        assert v["verdict"] == "clean"
        assert v["last_seq"] == {"0": 3, "1": 3}

    def test_identical_without_shutdown_inconclusive(self, tmp_path):
        d = str(tmp_path)
        for k in range(2):
            _mkring(d, k, [("Allreduce", 100)] * 3)
        v = pm.analyze_dir(d)
        assert v["verdict"] == "inconclusive"
        assert "global stall" in v["detail"]

    def test_empty_and_recordless_inconclusive(self, tmp_path):
        v = pm.analyze_dir(str(tmp_path))
        assert v["verdict"] == "inconclusive" and "no flight-recorder" in v["detail"]
        _mkring(str(tmp_path), 0, [])
        v = pm.analyze_dir(str(tmp_path))
        assert v["verdict"] == "inconclusive"
        assert "no collective records" in v["detail"]

    def test_wrapped_ring_window_still_diagnoses(self, tmp_path):
        # rank 0's ring wrapped (slots=8, 20 colls): the common window is
        # the intersection, and the straggler at seq 5 is still named
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)] * 20, slots=8)
        _mkring(d, 1, [("Allreduce", 100)] * 5)
        v = pm.analyze_dir(d)
        assert v["verdict"] == "straggler" and v["straggler"]["rank"] == 1

    def test_heartbeats_joined(self, tmp_path):
        d = str(tmp_path / "fr")
        os.makedirs(d)
        _mkring(d, 0, [("Allreduce", 100)])
        hb_dir = str(tmp_path / "hb")
        os.makedirs(hb_dir)
        json.dump(
            {"step": 4, "seq": 17, "collective": "Alltoall", "status": "ok"},
            open(os.path.join(hb_dir, "rank0.json"), "w"),
        )
        v = pm.analyze_dir(d, heartbeat_dir=hb_dir)
        hb = v["heartbeats"]["0"]
        assert hb["seq"] == 17 and hb["collective"] == "Alltoall"
        assert "age_s" in hb

    def test_grid_marks_divergence(self, tmp_path):
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100), ("Alltoall", 200)])
        _mkring(d, 1, [("Allreduce", 100), ("Bcast", 50)])
        grid = pm.render_grid(pm.load_rings(d))
        lines = grid.splitlines()
        assert "rank0" in lines[0] and "rank1" in lines[0]
        row2 = next(ln for ln in lines if ln.startswith("2"))
        assert row2.rstrip().endswith("*")
        row1 = next(ln for ln in lines if ln.startswith("1"))
        assert not row1.rstrip().endswith("*")

    def test_render_full_report(self, tmp_path):
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)] * 4)
        _mkring(d, 1, [("Allreduce", 100)] * 2)
        rings = pm.load_rings(d)
        v = pm.analyze(rings)
        text = pm.render(v, rings)
        assert "POSTMORTEM verdict=straggler" in text
        assert "collective timeline" in text
        assert "last staged seq per rank" in text

    def test_cli_exit_codes_and_json(self, tmp_path, capsys):
        d = str(tmp_path / "run")
        os.makedirs(d)
        _mkring(d, 0, [("Allreduce", 100)] * 3, shutdown=True)
        out_json = str(tmp_path / "verdict.json")
        rc = pm.main([d, "--json", out_json])
        assert rc == 0
        assert "verdict=clean" in capsys.readouterr().out
        assert json.load(open(out_json))["verdict"] == "clean"
        empty = str(tmp_path / "empty")
        os.makedirs(empty)
        assert pm.main([empty]) == 1

    def test_cli_expected_ranks_flag(self, tmp_path, capsys):
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)], shutdown=True)
        assert pm.main([d]) == 0
        assert "verdict=clean" in capsys.readouterr().out
        assert pm.main([d, "--expected-ranks", "2"]) == 0
        out = capsys.readouterr().out
        assert "verdict=inconclusive" in out
        assert "NO ring file: 1" in out

    def test_unreadable_ring_skipped(self, tmp_path):
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)] * 2)
        with open(os.path.join(d, "flight_rank1.ring"), "wb") as fh:
            fh.write(b"garbage")
        rings = pm.load_rings(d)
        assert sorted(rings) == [0]


# ---------------------------------------------------------------------- #
# wait-time attribution (the straggler evidence)
# ---------------------------------------------------------------------- #
class TestWaitAttribution:
    def test_guard_blocking_records_wait_no_deadline(self):
        telemetry.enable()
        health.guard_blocking(lambda: time.sleep(0.02), "comm.Wait")
        h = telemetry.report()["histograms"]["comm.Wait.wait"]
        assert h["count"] == 1 and h["max_s"] >= 0.02

    def test_guard_blocking_records_wait_under_deadline(self):
        telemetry.enable()
        with health.deadline(5.0):
            health.guard_blocking(lambda: time.sleep(0.02), "comm.Barrier")
        h = telemetry.report()["histograms"]["comm.Barrier.wait"]
        assert h["count"] == 1 and h["max_s"] >= 0.02

    def test_trip_records_full_burned_budget(self):
        telemetry.enable()
        with health.deadline(0.15):
            with pytest.raises(health.CollectiveTimeoutError):
                health.guard_blocking(lambda: time.sleep(30), "comm.Alltoall")
        h = telemetry.report()["histograms"]["comm.Alltoall.wait"]
        assert h["count"] == 1 and h["max_s"] >= 0.14

    def test_disarmed_telemetry_records_nothing(self):
        # telemetry OFF (autouse fixture): the no-deadline guard is a BARE
        # call — no clocks, no histogram entry.  Per-call observation
        # between back-to-back collectives is hot-path cost the off
        # contract forbids (and it measurably perturbs rapid small-
        # collective streams on slow hosts).
        health.guard_blocking(lambda: time.sleep(0.01), "comm.Wait")
        assert "comm.Wait.wait" not in telemetry.report()["histograms"]

    def test_no_telemetry_module_is_silent(self, monkeypatch):
        # a bare supervisor process never imports telemetry: the
        # observation is dropped, not an ImportError
        monkeypatch.setitem(sys.modules, "heat_tpu.utils.telemetry", None)
        health.guard_blocking(lambda: None, "comm.Wait")

    def test_wait_hists_flow_to_analyzer(self, tmp_path):
        tdir = str(tmp_path / "tel")
        telemetry.enable(tdir)
        with health.deadline(5.0):
            health.guard_blocking(lambda: time.sleep(0.02), "comm.Alltoall")
        telemetry.flush()
        waits = pm.load_wait_hists(tdir)
        rank = next(iter(waits))
        assert "comm.Alltoall.wait" in waits[rank]
        w = waits[rank]["comm.Alltoall.wait"]
        assert w["count"] == 1 and w["total_s"] > 0
        # and the straggler verdict attaches it as evidence
        d = str(tmp_path / "fr")
        os.makedirs(d)
        _mkring(d, rank, [("Allreduce", 100)] * 2)
        _mkring(d, rank + 1, [("Allreduce", 100)] * 5)
        v = pm.analyze_dir(d, telemetry_dir=tdir)
        assert v["verdict"] == "straggler" and v["straggler"]["rank"] == rank
        assert "comm.Alltoall.wait" in v["straggler"]["wait"]


# ---------------------------------------------------------------------- #
# signal flush (SIGTERM/SIGINT graceful-kill export)
# ---------------------------------------------------------------------- #
class TestSignalFlush:
    def test_install_idempotent_and_uninstall(self):
        assert telemetry.install_signal_flush()
        assert telemetry.install_signal_flush()  # second call: still True
        assert signal.getsignal(signal.SIGTERM) is telemetry._signal_flush_handler
        telemetry._uninstall_signal_flush()
        assert signal.getsignal(signal.SIGTERM) is not telemetry._signal_flush_handler

    def test_install_refused_off_main_thread(self):
        import threading

        out = {}

        def run():
            out["ok"] = telemetry.install_signal_flush()

        t = threading.Thread(target=run)
        t.start()
        t.join()
        assert out["ok"] is False

    @pytest.mark.parametrize("sig", ["SIGTERM", "SIGINT"])
    def test_sigterm_flushes_counts_and_dies_of_signal(self, tmp_path, sig):
        td = str(tmp_path)
        code = f"""
import os, time, signal
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from heat_tpu.utils import telemetry, flightrec, health
import heat_tpu.utils.profiler
telemetry.enable({td!r})
flightrec.enable({td!r}, rank=0)
with health.deadline(5.0):
    health.guard_blocking(lambda: time.sleep(0.01), "comm.Wait")
flightrec.record_collective("Allreduce", 123)
os.kill(os.getpid(), signal.{sig})
time.sleep(30)
"""
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=180, cwd=REPO,
        )
        signum = getattr(signal, sig)
        # SIGINT lands as KeyboardInterrupt via the chained default handler
        assert p.returncode != 0 and p.returncode != -signal.SIGKILL
        if sig == "SIGTERM":
            assert p.returncode == -signum
        rank_file = os.path.join(td, "rank0.jsonl")
        assert os.path.exists(rank_file), p.stderr
        counters = {}
        for line in open(rank_file):
            rec = json.loads(line)
            if rec.get("type") == "counters":
                counters = rec["values"]
            if rec.get("type") == "hist" and rec["name"] == "comm.Wait.wait":
                assert rec["count"] == 1
        assert counters.get("health.signal_flush") == 1
        ring = flightrec.read_ring(os.path.join(td, "flight_rank0.ring"))
        assert any(r["k"] == "coll" for r in ring["records"])

    def test_chains_previous_python_handler(self, tmp_path):
        marker = str(tmp_path / "prev_ran")
        code = f"""
import os, signal, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
def prev(signum, frame):
    open({marker!r}, "w").write("yes")
    sys.exit(0)
signal.signal(signal.SIGTERM, prev)
from heat_tpu.utils import telemetry
telemetry.enable()
assert telemetry.install_signal_flush()
os.kill(os.getpid(), signal.SIGTERM)
import time; time.sleep(30)
"""
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=180, cwd=REPO,
        )
        assert p.returncode == 0, p.stderr
        assert os.path.exists(marker)


# ---------------------------------------------------------------------- #
# supervisor harvest + report embedding
# ---------------------------------------------------------------------- #
class TestSupervisorPostmortem:
    def test_run_postmortem_harvests_rings(self, tmp_path):
        fr_dir = str(tmp_path / "fr")
        os.makedirs(fr_dir)
        _mkring(fr_dir, 0, [("Allreduce", 100)] * 5)
        _mkring(fr_dir, 1, [("Allreduce", 100)] * 2)
        s = sup.Supervisor(
            lambda rank, epoch, port: None, 2,
            flightrec_dir=fr_dir, poll_interval=0.05,
        )
        v = s._run_postmortem(0, "rank 1 heartbeat stale")
        assert v["verdict"] == "straggler" and v["straggler"]["rank"] == 1
        assert v["epoch"] == 0 and v["failure"] == "rank 1 heartbeat stale"
        # rings archived under epoch0/: the relaunch starts a clean box
        assert flightrec.find_ring_files(fr_dir) == []
        assert len(flightrec.find_ring_files(os.path.join(fr_dir, "epoch0"))) == 2

    def test_no_flightrec_dir_is_none(self):
        s = sup.Supervisor(lambda rank, epoch, port: None, 1)
        assert s._run_postmortem(0, "x") is None

    def test_semantic_progress_in_stall_message(self, tmp_path):
        hb_dir = str(tmp_path / "hb")
        os.makedirs(hb_dir)
        json.dump(
            {"step": 2, "seq": 417, "collective": "Alltoall"},
            open(os.path.join(hb_dir, "rank0.json"), "w"),
        )
        json.dump(
            {"step": 2, "seq": 423, "collective": "Allreduce"},
            open(os.path.join(hb_dir, "rank1.json"), "w"),
        )
        s = sup.Supervisor(
            lambda rank, epoch, port: None, 2, heartbeat_dir=hb_dir
        )
        msg = s._semantic_progress(0)
        assert "stuck at seq 417 Alltoall" in msg and "peers at seq 423" in msg
        # no seq in the beacon: the suffix degrades to nothing
        json.dump({"step": 2}, open(os.path.join(hb_dir, "rank0.json"), "w"))
        assert s._semantic_progress(0) == ""

    def test_supervisor_embeds_verdict_end_to_end(self, tmp_path):
        """Real (jax-free) subprocesses: both ranks write rings standalone,
        rank 1 stops early and stalls → heartbeat staleness → TEARDOWN
        runs the analyzer → the straggler verdict lands in
        ``SupervisorResult.report()``."""
        fr_dir = str(tmp_path / "fr")
        hb_dir = str(tmp_path / "hb")
        os.makedirs(fr_dir)
        os.makedirs(hb_dir)
        frpath = os.path.join(REPO, "heat_tpu", "utils", "flightrec.py")
        code = f"""
import importlib.util, json, os, time
spec = importlib.util.spec_from_file_location("fr", {frpath!r})
fr = importlib.util.module_from_spec(spec); spec.loader.exec_module(fr)
rank = int(os.environ["RANK"])
r = fr.FlightRecorder(
    os.path.join({fr_dir!r}, "flight_rank%d.ring" % rank), slots=64, rank=rank)
n = 2 if rank == 1 else 6
for i in range(n):
    r.record_collective("Allreduce", 100)
json.dump({{"step": n, "seq": n, "collective": "Allreduce"}},
          open(os.path.join({hb_dir!r}, "rank%d.json" % rank), "w"))
time.sleep(120)
"""

        def spawn(rank, epoch, port):
            env = dict(os.environ)
            env["RANK"] = str(rank)
            return subprocess.Popen(
                [sys.executable, "-c", code], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )

        s = sup.Supervisor(
            spawn, 2, heartbeat_dir=hb_dir, heartbeat_timeout=1.5,
            restart_budget=0, poll_interval=0.1, grace=1.0,
            flightrec_dir=fr_dir,
        )
        res = s.run()
        assert not res.ok and len(res.postmortems) == 1
        v = res.postmortems[0]
        assert v["verdict"] == "straggler"
        assert v["straggler"]["rank"] == 1 and v["straggler"]["lag"] == 4
        assert "heartbeat stale" in v["failure"]
        rep = res.report()
        assert rep["postmortems"] == res.postmortems
        assert json.loads(json.dumps(rep)) == rep


# ---------------------------------------------------------------------- #
# scripts/telemetry_report.py: flight-recorder timeline + CLI edge cases
# ---------------------------------------------------------------------- #
TREP_PATH = os.path.join(REPO, "scripts", "telemetry_report.py")


def _load_trep():
    import importlib.util

    spec = importlib.util.spec_from_file_location("trep_under_test", TREP_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_rank_jsonl(d, rank, with_meta=True):
    lines = []
    if with_meta:
        lines.append({"type": "meta", "rank": rank, "pid": 1234, "t0": 1.0})
    lines.append({"type": "span", "rank": rank, "name": "dispatch.local",
                  "ts": 10.0 + rank, "dur_s": 0.002, "self_s": 0.002, "depth": 0})
    lines.append({"type": "counters", "rank": rank,
                  "values": {"comm.resplit.calls": 3 + rank}})
    lines.append({"type": "hist", "rank": rank, "name": "comm.Wait.wait",
                  "bins": {"1": 2}, "count": 2, "total_s": 0.5, "max_s": 0.3,
                  "min_s": 0.2, "lo": 1e-6, "per_decade": 5})
    path = os.path.join(d, f"rank{rank}.jsonl")
    with open(path, "w") as fh:
        for rec in lines:
            fh.write(json.dumps(rec) + "\n")
    return path


class TestTelemetryReportCLI:
    def test_empty_dir_exits_1(self, tmp_path, capsys):
        trep = _load_trep()
        rc = trep.main([str(tmp_path)])
        cap = capsys.readouterr()
        assert rc == 1
        assert "no rank*.jsonl files" in cap.err

    def test_single_rank_report(self, tmp_path, capsys):
        trep = _load_trep()
        _write_rank_jsonl(str(tmp_path), 0)
        rc = trep.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ranks=[0]" in out
        assert "dispatch.local" in out
        assert "comm.resplit.calls" in out
        # no rings in the dir: no collective-timeline section
        assert "collective timeline" not in out

    def test_missing_meta_line_still_merges(self, tmp_path, capsys):
        """A rank file whose meta line is gone (torn flush head, manual
        concat) must still contribute its spans/counters/hists."""
        trep = _load_trep()
        _write_rank_jsonl(str(tmp_path), 0, with_meta=True)
        _write_rank_jsonl(str(tmp_path), 1, with_meta=False)
        rc = trep.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ranks=[0, 1]" in out
        merged = trep.merge_files(trep.find_rank_files(str(tmp_path)))
        assert merged["counters"]["comm.resplit.calls"] == 3 + 4

    def test_flightrec_timeline_section_rendered(self, tmp_path, capsys):
        """Ring files next to the rank jsonls fold the seq × rank grid and
        the one-line verdict into the SAME report (the ISSUE 7 satellite:
        one command reads a whole run's artifacts)."""
        trep = _load_trep()
        d = str(tmp_path)
        _write_rank_jsonl(d, 0)
        _write_rank_jsonl(d, 1)
        common = [("Allreduce", 100), ("Alltoall", 200)]
        _mkring(d, 0, common + [("Bcast", 50)])
        _mkring(d, 1, common + [("Allgather", 999)])
        rc = trep.main([d, "--context", "2"])
        out = capsys.readouterr().out
        assert rc == 0
        assert f"collective timeline (seq × rank) from {d}" in out
        assert "POSTMORTEM verdict=desync seq=3" in out
        # the grid marks the divergent row and shows both fingerprints
        assert "Bcast" in out and "Allgather" in out

    def test_section_names_rank_with_telemetry_but_no_ring(self, tmp_path, capsys):
        """The jsonl rank set doubles as the analyzer's expected ranks: a
        rank that exported telemetry but lost its black box must not hide
        inside a clean verdict in the report's timeline section."""
        trep = _load_trep()
        d = str(tmp_path)
        _write_rank_jsonl(d, 0)
        _write_rank_jsonl(d, 1)
        _mkring(d, 0, [("Allreduce", 100)] * 2, shutdown=True)  # rank 1: no ring
        rc = trep.main([d])
        out = capsys.readouterr().out
        assert rc == 0
        assert "verdict=inconclusive" in out
        assert "telemetry but NO ring file: 1" in out
        assert "verdict=clean" not in out

    def test_ring_only_dir_renders_timeline(self, tmp_path, capsys):
        """A harvested epoch dir (the supervisor moves ONLY the rings into
        ``{dir}/epoch{k}/``) must render the timeline, not exit 1."""
        trep = _load_trep()
        d = str(tmp_path)
        _mkring(d, 0, [("Allreduce", 100)] * 3)
        _mkring(d, 1, [("Allreduce", 100)])
        rc = trep.main([d])
        cap = capsys.readouterr()
        assert rc == 0
        assert "collective timeline" in cap.out
        assert "verdict=straggler" in cap.out
        # the no-telemetry banner (now shared with scheduler-journal-only
        # dirs — ISSUE 10 widened this path to serving artifacts)
        assert "rendering the journal/ring artifacts only" in cap.out

    def test_flightrec_section_empty_without_rings(self, tmp_path):
        trep = _load_trep()
        assert trep.flightrec_section([str(tmp_path)]) == ""

    def test_file_targets_skip_ring_scan(self, tmp_path, capsys):
        """Explicit FILE targets (not dirs) never grow a timeline section —
        the ring scan is directory-scoped on purpose."""
        trep = _load_trep()
        d = str(tmp_path)
        path = _write_rank_jsonl(d, 0)
        _mkring(d, 0, [("Allreduce", 100)])
        rc = trep.main([path])
        out = capsys.readouterr().out
        assert rc == 0
        assert "collective timeline" not in out


# ---------------------------------------------------------------------- #
# trace propagation (ISSUE 11): collective stamps carry the ambient tid
# ---------------------------------------------------------------------- #
class TestTraceStamping:
    def test_collective_stamp_carries_ambient_trace_id(self, tmp_path):
        flightrec.enable(str(tmp_path), rank=0)
        with telemetry.tracing(trace_id="feedface00000001"):
            flightrec.record_collective("resplit", 4096)
        flightrec.record_collective("resplit", 4096)  # untraced
        flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        colls = [r for r in ring["records"] if r["k"] == "coll"]
        assert colls[0]["tid"] == "feedface00000001"
        assert "tid" not in colls[1]

    def test_staged_collective_through_account_bytes_carries_tid(self, tmp_path):
        """The real choke point: a resplit staged inside telemetry.tracing
        lands in the ring with the trace id — no telemetry arming needed
        (trace identity is a contextvar, not span-ring state)."""
        flightrec.enable(str(tmp_path), rank=0)
        comm = ht.communication.get_comm()
        x = ht.reshape(ht.arange(comm.size * comm.size, dtype=ht.float32,
                                 split=0), (comm.size, comm.size))
        with telemetry.tracing(name="resplit-test") as tid:
            x = x.resplit(1)
        flightrec.disable()
        ring = flightrec.read_ring(str(tmp_path / "flight_rank0.ring"))
        stamped = [r for r in ring["records"]
                   if r["k"] == "coll" and r.get("tid") == tid]
        assert stamped and stamped[-1]["op"] == "resplit"

    def test_tid_not_part_of_the_desync_fingerprint(self, tmp_path):
        """Two ranks staging the identical stream, only one under a trace:
        the analyzer must NOT read the tid difference as a desync — trace
        identity is attribution, never evidence of divergence."""
        d = str(tmp_path)
        _mkring(d, 0, [{"op": "resplit", "wire": 64, "tid": "aaaa"}],
                shutdown=True)
        _mkring(d, 1, [{"op": "resplit", "wire": 64}], shutdown=True)
        verdict = pm.analyze(pm.load_rings(d))
        assert verdict["verdict"] == "clean", verdict

    def test_oversize_record_keeps_tid(self, tmp_path):
        p = str(tmp_path / "flight_rank0.ring")
        r = flightrec.FlightRecorder(p, rank=0)
        r.record("coll", seq=1, op="resplit", wire=64,
                 tid="feedface00000003", gshape=list(range(200)))
        r.close()
        ring = flightrec.read_ring(p)
        (rec,) = [x for x in ring["records"] if x["k"] == "coll"]
        assert rec.get("trunc") == 1 and rec["tid"] == "feedface00000003"
        assert rec["seq"] == 1 and rec["op"] == "resplit"
