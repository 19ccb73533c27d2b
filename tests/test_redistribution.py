"""Memory-bounded streaming resplit (ISSUE 6): planner, executor, wiring.

Three layers under test:

- the PURE planner (``plan_resplit``): tile-axis choice, budget→tile-extent
  math, exact partitioning (tail tile clipped, never overlapping), and every
  monolithic-fallback reason;
- the streaming executor through the public surfaces
  (``Communication.resplit(memory_budget=)`` / ``resplit_tiled`` /
  ``manipulations.resplit`` / ``DNDarray.resplit_``): bit-exact equality
  with the unchunked path over all transitions × budgets, canonical output
  sharding, program-cache steady state (second identical resplit compiles
  NOTHING), and telemetry — ``comm.resplit.bytes`` totals IDENTICAL between
  chunked and monolithic (telescoped per-tile accounting), ``.calls`` = K,
  ``.tiles`` = K, ``.peak_tile_bytes`` = the largest tile;
- the robustness hooks: per-tile ``comm.collective`` fault site under an
  armed ``comm.deadline`` (a hung tile trips ``CollectiveTimeoutError``) and
  the budget default plumbing (``set_redistribution_budget`` / env parsing);
- the monolithic program (ISSUE 26): across devices ``Communication.resplit``
  launches one cached identity with ``out_shardings`` (an all-to-all for
  k→j), counted under ``comm.resplit.compiled``; what the input shows decides
  whether it engages.
"""

import warnings

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import redistribution as rd
from heat_tpu.core.communication import Communication
from heat_tpu.utils import profiler


@pytest.fixture(autouse=True)
def _no_process_budget():
    """Tests control the budget explicitly; never inherit another test's."""
    prev = rd.set_redistribution_budget(None)
    yield
    rd.set_redistribution_budget(prev)


def _counters():
    return {
        k: v for k, v in profiler.counters().items() if k.startswith("comm.resplit")
    }


# ---------------------------------------------------------------------- #
# planner (pure)
# ---------------------------------------------------------------------- #
class TestPlanner:
    def test_basic_tiling(self):
        p = rd.plan_resplit((8, 5, 8), 4, 0, 2, 8, 512)
        assert p.tile_axis == 1  # the only non-split axis
        assert p.n_tiles > 1 and p.reason == "tiled"
        assert p.max_tile_bytes <= 512

    def test_tiles_partition_exactly(self):
        p = rd.plan_resplit((8, 7, 8), 4, 0, 2, 8, 500)
        spans = [p.tile_bounds(i) for i in range(p.n_tiles)]
        # contiguous, non-overlapping, covering [0, n)
        assert spans[0][0] == 0
        for (s0, l0), (s1, _) in zip(spans, spans[1:]):
            assert s0 + l0 == s1
        s, length = spans[-1]
        assert s + length == 7
        assert sum(length for _, length in spans) == 7
        # byte totals partition too (tail tile clipped, not padded-counted)
        assert sum(p.tile_nbytes(length) for _, length in spans) == p.total_bytes

    def test_largest_free_axis_wins(self):
        p = rd.plan_resplit((8, 3, 9, 8), 4, 0, 3, 8, 1024)
        assert p.tile_axis == 2

    def test_budget_below_one_slice_floors(self):
        # tiling axis 1 (extent 4), one slice = 1024 B >> the 1 B budget:
        # best effort floors at one slice per tile
        p = rd.plan_resplit((8, 4, 8), 4, 0, 2, 8, 1)
        assert p.tile_axis == 1 and p.tile_extent == 1
        assert p.n_tiles == 4
        assert p.reason == "tiled-floor-one-slice"

    @pytest.mark.parametrize(
        "gshape,src,dst,budget,reason",
        [
            ((8, 5, 8), 0, 2, None, "no-budget"),
            ((16,), 0, None, 16, "too-few-dims"),
            ((), None, None, 16, "too-few-dims"),
            ((8, 5, 8), 0, 2, 10**9, "fits-in-budget"),
            ((9, 5, 8), 0, 2, 64, "ragged-src"),
            ((8, 5, 9), 0, 2, 64, "ragged-dst"),
            ((8, 8), 0, 1, 64, "no-free-axis"),
            ((8, 1, 8), 0, 2, 64, "no-free-axis"),  # free axis too short
        ],
    )
    def test_monolithic_reasons(self, gshape, src, dst, budget, reason):
        p = rd.plan_resplit(gshape, 4, src, dst, 8, budget)
        assert p.n_tiles == 1 and p.tile_axis is None
        assert p.reason == reason

    def test_negative_split_normalized(self):
        p = rd.plan_resplit((8, 5, 8), 4, 0, -1, 8, 512)
        assert p.dst_split == 2 and p.tile_axis == 1

    def test_parse_budget(self):
        assert rd.parse_budget(None) is None
        assert rd.parse_budget(0) is None
        assert rd.parse_budget(-3) is None
        assert rd.parse_budget("") is None
        assert rd.parse_budget(4096) == 4096
        assert rd.parse_budget("512") == 512
        assert rd.parse_budget("4K") == 4096
        assert rd.parse_budget("64M") == 64 * 2**20
        assert rd.parse_budget("2GB") == 2 * 2**30
        # fractional budgets scale BEFORE truncation ("0.5G" must not
        # int()-truncate to 0 and silently mean unbounded)
        assert rd.parse_budget("0.5G") == 512 * 2**20
        assert rd.parse_budget("1.5M") == 1536 * 2**10

    def test_default_budget_roundtrip(self):
        prev = rd.set_redistribution_budget("1M")
        try:
            assert rd.get_redistribution_budget() == 2**20
            assert ht.get_redistribution_budget() == 2**20  # flat re-export
        finally:
            rd.set_redistribution_budget(prev)


# ---------------------------------------------------------------------- #
# round-trip correctness over transitions × budgets
# ---------------------------------------------------------------------- #
def _fresh(shape, split):
    n = int(np.prod(shape))
    return ht.reshape(ht.arange(n, dtype=ht.float32, split=0), shape).resplit(split)


class TestRoundTrip:
    SHAPE = (16, 6, 8)

    @pytest.mark.mp
    @pytest.mark.parametrize("src,dst", [(0, 2), (2, 0), (0, None), (None, 0), (1, 2)])
    @pytest.mark.parametrize("budget", [256, 4096, "64M"])
    def test_bit_exact_vs_monolithic(self, src, dst, budget):
        x = _fresh(self.SHAPE, src)
        ref = x.resplit(dst)  # unchunked oracle
        got = x.resplit(dst, memory_budget=budget)
        assert got.split == dst
        np.testing.assert_array_equal(got.numpy(), ref.numpy())
        comm = x.comm
        assert got._jarray.sharding == comm.sharding(len(self.SHAPE), dst)

    def test_one_slice_budget(self):
        # the finest possible streaming: one tiling-axis slice per tile
        x = _fresh((8, 6, 8), 0)
        got = x.resplit(2, memory_budget=1)
        np.testing.assert_array_equal(got.numpy(), x.resplit(2).numpy())

    def test_ragged_tiling_axis_tail_tile(self):
        x = _fresh((8, 7, 8), 0)  # 7 on the tiling axis: K=4 with tail 1
        comm = x.comm
        plan = rd.plan_resplit((8, 7, 8), 4, 0, 2, comm.size, 600)
        assert plan.n_tiles == 4
        assert plan.tile_bounds(plan.n_tiles - 1)[1] < plan.tile_extent
        got = x.resplit(2, memory_budget=600)
        np.testing.assert_array_equal(got.numpy(), x.resplit(2).numpy())

    def test_inplace_budgeted(self):
        x = _fresh(self.SHAPE, 0)
        want = x.numpy()
        x.resplit_(2, memory_budget=512)
        assert x.split == 2
        np.testing.assert_array_equal(x.numpy(), want)

    def test_inplace_budgeted_to_none(self):
        x = _fresh(self.SHAPE, 0)
        want = x.numpy()
        x.resplit_(None, memory_budget=512)
        assert x.split is None
        np.testing.assert_array_equal(x.numpy(), want)

    def test_process_default_budget_applies(self):
        x = _fresh(self.SHAPE, 0)
        ref = x.resplit(2)
        rd.set_redistribution_budget(512)
        profiler.reset_counters()
        got = x.resplit(2)
        assert _counters()["comm.resplit.tiles"] > 1  # the default kicked in
        np.testing.assert_array_equal(got.numpy(), ref.numpy())

    def test_explicit_zero_budget_forces_monolithic(self):
        rd.set_redistribution_budget(512)
        x = _fresh(self.SHAPE, 0)
        profiler.reset_counters()
        got = x.resplit(2, memory_budget=0)  # overrides the process default
        assert _counters()["comm.resplit.tiles"] == 1
        np.testing.assert_array_equal(got.numpy(), x.resplit(2).numpy())

    def test_edge_cases_fall_back(self):
        # 2-d k->j (no free axis), 1-d, ragged: all monolithic, all exact
        m = _fresh((8, 8), 0)
        np.testing.assert_array_equal(
            m.resplit(1, memory_budget=64).numpy(), m.resplit(1).numpy()
        )
        v = ht.arange(16, dtype=ht.float32, split=0)
        np.testing.assert_array_equal(
            v.resplit(None, memory_budget=8).numpy(), np.arange(16, dtype=np.float32)
        )
        r = ht.reshape(ht.arange(9 * 5 * 8, dtype=ht.float32), (9, 5, 8))
        got = r.resplit(0, memory_budget=64)  # ragged dst -> monolithic
        np.testing.assert_array_equal(got.numpy(), r.numpy())

    def test_resplit_tiled_explicit_entry(self):
        comm = ht.communication.get_comm()
        x = _fresh(self.SHAPE, 0)
        out = comm.resplit_tiled(x._jarray, 2, memory_budget=512)
        assert out.sharding == comm.sharding(3, 2)
        np.testing.assert_array_equal(
            np.asarray(Communication.host_fetch(out)), x.resplit(2).numpy()
        )
        # untileable input degenerates to the monolithic path, same result
        m = _fresh((8, 8), 0)
        out2 = comm.resplit_tiled(m._jarray, 1, memory_budget=64)
        np.testing.assert_array_equal(
            np.asarray(Communication.host_fetch(out2)), m.resplit(1).numpy()
        )


# ---------------------------------------------------------------------- #
# telemetry: exact byte totals, tiles, peak tile, calls
# ---------------------------------------------------------------------- #
class TestAccounting:
    SHAPE = (16, 6, 8)

    def test_bytes_identical_chunked_vs_monolithic(self):
        # including an odd budget whose tiles do NOT divide the total evenly:
        # the telescoped per-tile accounting must still sum to the byte
        for budget in (256, 500, 1000, 4096):
            x = _fresh(self.SHAPE, 0)
            profiler.reset_counters()
            _ = x.resplit(2, memory_budget=0)
            mono = _counters()
            profiler.reset_counters()
            _ = x.resplit(2, memory_budget=budget)
            tiled = _counters()
            assert tiled["comm.resplit.bytes"] == mono["comm.resplit.bytes"], budget
            assert mono["comm.resplit.calls"] == 1
            assert mono["comm.resplit.tiles"] == 1

    def test_tiles_calls_and_peak(self):
        comm = ht.communication.get_comm()
        x = _fresh(self.SHAPE, 0)
        plan = rd.make_plan(comm, x._jarray, 2, 512)
        assert plan is not None and plan.n_tiles > 1
        profiler.reset_counters()
        _ = x.resplit(2, memory_budget=512)
        c = _counters()
        assert c["comm.resplit.calls"] == plan.n_tiles  # one staged transfer per tile
        assert c["comm.resplit.tiles"] == plan.n_tiles
        assert c["comm.resplit.peak_tile_bytes"] == plan.max_tile_bytes
        assert c["comm.resplit.peak_tile_bytes"] <= 512

    def test_noop_resplit_still_uncounted(self):
        x = _fresh(self.SHAPE, 0)
        profiler.reset_counters()
        _ = x.resplit(0, memory_budget=512)  # already there: no bytes, no tiles
        assert _counters().get("comm.resplit.calls", 0) == 0
        assert _counters().get("comm.resplit.tiles", 0) == 0

    def test_counter_max_semantics(self):
        profiler.reset_counters()
        profiler.counter_max("t.peak", 5)
        profiler.counter_max("t.peak", 3)
        profiler.counter_max("t.peak", 9)
        assert profiler.counters()["t.peak"] == 9


# ---------------------------------------------------------------------- #
# program cache: steady-state chunked resplit recompiles nothing
# ---------------------------------------------------------------------- #
class TestProgramCache:
    def test_zero_recompiles_second_run(self):
        shape = (16, 6, 8)
        x = _fresh(shape, 0)
        _ = x.resplit(2, memory_budget=512)  # warm: builds the per-tile programs
        y = _fresh(shape, 0)  # fresh array, same signature
        profiler.reset_cache_stats()
        got = y.resplit(2, memory_budget=512)
        stats = profiler.cache_stats()
        assert stats["misses"] == 0, stats
        assert stats["hits"] > 0
        np.testing.assert_array_equal(got.numpy(), y.resplit(2).numpy())

    def test_flip_flop_steady_state(self):
        x = _fresh((16, 6, 8), 0)
        x.resplit_(2, memory_budget=512)
        x.resplit_(0, memory_budget=512)  # warm both directions
        profiler.reset_cache_stats()
        x.resplit_(2, memory_budget=512)
        x.resplit_(0, memory_budget=512)
        assert profiler.cache_stats()["misses"] == 0


# ---------------------------------------------------------------------- #
# robustness hooks
# ---------------------------------------------------------------------- #
class TestRobustness:
    def test_hung_tile_trips_deadline(self):
        from heat_tpu.utils import faults, health

        comm = ht.communication.get_comm()
        x = _fresh((16, 6, 8), 0)
        profiler.reset_counters()
        with faults.inject("comm.collective", hang=1):
            with pytest.raises(health.CollectiveTimeoutError):
                with comm.deadline(0.3):
                    x.resplit(2, memory_budget=512)
        # a mid-plan abort leaves the plan-shape counters CONSISTENT with
        # the per-tile traffic counters (tiles advance per tile, not at the
        # end of the loop): the hung tile staged nothing, so both are equal
        c = _counters()
        assert c.get("comm.resplit.tiles", 0) == c.get("comm.resplit.calls", 0)

    def test_blown_deadline_refuses_next_tile(self):
        import time

        from heat_tpu.utils import health

        comm = ht.communication.get_comm()
        x = _fresh((16, 6, 8), 0)
        with pytest.raises(health.CollectiveTimeoutError):
            with comm.deadline(0.05):
                time.sleep(0.1)  # blow the budget before the first tile
                x.resplit(2, memory_budget=512)

    def test_no_warnings_on_tiled_path(self):
        # the expected "donated buffers were not usable" compile noise of the
        # per-tile programs must be filtered at the source
        x = _fresh((16, 6, 8), 0)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = x.resplit(2, memory_budget=512)
        np.testing.assert_array_equal(got.numpy(), x.resplit(2).numpy())

    def test_sanitizer_checks_tiled_output(self):
        from heat_tpu.core import sanitation

        was = sanitation.checks_enabled()
        sanitation.enable_checks()
        try:
            x = _fresh((16, 6, 8), 0)
            got = x.resplit(2, memory_budget=512)  # the resplit_check slot runs on out
            assert got.split == 2
            got2 = sanitation.check(got, "test")
            assert got2 is got
        finally:
            if not was:
                sanitation.disable_checks()

    def test_tracer_falls_back(self):
        import jax

        comm = ht.communication.get_comm()
        rd.set_redistribution_budget(64)

        @jax.jit
        def f(j):
            return comm.resplit(j, 1)  # tracer: planner must decline

        x = _fresh((8, 8), 0)
        out = f(x._jarray)
        np.testing.assert_array_equal(np.asarray(out), x.numpy())


# ---------------------------------------------------------------------- #
# the monolithic program: one cached identity with out_shardings
# ---------------------------------------------------------------------- #
def _compiled():
    return profiler.counters().get("comm.resplit.compiled", 0)


def _programs(comm):
    """The monolithic reshard programs in ``comm``'s table, by key."""
    from heat_tpu.core import _cache

    table = comm._compiled_programs.get(_cache._DISPATCH_SLOT, {})
    return {k: p for k, p in table.items() if k[:2] == ("resplit", "mono")}


class TestMonolithicProgram:
    SHAPE = (16, 8)

    @pytest.mark.parametrize(
        "src,dst", [(0, 1), (1, 0), (0, None), (1, None), (None, 0), (None, 1)]
    )
    def test_engages_across_devices(self, src, dst):
        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("the program needs a multi-device mesh")
        x, y = _fresh(self.SHAPE, src), _fresh(self.SHAPE, src)
        want = x.numpy()
        profiler.reset_counters()
        got = comm.resplit(x._jarray, dst)
        assert _compiled() == 1
        assert got.sharding == comm.sharding(2, dst)
        np.testing.assert_array_equal(np.asarray(got), want)  # bit for bit
        # a fresh array of the same signature: a hit, nothing built
        profiler.reset_cache_stats()
        again = comm.resplit(y._jarray, dst)
        assert _compiled() == 2
        assert profiler.cache_stats() == {"hits": 1, "misses": 0, "slow": 0}
        np.testing.assert_array_equal(np.asarray(again), want)
        assert _counters()["comm.resplit.calls"] == 2

    def test_program_is_an_all_to_all(self):
        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("the program needs a multi-device mesh")
        x = _fresh((32, 24), 0)
        comm.resplit(x._jarray, 1)
        prog = _programs(comm)[("resplit", "mono", (32, 24), "float32", 0, 1, False)]
        assert "all-to-all" in prog.lower(x._jarray).compile().as_text()

    def test_inplace_form_is_its_own_donating_program(self):
        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("the program needs a multi-device mesh")
        x = _fresh((32, 8), 0)
        want, old = x.numpy(), x._jarray
        profiler.reset_counters()
        with warnings.catch_warnings():
            # "donated buffers were not usable" is filtered at the launch
            warnings.simplefilter("error")
            x.resplit_(1)
        assert _compiled() == 1 and old.is_deleted()
        np.testing.assert_array_equal(x.numpy(), want)
        keys = [k for k in _programs(comm) if k[2] == (32, 8)]
        assert [k[-1] for k in keys] == [True]  # donate is part of the key

    @pytest.mark.parametrize(
        "case", ["ragged", "tracer", "already_placed", "one_device", "off_mesh"]
    )
    def test_does_not_engage(self, case):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("the program needs a multi-device mesh")
        want = np.arange(9 * 8, dtype=np.float32).reshape(9, 8)
        j = jnp.asarray(want)  # one default device: not on the mesh
        profiler.reset_counters()
        profiler.reset_cache_stats()
        if case == "ragged":
            out = comm.resplit(j, 0)  # 9 rows over the mesh: split stays logical
            assert out is j
        elif case == "tracer":
            x = _fresh((8, 8), 0)
            want = x.numpy()
            out = jax.jit(lambda t: comm.resplit(t, 1))(x._jarray)
        elif case == "already_placed":
            x = _fresh((8, 8), 0)
            want = x.numpy()
            out = comm.resplit(x._jarray, 0)
            assert out is x._jarray
            assert _counters().get("comm.resplit.calls", 0) == 0  # still uncounted
        elif case == "one_device":
            one = Communication(Mesh(np.asarray(jax.devices()[:1]), ("x",)), "x")
            src = one.shard(j, 0)
            out = one.resplit(src, 1)
            assert out.sharding == one.sharding(2, 1)
        else:
            out = comm.resplit(j, 1)  # shard()'s device_put brings it onto the mesh
            assert out.sharding == comm.sharding(2, 1)
        assert _compiled() == 0
        assert profiler.cache_stats()["misses"] == 0
        np.testing.assert_array_equal(np.asarray(out), want)
