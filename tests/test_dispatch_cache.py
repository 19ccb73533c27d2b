"""Zero-copy dispatch contract tests (sharding-keyed program cache +
buffer donation across the op layer).

Three claims are pinned here, matching the dispatch redesign:

- **cache**: repeated ops with an identical ``(op, avals, split)`` signature
  reuse ONE compiled executable — zero recompilation over 100+ calls,
  observable through the ``utils.profiler`` hit/miss counters;
- **donation**: the in-place surfaces (``__i*__`` dunders, ``resplit_``,
  the DASO/DataParallel train steps) hand their input buffers to XLA —
  ``input_output_alias`` shows up in the compiled HLO where layouts permit
  aliasing, and the donated source buffer is actually consumed;
- **correctness**: cached/donating paths produce the same values and split
  metadata as the eager path they replaced.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _cache
from heat_tpu.utils import profiler


def _dispatch_table(comm):
    return comm.__dict__.get("_compiled_programs", {}).get(
        _cache._DISPATCH_SLOT, {}
    )


class TestProgramCacheHitRate:
    def test_repeated_ops_zero_recompilation(self):
        """≥100 repeated same-signature ops: every one a cache hit."""
        x = ht.random.randn(64, 32, split=0)
        y = ht.random.randn(64, 32, split=0)
        # warmup: one miss per distinct signature
        _ = x + y, x * 2, ht.exp(x), ht.sum(x, axis=0), ht.cumsum(x, axis=0)
        profiler.reset_cache_stats()
        n0 = len(_dispatch_table(x.comm))
        for _ in range(25):
            _ = x + y
            _ = x * 2
            _ = ht.exp(x)
            _ = ht.sum(x, axis=0)
            _ = ht.cumsum(x, axis=0)
        stats = profiler.cache_stats()
        assert stats["misses"] == 0, f"recompilations after warmup: {stats}"
        assert stats["hits"] >= 125
        assert profiler.cache_hit_rate() >= 0.99
        assert len(_dispatch_table(x.comm)) == n0  # no table growth

    def test_distinct_signatures_miss_once(self):
        x = ht.random.randn(16, 16, split=0)
        profiler.reset_cache_stats()
        _ = x + 1.5
        _ = x + 2.5  # same program: the scalar is a runtime arg, not a constant
        s = profiler.cache_stats()
        assert s["misses"] == 1 and s["hits"] == 1, s
        y = x.resplit(1)
        s = profiler.cache_stats()
        _ = y + 1.5  # different operand split: a new signature
        assert profiler.cache_stats()["misses"] == s["misses"] + 1

    def test_cached_path_matches_eager_metadata(self):
        x = ht.random.randn(64, 32, split=0)
        y = ht.random.randn(64, 32, split=0)
        for _ in range(2):  # second pass takes the cached program
            z = x * y
            assert z.split == 0 and z.shape == (64, 32)
            s0 = ht.sum(x, axis=0)
            assert s0.split is None  # reduced over the split axis
            s1 = ht.sum(x, axis=1)
            assert s1.split == 0
            c = ht.cumsum(x, axis=1)
            assert c.split == 0
        np.testing.assert_allclose(z.numpy(), x.numpy() * y.numpy(), rtol=1e-5)
        np.testing.assert_allclose(
            s1.numpy(), x.numpy().sum(axis=1), rtol=1e-4, atol=1e-4
        )

    def test_matmul_program_cached(self):
        a = ht.random.randn(32, 16, split=0)
        b = ht.random.randn(16, 24)
        c1 = a @ b
        profiler.reset_cache_stats()
        c2 = a @ b
        s = profiler.cache_stats()
        assert s["misses"] == 0 and s["hits"] >= 1
        assert c2.split == c1.split == 0
        np.testing.assert_allclose(
            c2.numpy(), a.numpy() @ b.numpy(), rtol=1e-4, atol=1e-4
        )

    def test_weak_scalar_promotion_preserved(self):
        # scalars ride as weak-typed runtime args: int8 + 2 stays int8,
        # exactly like the eager path
        x = ht.array(np.arange(6, dtype=np.int8), split=0)
        for _ in range(2):
            y = x + 2
            assert y.dtype == ht.int8, y.dtype
        z = x + 2.5  # weak float promotes to the default float
        assert z.dtype == ht.float32

    def test_tracer_dispatch_bypasses_cache(self):
        # inside jit the surrounding trace owns compilation; the dispatch
        # cache must not capture tracers
        x = ht.random.randn(16, 8, split=0)

        @jax.jit
        def f(a):
            return a + a * 2

        r = f(x)
        np.testing.assert_allclose(r.numpy(), x.numpy() * 3, rtol=1e-5)


class TestDonation:
    def test_iadd_emits_input_output_alias(self):
        """The in-place dunder's compiled program aliases in/out buffers."""
        x = ht.random.randn(32, 16, split=0)
        x += 1.0  # builds + caches the donating program
        table = _dispatch_table(x.comm)
        progs = [
            v for k, v in table.items()
            if k[0] == "binary" and k[4] is True  # the donate key component
        ]
        assert progs, f"no donating binary program cached: {list(table)}"
        prog = progs[-1][0]
        hlo = prog.lower(x._jarray, 1.0).compile().as_text()
        assert "input_output_alias" in hlo, "donation did not alias in/out"

    def test_iadd_consumes_old_buffer(self):
        x = ht.random.randn(32, 16, split=0)
        ref = x.numpy()
        old = x._parray
        x += 2.0
        np.testing.assert_allclose(x.numpy(), ref + 2.0, rtol=1e-6)
        assert old.is_deleted(), "in-place add kept a second live copy"

    def test_out_of_place_never_donates(self):
        x = ht.random.randn(32, 16, split=0)
        y = x + 1.0
        _ = x + 1.0  # cached path again
        np.testing.assert_allclose(
            (x + y).numpy(), 2 * x.numpy() + 1.0, rtol=1e-5, atol=1e-6
        )  # x still alive and correct (atol: near-zero elements may differ
        # by one float32 ulp between the cached program's (x+y) association
        # and the numpy oracle's 2x+1)

    def test_self_referencing_iadd_safe(self):
        # x += x may not donate (one buffer, two args) — falls back cleanly
        x = ht.random.randn(16, 8, split=0)
        ref = x.numpy()
        x += x
        np.testing.assert_allclose(x.numpy(), 2 * ref, rtol=1e-6)

    def test_resplit_donates_source_buffer(self):
        """resplit_ hands its source buffer to the reshard program
        (``donate_argnums``): it is freed once the transfer has consumed it.
        The copying form leaves its source alive and readable."""
        comm = ht.communication.get_comm()
        if not comm.is_distributed():
            pytest.skip("resplit needs a multi-device mesh")
        x = ht.random.randn(32, 16, split=0)
        ref = x.numpy()
        old = x._jarray
        x.resplit_(1)
        assert old.is_deleted(), "resplit_ did not donate its source"
        assert x.split == 1
        np.testing.assert_array_equal(x.numpy(), ref)
        # the copying form must NOT donate (source stays live)
        src = x._jarray
        y = x.resplit(0)
        assert not src.is_deleted() and x._jarray is src
        np.testing.assert_array_equal(x.numpy(), ref)
        np.testing.assert_array_equal(y.numpy(), ref)

    def test_resplit_roundtrip_values(self):
        x = ht.random.randn(48, 16, split=0)
        ref = x.numpy()
        x.resplit_(1)
        np.testing.assert_allclose(x.numpy(), ref, rtol=1e-6)
        x.resplit_(None)
        np.testing.assert_allclose(x.numpy(), ref, rtol=1e-6)
        x.resplit_(0)
        np.testing.assert_allclose(x.numpy(), ref, rtol=1e-6)
        assert x.split == 0


class TestTrainStepDonation:
    def _mesh_4x2(self):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 devices")
        from jax.sharding import Mesh

        return Mesh(np.asarray(devs[:8]).reshape(4, 2), ("dcn", "ici"))

    def test_daso_step_emits_input_output_alias(self):
        """The DASO per-step program aliases params/opt_state in→out: the
        hierarchical train loop holds ONE copy of the model state."""
        mesh = self._mesh_4x2()
        opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
        daso = ht.optim.DASO(opt, mesh=mesh, global_skip=2, warmup_steps=0)
        model = ht.nn.Sequential(ht.nn.Linear(8, 16), ht.nn.ReLU(), ht.nn.Linear(16, 4))
        daso.init(model, key=jax.random.key(0))

        def loss_fn(pred, y):
            return jnp.mean((pred - y) ** 2)

        daso._build_steps(loss_fn)
        g, ici = daso.n_groups, daso.ici_size
        xs = jnp.zeros((g, 4 * ici, 8), jnp.float32)
        ys = jnp.zeros((g, 4 * ici, 4), jnp.float32)
        hlo = (
            daso._train_step.lower(daso._params, daso._opt_state, xs, ys)
            .compile()
            .as_text()
        )
        assert "input_output_alias" in hlo, "DASO step does not donate state"

    def test_daso_losses_stay_on_device(self):
        # host-sync audit: step() returns an async 0-d device array, not a
        # blocking float — materialization is the caller's choice
        mesh = self._mesh_4x2()
        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer("sgd", lr=0.05), mesh=mesh, warmup_steps=1
        )
        model = ht.nn.Sequential(ht.nn.Linear(8, 4))
        daso.init(model, key=jax.random.key(1))

        def loss_fn(pred, y):
            return jnp.mean((pred - y) ** 2)

        rng = np.random.default_rng(0)
        xb = rng.normal(size=(16, 8)).astype(np.float32)
        loss = daso.step(loss_fn, jnp.asarray(xb), jnp.asarray(xb @ np.ones((8, 4), np.float32)))
        assert isinstance(loss, jax.Array)
        assert float(loss) >= 0.0  # materializes on demand

    def test_data_parallel_step_donates_and_trains(self):
        opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
        dp = ht.nn.DataParallel(
            ht.nn.Sequential(ht.nn.Flatten(), ht.nn.Linear(8, 4)), optimizer=opt
        )
        params = dp.init(jax.random.key(0))
        state = opt.init_state(params)
        step = dp.make_train_step(lambda p, y: jnp.mean((p - y) ** 2))
        hlo = None
        x = jnp.zeros((16, 8), jnp.float32)
        y = jnp.zeros((16, 4), jnp.float32)
        hlo = step.lower(params, state, x, y).compile().as_text()
        assert "input_output_alias" in hlo
        old_leaves = jax.tree_util.tree_leaves(params)
        params, state, loss = step(params, state, x, y)
        # the pre-step replicas were consumed (no second live copy)
        assert any(leaf.is_deleted() for leaf in old_leaves)
        params, state, loss = step(params, state, x, y)  # rebind loop works
        assert np.isfinite(float(loss))

    def test_data_parallel_step_donation_opt_out(self):
        opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
        dp = ht.nn.DataParallel(
            ht.nn.Sequential(ht.nn.Flatten(), ht.nn.Linear(8, 4)), optimizer=opt
        )
        params = dp.init(jax.random.key(0))
        state = opt.init_state(params)
        step = dp.make_train_step(lambda p, y: jnp.mean((p - y) ** 2), donate=False)
        x = jnp.zeros((16, 8), jnp.float32)
        y = jnp.zeros((16, 4), jnp.float32)
        new_params, _, _ = step(params, state, x, y)
        # opt-out keeps the old tree alive (e.g. for trust-region rollbacks)
        assert all(not leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(params))
