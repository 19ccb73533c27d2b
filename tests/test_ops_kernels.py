"""Pallas kernel tests (interpret mode on the CPU mesh)."""

import numpy as np
import pytest

import heat_tpu as ht

# long-tail contract tests: nightly-style lane (CI 'test' matrix), excluded
# from the PR smoke lane (VERDICT r4 weak #7)
pytestmark = pytest.mark.heavy


class TestFlashAttention:
    """Flash-fused local attention (round-4b): the (S, S) score matrix never
    materializes.  Interpret mode on the CPU mesh; the same pallas_call runs
    compiled on TPU."""

    def _dense(self, q, k, v, causal):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import _dense_attention

        return _dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal,
            1.0 / np.sqrt(q.shape[-1]), q.shape[-2],
        )

    def test_matches_dense(self):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import flash_attention, path_counts

        rng = np.random.default_rng(0)
        before = path_counts["pallas"]
        for shape in ((2, 3, 64, 16), (1, 97, 8), (2, 300, 32)):
            q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                       for _ in range(3))
            for causal in (False, True):
                out = flash_attention(q, k, v, causal=causal)
                ref = self._dense(q, k, v, causal)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
                )
        # every call above actually took the Pallas path (S <= 512 on CPU)
        assert path_counts["pallas"] >= before + 6

    def test_bf16_accumulates_f32(self):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import flash_attention

        rng = np.random.default_rng(1)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 96, 16)), jnp.bfloat16)
                   for _ in range(3))
        out = flash_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        ref = self._dense(np.float32(q), np.float32(k), np.float32(v), True)
        np.testing.assert_allclose(
            np.float32(out), np.asarray(ref), rtol=5e-2, atol=5e-2
        )

    def test_large_s_falls_back_dense_on_cpu(self):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import flash_attention, path_counts

        rng = np.random.default_rng(2)
        q, k, v = (jnp.asarray(rng.normal(size=(1, 600, 8)), jnp.float32)
                   for _ in range(3))
        before = path_counts["dense"]
        out = flash_attention(q, k, v)
        assert path_counts["dense"] == before + 1
        ref = self._dense(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_matches_dense_and_stays_pallas(self):
        """custom_vjp: jax.grad runs the flash backward kernels (dq + dk/dv
        sweeps) — training never silently falls back to the (S, S)-
        materializing dense path (round-4b review finding)."""
        import jax
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import (
            _dense_attention, flash_attention, path_counts,
        )

        rng = np.random.default_rng(7)
        shape = (2, 2, 96, 16)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                   for _ in range(3))
        w = jnp.asarray(rng.normal(size=shape), jnp.float32)
        before = path_counts["pallas"]
        gf = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True) * w),
            argnums=(0, 1, 2),
        )(q, k, v)
        assert path_counts["pallas"] == before + 1  # grad did NOT fall back
        gd = jax.grad(
            lambda q, k, v: jnp.sum(
                _dense_attention(q, k, v, True, 0.25, 96) * w
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_shape_mismatch_raises(self):
        import jax.numpy as jnp
        import pytest

        from heat_tpu.ops.flash_attention import flash_attention

        q = jnp.zeros((1, 8, 4))
        k = jnp.zeros((1, 9, 4))
        with pytest.raises(ValueError):
            flash_attention(q, k, q)

    def test_ring_size1_routes_through_flash(self):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import path_counts as flash_counts
        from heat_tpu.parallel.ring_attention import ring_attention

        import jax
        from jax.sharding import Mesh

        comm = ht.communication.Communication(
            Mesh(np.asarray(jax.devices()[:1]), ("x",))
        )
        rng = np.random.default_rng(3)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 40, 8)), jnp.float32)
                   for _ in range(3))
        before = flash_counts["pallas"]
        out = ring_attention(q, k, v, comm, causal=True)
        assert flash_counts["pallas"] == before + 1
        ref = self._dense(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestFlashGQA:
    """Grouped-query attention kernel: each query head reads its group's
    K/V head straight from the grid index map — no repeated K/V in HBM,
    forward or backward (the dk/dv sweep accumulates a whole group through
    one scratch).  Oracle: dense attention over an explicit repeat."""

    def _ref(self, q, k, v, causal):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import _dense_attention

        g = q.shape[-3] // k.shape[-3]
        return _dense_attention(
            q, jnp.repeat(k, g, axis=-3), jnp.repeat(v, g, axis=-3),
            causal, q.shape[-1] ** -0.5, q.shape[-2],
        )

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("heads", [(4, 2), (4, 1)])  # GQA and MQA
    def test_matches_repeat_oracle(self, heads, causal):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import (
            flash_attention_gqa, path_counts,
        )

        hq, hk = heads
        rng = np.random.default_rng(hq * 10 + hk)
        B, S, d = 2, 40, 8
        q = jnp.asarray(rng.normal(size=(B, hq, S, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, hk, S, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, hk, S, d)), jnp.float32)
        before = path_counts["pallas"]
        out = flash_attention_gqa(q, k, v, causal=causal)
        assert path_counts["pallas"] == before + 1  # kernel, not fallback
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._ref(q, k, v, causal)),
            rtol=1e-5, atol=1e-5,
        )

    def test_grads_match_repeat_oracle(self):
        """dk/dv arrive in K/V-head shape (the group-summed gradient) and
        match differentiating the dense repeat."""
        import jax
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import flash_attention_gqa

        rng = np.random.default_rng(3)
        B, hq, hk, S, d = 2, 4, 2, 37, 8  # ragged S exercises pad keys
        q = jnp.asarray(rng.normal(size=(B, hq, S, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, hk, S, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, hk, S, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(B, hq, S, d)), jnp.float32)
        g = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention_gqa(q, k, v, causal=True) * w),
            (0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(self._ref(q, k, v, True) * w),
            (0, 1, 2))(q, k, v)
        assert g[1].shape == k.shape and g[2].shape == v.shape
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_sdpa_routes_gqa_to_kernel(self):
        import jax.numpy as jnp

        import heat_tpu as ht
        from heat_tpu.ops.flash_attention import path_counts

        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(2, 4, 24, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 1, 24, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 1, 24, 8)), jnp.float32)
        before = path_counts["pallas"]
        y = ht.nn.functional.scaled_dot_product_attention(
            q, k, v, is_causal=True, enable_gqa=True)
        assert path_counts["pallas"] == before + 1
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(self._ref(q, k, v, True)),
            rtol=1e-5, atol=1e-5,
        )

    def test_shape_validation(self):
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import flash_attention_gqa

        q = jnp.zeros((2, 3, 8, 4))
        kv = jnp.zeros((2, 2, 8, 4))
        with pytest.raises(ValueError, match="multiple"):
            flash_attention_gqa(q, kv, kv)

    def test_sdpa_gqa_broadcastable_batch_still_works(self):
        """Unequal-but-broadcastable leading axes must keep the repeat +
        dense einsum path (regression: the kernel route briefly rejected
        them)."""
        import jax.numpy as jnp

        import heat_tpu as ht

        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.normal(size=(2, 4, 24, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, 24, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 1, 24, 8)), jnp.float32)
        y = ht.nn.functional.scaled_dot_product_attention(
            q, k, v, is_causal=True, enable_gqa=True)
        kb = jnp.broadcast_to(k, (2, 1, 24, 8))
        vb = jnp.broadcast_to(v, (2, 1, 24, 8))
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(self._ref(q, kb, vb, True)),
            rtol=1e-5, atol=1e-5,
        )


# ---------------------------------------------------------------------- #
# more than one block: interior, edge and dead grid steps (PR 31)
# ---------------------------------------------------------------------- #


def _fa():
    import importlib

    # the module: ``heat_tpu.ops.flash_attention`` the attribute is the function
    return importlib.import_module("heat_tpu.ops.flash_attention")


@pytest.fixture
def blocks128(monkeypatch):
    """The static-offset kernels at 128 x 128 blocks, so that a CPU-sized
    sequence spans interior, edge and dead blocks.  The block shape is read
    when the jitted plumbing is traced: drop what was traced before and
    after."""
    fa = _fa()

    def clear():
        fa._flash_gqa_fwd_impl.clear_cache()
        fa._flash_gqa_bwd_impl.clear_cache()

    monkeypatch.setattr(fa, "_BLK", 128)
    monkeypatch.setattr(fa, "_BLK_WIDE", 128)
    clear()
    yield fa
    clear()


def _attention_case(fa, kind, S, d, dtype, seed):
    import jax.numpy as jnp

    hq, hk = (4, 1) if kind == "gqa" else (2, 2)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, hq, S, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(1, hk, S, d)), dtype) for _ in range(2))
    w = jnp.asarray(rng.normal(size=(1, hq, S, d)), jnp.float32)
    call = fa.flash_attention_gqa if kind == "gqa" else fa.flash_attention

    def dense(q, k, v, causal, scale):
        f32 = lambda t: t.astype(jnp.float32)
        g = hq // hk
        return fa._dense_attention(f32(q), jnp.repeat(f32(k), g, 1),
                                   jnp.repeat(f32(v), g, 1), causal, scale, S)

    return q, k, v, w, call, dense


def _value_and_grads(f, w, q, k, v):
    import jax
    import jax.numpy as jnp

    out = f(q, k, v)
    grads = jax.grad(lambda *o: jnp.sum(f(*o).astype(jnp.float32) * w),
                     argnums=(0, 1, 2))(q, k, v)
    return (out, *grads)


# the static-offset backward's two paths: one fused sweep wherever its VMEM
# budget holds (every shape of these tests), or the dq and dk/dv sweeps
BWD_PATHS = ["fused", "two_sweeps"]


def take_path(fa, monkeypatch, path):
    """Send the backward down ``path``: the two sweeps by a budget that no
    shape fits."""
    if path == "two_sweeps":
        monkeypatch.setattr(fa, "_fused_bwd_fits", lambda *a: False)


class TestFlashManyBlocks:
    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("S", [512, 400])  # 400: padding crosses the last column
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("kind", ["mha", "gqa"])
    def test_matches_dense(self, blocks128, monkeypatch, kind, causal, S, dtype, path):
        fa = blocks128
        take_path(fa, monkeypatch, path)
        d = 16
        q, k, v, w, call, dense = _attention_case(fa, kind, S, d, dtype, seed=S + causal)
        assert fa._block_census(512, S, 128, 128, causal) == {
            (True, 512): {"interior": 6, "edge": 4, "dead": 6},
            (True, 400): {"interior": 6, "edge": 4, "dead": 6},
            (False, 512): {"interior": 16, "edge": 0, "dead": 0},
            (False, 400): {"interior": 12, "edge": 4, "dead": 0},
        }[causal, S]
        assert fa._scale_folds(d**-0.5)
        before = dict(fa.path_counts)
        got = _value_and_grads(lambda *o: call(*o, causal=causal), w, q, k, v)
        assert fa.path_counts["pallas"] == before["pallas"] + 2  # the kernels, forward and backward
        assert fa.path_counts[f"bwd_{path}"] == before[f"bwd_{path}"] + 1
        want = _value_and_grads(lambda *o: dense(*o, causal, d**-0.5), w, q, k, v)
        tol = dict(rtol=1e-4, atol=1e-5) if dtype == "float32" else dict(rtol=5e-2, atol=5e-2)
        for g, r, what in zip(got, want, ("out", "dq", "dk", "dv")):
            assert g.dtype == q.dtype
            np.testing.assert_allclose(np.float32(g), np.float32(r), err_msg=what, **tol)

    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("how", ["d48", "explicit"])
    def test_a_scale_that_is_no_power_of_two_stays_on_the_scores(self, blocks128, monkeypatch, how, path):
        fa = blocks128
        take_path(fa, monkeypatch, path)
        d = 48 if how == "d48" else 16
        scale = d**-0.5 if how == "d48" else 0.3
        assert not fa._scale_folds(scale)
        q, k, v, w, call, dense = _attention_case(fa, "gqa", 400, d, "float32", seed=48)
        kw = {} if how == "d48" else {"scale": scale}
        got = _value_and_grads(lambda *o: call(*o, causal=True, **kw), w, q, k, v)
        want = _value_and_grads(lambda *o: dense(*o, True, scale), w, q, k, v)
        for g, r, what in zip(got, want, ("out", "dq", "dk", "dv")):
            np.testing.assert_allclose(np.float32(g), np.float32(r), rtol=1e-4, atol=1e-5,
                                       err_msg=what)

    @pytest.mark.parametrize("scale", [0.25, 1.0, 2.0**-10, 0.5])
    def test_powers_of_two_fold(self, scale):
        fa = _fa()
        assert fa._scale_folds(scale)
        assert not fa._scale_folds(scale * 1.5) and not fa._scale_folds(scale / 3)

    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal,s_valid", [(True, 512), (True, 400), (False, 400)])
    def test_every_bit_as_before(self, blocks128, causal, s_valid, dtype, path):
        """Folding a power of two into an operand, scaling an accumulator
        instead of every ``ds``, and dropping guards that guard nothing are
        all exact: at equal block sizes the kernels give the bits that the
        arithmetic before PR 31 gave (written out below in ``jax.numpy``,
        scale on the scores, mask and guards on every live block).  One
        sweep adds each gradient's terms in the two sweeps' order, so it
        gives their bits too."""
        import jax
        import jax.numpy as jnp

        fa = blocks128
        Sp, d, blk, scale = 512, 16, 128, 0.25
        rng = np.random.default_rng(s_valid)
        q, k, v, do = (jnp.asarray(rng.normal(size=(1, Sp, d)), dtype) for _ in range(4))
        out, lse = fa._flash_gqa_fwd_impl(q, k, v, causal, scale, s_valid, 1, 1, True)
        dq, dk, dv = fa._flash_gqa_bwd_impl(q, k, v, out, lse, do, causal, scale, s_valid,
                                            1, 1, True, fused=path == "fused")
        # jitted like the interpreted kernel: op by op, XLA's CPU backend
        # rounds a few of these expressions differently
        want = jax.jit(_as_before, static_argnums=(4, 5, 6, 7))(
            q[0], k[0], v[0], do[0], causal, scale, s_valid, blk)
        for g, r, what in zip((out[0], lse[0, 0], dq[0], dk[0], dv[0]), want,
                              ("out", "lse", "dq", "dk", "dv")):
            np.testing.assert_array_equal(np.float32(g), np.float32(r), err_msg=what)


class TestBackwardPath:
    """Which backward a static-offset call's gradient takes: the fused sweep
    where its resident dQ, accumulators, blocks and scores fit the VMEM its
    ``CompilerParams`` name, else the two sweeps; shapes alone decide."""

    # each model cell's attention layers: S, d, dv
    CELLS = {
        "trinity_mini_26b_a3b_train_1x32k": (32768, 128, 128),
        "smallthinker_21b_a3b_train_1x16k": (16384, 128, 128),
        "moonlight_16b_a3b_train_4x8k": (8192, 192, 128),
        "kimi_linear_48b_a3b_train_2x8k": (8192, 192, 128),
        "lfm2_8b_a1b_train_4x8k": (8192, 64, 64),
    }

    @pytest.mark.parametrize("cell", list(CELLS))
    def test_the_cells_shapes_fuse(self, cell):
        fa = _fa()
        S, d, dv = self.CELLS[cell]
        assert fa._block_shape(S, max(d, dv), 2) == (1024, 1024)
        assert fa._fused_bwd_fits(S, d, dv, 2)
        # Trinity's layers the largest: a head's dQ, dK and dV are 48 MiB of the 68.4
        assert fa._fused_bwd_bytes(S, d, dv, 1024, 2) <= 68.4 * 2**20

    @pytest.mark.parametrize("S,d,itemsize,fits", [
        (49152, 128, 2, True),     # 72 MiB of dQ, dK and dV: compiled for a v5e at the limit
        (65536, 128, 2, False),    # 96 MiB of them
        (131072, 128, 2, False),
        (32768, 256, 2, False),    # 96 MiB, at 1024-row blocks
        (65536, 128, 4, False),    # float32
    ])
    def test_past_the_budget_the_two_sweeps(self, S, d, itemsize, fits):
        fa = _fa()
        assert fa._fused_bwd_fits(S, d, d, itemsize) == fits
        blk = fa._block_shape(S, d, itemsize)[0]
        assert (fa._fused_bwd_bytes(S, d, d, blk, itemsize) <= fa._FUSED_VMEM) == fits

    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("kind", ["mha", "gqa"])
    def test_path_counts_count_each(self, blocks128, monkeypatch, kind, path):
        import jax
        import jax.numpy as jnp

        fa = blocks128
        take_path(fa, monkeypatch, path)
        q, k, v, w, call, _ = _attention_case(fa, kind, 384, 16, "float32", seed=7)
        before = dict(fa.path_counts)
        text = str(jax.make_jaxpr(jax.grad(lambda *o: jnp.sum(call(*o, causal=True) * w),
                                           argnums=(0, 1, 2)))(q, k, v))
        assert {n: fa.path_counts[n] - before[n] for n in before} == {
            "pallas": 1, "dense": 0, "kept": 1,
            "bwd_fused": int(path == "fused"), "bwd_two_sweeps": int(path == "two_sweeps")}
        # the forward kernel and one backward kernel, or two
        assert text.count("pallas_call[") == (2 if path == "fused" else 3)
        # a call without gradients counts no backward
        call(q, k, v, causal=True)
        assert fa.path_counts["bwd_fused"] + fa.path_counts["bwd_two_sweeps"] == \
            before["bwd_fused"] + before["bwd_two_sweeps"] + 1


def _as_before(q, k, v, do, causal, scale, s_valid, blk):
    """One head's forward and backward as the kernels computed them before
    PR 31, block by block: ``_masked_scores`` (scale on the scores, the mask
    on every live block), ``_online_update`` and ``_recompute_p`` with their
    ``isfinite`` guards, ``ds`` scaled score by score."""
    import jax
    import jax.numpy as jnp

    Sp, d = q.shape
    n = Sp // blk
    dot = lambda a, b, dims: jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)
    rows = lambda i: slice(i * blk, (i + 1) * blk)

    def live(i, j):
        return j * blk < s_valid and (not causal or j * blk <= i * blk + blk - 1)

    def scores(i, j):
        s = dot(q[rows(i)], k[rows(j)], ((1,), (1,))) * scale
        kv_pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        mask = kv_pos < s_valid
        if causal:
            mask = mask & (i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0) >= kv_pos)
        return jnp.where(mask, s, -jnp.inf)

    out, lse = [], []
    for i in range(n):
        m = jnp.full((blk,), -jnp.inf, jnp.float32)
        l = jnp.zeros((blk,), jnp.float32)
        acc = jnp.zeros((blk, d), jnp.float32)
        for j in range(n):
            if not live(i, j):
                continue
            s = scores(i, j)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m[:, None]), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[:, None] + dot(p.astype(v.dtype), v[rows(j)], ((1,), (0,)))
            m = m_new
        out.append((acc / jnp.maximum(l, 1e-30)[:, None]).astype(q.dtype))
        lse.append(jnp.where(jnp.isfinite(m), m, 0.0) + jnp.log(jnp.maximum(l, 1e-30)))
    out, lse = jnp.concatenate(out), jnp.concatenate(lse)
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def p_ds(i, j):
        s = scores(i, j)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse[rows(i)][:, None]), 0.0)
        dp = dot(do[rows(i)], v[rows(j)], ((1,), (1,)))
        return p, p * (dp - dd[rows(i)][:, None]) * scale

    dq, dk, dv = [], [], []
    for i in range(n):
        acc = jnp.zeros((blk, d), jnp.float32)
        for j in range(n):
            if live(i, j):
                acc = acc + dot(p_ds(i, j)[1].astype(k.dtype), k[rows(j)], ((1,), (0,)))
        dq.append(acc.astype(q.dtype))
    for j in range(n):
        acc_k = jnp.zeros((blk, d), jnp.float32)
        acc_v = jnp.zeros((blk, d), jnp.float32)
        for i in range(n):
            if live(i, j):
                p, ds = p_ds(i, j)
                acc_v = acc_v + dot(p.astype(do.dtype), do[rows(i)], ((0,), (0,)))
                acc_k = acc_k + dot(ds.astype(q.dtype), q[rows(i)], ((0,), (0,)))
        dk.append(acc_k.astype(k.dtype))
        dv.append(acc_v.astype(v.dtype))
    return out, lse, jnp.concatenate(dq), jnp.concatenate(dk), jnp.concatenate(dv)


class TestBlockKind:
    """The classification that decides which body a grid step runs."""

    CASES = [  # Sp, s_valid, blk_q, blk_k, causal
        (512, 512, 128, 128, True), (512, 400, 128, 128, True),
        (512, 400, 128, 128, False), (512, 512, 128, 128, False),
        (1024, 1000, 128, 256, True), (1024, 770, 256, 128, True),
        (1024, 513, 512, 256, False), (1024, 1, 256, 512, True),
        (384, 300, 128, 384, True), (768, 768, 384, 128, True),
    ]

    @pytest.mark.parametrize("Sp,s_valid,blk_q,blk_k,causal", CASES)
    def test_against_the_mask_itself(self, Sp, s_valid, blk_q, blk_k, causal):
        fa = _fa()
        rows, keys = np.arange(Sp)[:, None], np.arange(Sp)[None, :]
        mask = np.broadcast_to(keys < s_valid, (Sp, Sp))
        if causal:
            mask = mask & (rows >= keys)
        counted = {"interior": 0, "edge": 0, "dead": 0}
        for iq in range(Sp // blk_q):
            for ik in range(Sp // blk_k):
                q_lo, k_lo = iq * blk_q, ik * blk_k
                block = mask[q_lo:q_lo + blk_q, k_lo:k_lo + blk_k]
                live, interior = fa._block_kind(q_lo, k_lo, blk_q, blk_k, s_valid, causal)
                assert bool(interior) == bool(block.all()), (iq, ik)
                assert bool(live) == bool(block.any()), (iq, ik)
                counted["interior" if block.all() else "edge" if block.any() else "dead"] += 1
                # the clamped index maps: a live step fetches its own block,
                # a dead one the nearest live block of its sweep
                last = int(fa._last_live_k(iq, blk_q, blk_k, s_valid, causal))
                first = int(fa._first_live_q(ik, blk_q, blk_k, causal))
                assert bool(live) == (ik <= last), (iq, ik)
                if k_lo < s_valid:
                    assert bool(live) == (iq >= first), (iq, ik)
        assert fa._block_census(Sp, s_valid, blk_q, blk_k, causal) == counted

    def test_the_training_cell(self):
        fa = _fa()
        # lfm2_8b_a1b_train_4x8k: S = 8192, causal, no padding
        assert fa._block_census(8192, 8192, 512, 512, True) == {
            "interior": 120, "edge": 16, "dead": 120}
        # ... and at the block shape the kernels choose for it
        assert fa._block_shape(8192, 64, 2) == (1024, 1024)
        assert fa._block_census(8192, 8192, 1024, 1024, True) == {
            "interior": 28, "edge": 8, "dead": 28}
