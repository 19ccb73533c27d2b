"""The static-offset flash kernels under a window (interpreter on the CPU):
against ``_dense_attention(window=)`` forward and backward, the block
classification against the mask itself, the guard of rows that have met no key
yet, and ``window=None`` still lowering to the programs it lowered to before
there was a window."""

import gzip
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ops_kernels import BWD_PATHS, _fa, _value_and_grads, blocks128, take_path  # noqa: F401  (the fixture: 128 x 128 blocks)

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_flash_window")


def _case(S, hq, hk, d=16, dv=None, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    dv = dv or d
    q = jax.random.normal(keys[0], (2, hq, S, d), dtype)
    k = jax.random.normal(keys[1], (2, hk, S, d), dtype)
    v = jax.random.normal(keys[2], (2, hk, S, dv), dtype)
    w = jax.random.normal(keys[3], (2, hq, S, dv), jnp.float32)
    return q, k, v, w


def _dense(fa, window, S):
    def dense(q, k, v):
        g = q.shape[1] // k.shape[1]
        return fa._dense_attention(q, jnp.repeat(k, g, 1), jnp.repeat(v, g, 1), True,
                                   q.shape[-1] ** -0.5, S, window=window)
    return dense


class TestWindowedKernels:
    # 128-wide blocks: a window smaller than a block, of one block exactly (the
    # sweep's first block then starves its last rows), between one and two,
    # of two, and larger than the sequence (no window at all)
    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("window", [1, 50, 128, 200, 256, 1000])
    @pytest.mark.parametrize("S", [512, 400])  # 400: padding crosses the last block
    @pytest.mark.parametrize("hq,hk", [(2, 2), (7, 1)], ids=["group1", "group7"])
    def test_matches_dense_forward_and_backward(self, blocks128, monkeypatch, hq, hk, S, window, path):
        fa = blocks128
        take_path(fa, monkeypatch, path)
        q, k, v, w = _case(S, hq, hk, seed=S + window)
        before = dict(fa.path_counts)
        call = lambda q, k, v: fa.flash_attention_gqa(q, k, v, causal=True, window=window)  # noqa: E731
        got = _value_and_grads(call, w, q, k, v)
        assert fa.path_counts["pallas"] > before["pallas"] and fa.path_counts["dense"] == before["dense"]
        want = _value_and_grads(_dense(fa, window, S), w, q, k, v)
        for a, b in zip(got, want):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("d,dv", [(24, 16), (48, 32)])
    def test_keys_and_values_of_different_widths_in_bfloat16(self, blocks128, monkeypatch, d, dv, path):
        fa = blocks128
        take_path(fa, monkeypatch, path)
        q, k, v, w = _case(384, 2, 2, d=d, dv=dv, dtype=jnp.bfloat16, seed=3)
        call = lambda q, k, v: fa.flash_attention(q, k, v, causal=True, window=130)  # noqa: E731
        f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
        got = _value_and_grads(call, w, q, k, v)
        want = _value_and_grads(_dense(fa, 130, 384), w, f32(q), f32(k), f32(v))
        for a, b in zip(got, want):
            np.testing.assert_allclose(f32(a), b, atol=0.06, rtol=0.06)

    @pytest.mark.parametrize("path", BWD_PATHS)
    @pytest.mark.parametrize("hq,hk", [(2, 2), (4, 1)], ids=["group1", "group4"])
    def test_a_window_of_four_blocks(self, blocks128, monkeypatch, hq, hk, path):
        """SmallThinker's ratio of window to block (4,096 to 1,024) at
        128-row blocks: a K/V block's sweep reaches five Q blocks, the last
        four of them past its own.  A sequence of 1,024 is past the
        interpreter's gate, so the kernels are called as the entry calls
        them."""
        fa = blocks128
        take_path(fa, monkeypatch, path)
        S, window = 1024, 512
        assert fa._window_steps(S // 128, 128, 128, window) == 5
        q, k, v, w = _case(S, hq, hk, seed=21)
        flat = lambda t: t.reshape((-1,) + t.shape[2:])  # noqa: E731

        def call(q, k, v):
            out = fa._flash_gqa(flat(q), flat(k), flat(v), True, 16 ** -0.5, S, hq, hk, True, window)
            return out.reshape(q.shape)

        before = dict(fa.path_counts)
        got = _value_and_grads(call, w, q, k, v)
        assert fa.path_counts[f"bwd_{path}"] == before[f"bwd_{path}"] + 1
        want = _value_and_grads(_dense(fa, window, S), w, q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_the_last_row_of_the_first_live_block_has_no_key(self, blocks128, monkeypatch):
        """Window 128 on 128-wide blocks: a Q block's sweep starts at the K/V
        block before its own, and the block's last row (``q_lo + 127``) sees
        only keys ``> q_lo - 1``: none of that first block.  The guard keeps
        ``exp(-inf - -inf)`` out; without it such rows are NaN."""
        fa = blocks128
        starving = [(q_lo, k_lo) for q_lo in range(0, 512, 128) for k_lo in range(0, 512, 128)
                    if fa._block_kind(q_lo, k_lo, 128, 128, 512, True, 128)[0]
                    and fa._block_starves(q_lo, k_lo, 128, 128, 512, 128)]
        assert starving == [(128, 0), (256, 128), (384, 256)]
        q, k, v, w = _case(512, 2, 2, seed=11)
        call = lambda q, k, v: fa.flash_attention(q, k, v, causal=True, window=128)  # noqa: E731
        out = call(q, k, v)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(out, _dense(fa, 128, 512)(q, k, v), atol=2e-5, rtol=2e-5)
        # the same sweep with the guard taken away: the premise that block 0 comes first is gone
        monkeypatch.setattr(fa, "_block_starves", lambda *a: jnp.bool_(False))
        fa._flash_gqa_fwd_impl.clear_cache()
        assert not bool(jnp.all(jnp.isfinite(call(q, k, v))))

    def test_a_window_needs_causal_and_a_wide_one_is_none(self, blocks128):
        fa = blocks128
        q, k, v, _ = _case(256, 2, 2)
        with pytest.raises(ValueError, match="causal"):
            fa.flash_attention(q, k, v, causal=False, window=64)
        with pytest.raises(ValueError, match="at least 1"):
            fa.flash_attention_gqa(q, k[:, :1], v[:, :1], causal=True, window=0)
        assert fa._checked_window(256, True, 256) is None and fa._checked_window(255, True, 256) == 255
        np.testing.assert_array_equal(fa.flash_attention(q, k, v, causal=True, window=256),
                                      fa.flash_attention(q, k, v, causal=True))

    def test_the_dense_path_takes_the_window(self):
        """Past the interpreter's gate the dense form runs, with the same mask."""
        fa = _fa()
        q, k, v, _ = _case(640, 2, 1, d=8, seed=5)
        before = fa.path_counts["dense"]
        out = fa.flash_attention_gqa(q, k, v, causal=True, window=100)
        assert fa.path_counts["dense"] == before + 1
        s = jnp.einsum("bhqd,bkd->bhqk", q, k[:, 0]) * 8 ** -0.5
        i, j = jnp.arange(640)[:, None], jnp.arange(640)[None, :]
        p = jax.nn.softmax(jnp.where((j <= i) & (i - j < 100), s, -jnp.inf), axis=-1)
        np.testing.assert_allclose(out, jnp.einsum("bhqk,bkd->bhqd", p, v[:, 0]), atol=1e-5, rtol=1e-5)


class TestBlockKindUnderAWindow:
    """The classification that decides which body a grid step runs, and the
    sweeps that visit only what a window can reach."""

    CASES = [  # Sp, s_valid, blk_q, blk_k, window
        (512, 512, 128, 128, 128), (512, 400, 128, 128, 128), (512, 512, 128, 128, 1),
        (512, 512, 128, 128, 50), (512, 400, 128, 128, 200), (512, 512, 128, 128, 256),
        (1024, 1000, 128, 256, 300), (1024, 770, 256, 128, 129), (1024, 1024, 256, 256, 1023),
        (768, 700, 384, 128, 384), (1024, 513, 512, 256, 255), (2048, 2048, 256, 256, 1024),
    ]

    @pytest.mark.parametrize("Sp,s_valid,blk_q,blk_k,window", CASES)
    def test_against_the_mask_itself(self, Sp, s_valid, blk_q, blk_k, window):
        fa = _fa()
        rows, keys = np.arange(Sp)[:, None], np.arange(Sp)[None, :]
        mask = (keys < s_valid) & (rows >= keys) & (rows - keys < window)
        nq, nk = Sp // blk_q, Sp // blk_k
        k_steps = fa._window_steps(nq, blk_q, blk_k, window)
        q_steps = fa._window_steps(nk, blk_k, blk_q, window)
        counted = {"interior": 0, "edge": 0, "dead": 0}
        seen_by_dq, seen_by_dkv = set(), set()
        for iq in range(nq):
            first_k = int(fa._first_live_k(iq, blk_q, blk_k, window))
            for ik in range(nk):
                q_lo, k_lo = iq * blk_q, ik * blk_k
                block = mask[q_lo:q_lo + blk_q, k_lo:k_lo + blk_k]
                live, interior = fa._block_kind(q_lo, k_lo, blk_q, blk_k, s_valid, True, window)
                assert bool(interior) == bool(block.all()), (iq, ik)
                assert bool(live) == bool(block.any()), (iq, ik)
                # a row of a live block that has met no key in it or before it
                met = mask[q_lo:q_lo + blk_q, :k_lo + blk_k].any(axis=1)
                if live:
                    assert bool(fa._block_starves(q_lo, k_lo, blk_q, blk_k, s_valid, window)) == (not met.all())
                # the forward and dq sweeps of Q block iq reach every live block
                if live:
                    assert first_k <= ik < first_k + k_steps, (iq, ik)
                    seen_by_dq.add((iq, ik))
                    first_q = int(fa._first_live_q(ik, blk_q, blk_k, True))
                    assert first_q <= iq < first_q + q_steps and iq <= int(
                        fa._last_live_q(ik, blk_q, blk_k, nq, window)), (iq, ik)
                    seen_by_dkv.add((iq, ik))
            for ik in range(first_k, first_k + k_steps):
                live, interior = fa._block_kind(iq * blk_q, ik * blk_k, blk_q, blk_k, s_valid, True, window)
                counted["interior" if interior else "edge" if live else "dead"] += 1
                assert not live or ik < nk  # a step past the last K/V block is dead
        assert seen_by_dq == seen_by_dkv == {(i, j) for i in range(nq) for j in range(nk)
                                             if mask[i * blk_q:(i + 1) * blk_q, j * blk_k:(j + 1) * blk_k].any()}
        assert fa._block_census(Sp, s_valid, blk_q, blk_k, True, window) == counted
        assert sum(counted.values()) == nq * k_steps <= nq * nk

    def test_the_training_cell(self):
        fa = _fa()
        # smallthinker_21b_a3b_train_1x16k: S = 16,384, heads of 128 in bfloat16, window 4,096
        assert fa._block_shape(16384, 128, 2) == (1024, 1024)
        assert fa._window_steps(16, 1024, 1024, 4096) == 5  # window / blk_k + 1, not 16
        assert fa._block_census(16384, 16384, 1024, 1024, True, 4096) == {
            "interior": 42, "edge": 28, "dead": 10}
        assert fa._block_census(16384, 16384, 1024, 1024, True) == {
            "interior": 120, "edge": 16, "dead": 120}
        # one block a Q block is guarded: the one the window's lower edge crosses
        guarded = [(iq, ik) for iq in range(16) for ik in range(16)
                   if fa._block_kind(iq * 1024, ik * 1024, 1024, 1024, 16384, True, 4096)[0]
                   and fa._block_starves(iq * 1024, ik * 1024, 1024, 1024, 16384, 4096)]
        assert guarded == [(iq, iq - 4) for iq in range(4, 16)]


def _jaxpr_text(fa, call, shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: jnp.sum(call(q, k, v, causal=True, **kw)), (0, 1, 2)))(*args))
    return re.sub(r" at 0x[0-9a-f]+", "", re.sub(r"/[^\s:]+\.py:\d+", "FILE", text))


# the attention calls of the two accepted model cells at their tests' toy shapes, and
# at four blocks a side (where the clamped index maps and the three kinds of block show)
JAXPR_CASES = {
    "lfm2_toy": ("flash_attention_gqa", [(2, 4, 32, 16), (2, 2, 32, 16), (2, 2, 32, 16)], {}),
    "kimi_toy": ("flash_attention", [(2, 2, 32, 24), (2, 2, 32, 24), (2, 2, 32, 16)], {"scale": 24 ** -0.5}),
    "lfm2_blocks": ("flash_attention_gqa", [(1, 4, 400, 16), (1, 2, 400, 16), (1, 2, 400, 16)], {}),
    "kimi_blocks": ("flash_attention", [(1, 2, 512, 24), (1, 2, 512, 24), (1, 2, 512, 16)], {"scale": 24 ** -0.5}),
}


@pytest.mark.parametrize("name", list(JAXPR_CASES))
def test_without_a_window_the_kernels_lower_as_before(blocks128, name):
    """``window=None`` is a static branch that builds the kernels as they were
    at 57d3c79, instruction for instruction: the fixtures are the jaxprs of
    value and gradients that commit traced (source lines and addresses
    stripped), and the two cells that run these kernels must not move.  They
    also hold the forward's two residuals' names (``KEPT``: a ``name``
    equation each, which lowers to nothing) and the fused backward, one
    kernel where the two sweeps were; with those names and with the two
    sweeps (``_fused_bwd_fits`` False) the jaxprs were 57d3c79's to the
    character when the fixtures were recorded again."""
    fa = blocks128
    call, shapes, kw = JAXPR_CASES[name]
    with gzip.open(os.path.join(FIXTURES, f"{name}.jaxpr.txt.gz"), "rt") as f:
        before = f.read()
    assert _jaxpr_text(fa, getattr(fa, call), shapes, **kw) == before
    # and a window is another program
    assert _jaxpr_text(fa, getattr(fa, call), shapes, window=8, **kw) != before


def _lowers_for_the_tpu(call, *avals, kernels):
    """Pallas -> Mosaic lowering from the CPU host (``jax.export``), where block
    shapes and index maps are validated: the forward and the backward's
    kernels (one fused sweep, or the two)."""
    def value_and_grads(q, k, v):
        return jax.value_and_grad(lambda a, b, c: jnp.sum(call(a, b, c).astype(jnp.float32)), (0, 1, 2))(q, k, v)

    exported = jax.export.export(jax.jit(value_and_grads), platforms=["tpu"])(*avals)
    assert exported.mlir_module().count("tpu_custom_call") == kernels


@pytest.mark.parametrize("path", BWD_PATHS)
@pytest.mark.parametrize("S,window,dtype", [
    (16384, 4096, jnp.bfloat16),   # smallthinker_21b_a3b_train_1x16k: 28 query heads over 4, heads of 128
    (16384 - 24, 4096, jnp.bfloat16),  # the same with pad keys in the last block
    (2048, 700, jnp.float32),      # a window that no block divides
])
def test_windowed_kernels_lower_for_the_tpu(monkeypatch, S, window, dtype, path):
    fa = _fa()
    take_path(fa, monkeypatch, path)
    hq, hk, d = 28, 4, 128
    padded = -(-S // 1024) * 1024
    q = jax.ShapeDtypeStruct((hq, padded, d), dtype)
    kv = jax.ShapeDtypeStruct((hk, padded, d), dtype)
    _lowers_for_the_tpu(lambda a, b, c: fa._flash_gqa(a, b, c, True, d ** -0.5, S, hq, hk, False, window), q, kv, kv,
                        kernels=2 if path == "fused" else 3)
