"""Every ``pallas_call`` in ``heat_tpu/ops/`` lowers for the TPU, checked from
the CPU host through ``jax.export`` (Pallas -> Mosaic lowering, where block
shapes are validated; no chip and no Mosaic compile needed), and a kernel
that was selected raises instead of quietly returning the reference.

The lowering half fails at the parent of PR 21: the logsumexp carrier was a
``(B, S)`` array blocked ``(1, blk)``, which Mosaic refuses once B > 1.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest

fa = importlib.import_module("heat_tpu.ops.flash_attention")

B, S, D = 6, 1024, 64  # batch x heads > 1; two 512-blocks per sequence


def _lowers(fn, *avals):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    assert "tpu_custom_call" in exported.mlir_module()


def _vg(kernel_call):
    """forward + both backward sweeps of one flash variant as one function"""
    def f(q, k, v, *rest):
        def loss(a, b, c):
            out = kernel_call(a, b, c, *rest)
            out = out if isinstance(out, tuple) else (out,)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in out)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    return f


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
class TestFlashLowers:
    def test_static(self, dtype):
        q = jax.ShapeDtypeStruct((B, S, D), dtype)
        _lowers(_vg(lambda a, b, c: fa._flash(a, b, c, True, D**-0.5, S - 24, False)),
                q, q, q)

    def test_gqa(self, dtype):
        q = jax.ShapeDtypeStruct((B, S, D), dtype)
        kv = jax.ShapeDtypeStruct((B // 3, S, D), dtype)
        _lowers(_vg(lambda a, b, c: fa._flash_gqa(a, b, c, True, D**-0.5, S, 3, 1, False)),
                q, kv, kv)

    def test_positions(self, dtype):
        q = jax.ShapeDtypeStruct((B, S, D), dtype)
        kv = jax.ShapeDtypeStruct((B, S // 2, D), dtype)  # rectangular block
        qpos = jax.ShapeDtypeStruct((S, 1), jnp.int32)
        kpos = jax.ShapeDtypeStruct((1, S // 2), jnp.int32)
        _lowers(_vg(lambda a, b, c, qp, kp: fa._flash_pos(
            a, b, c, qp, kp, True, D**-0.5, S // 2, True, False)), q, kv, kv, qpos, kpos)


def test_gqa_lowers_at_the_training_cell_shape():
    """``lfm2_8b_a1b_train_4x8k``: 4 sequences x 32 query heads over 8 K/V
    heads, S = 8192, d = 64, causal bfloat16, at the block shape
    ``_block_shape`` gives that shape: forward and both backward sweeps."""
    S, d, hq, hk = 8192, 64, 32, 8
    assert fa._block_shape(S, d, 2) == (1024, 1024)
    q = jax.ShapeDtypeStruct((4 * hq, S, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4 * hk, S, d), jnp.bfloat16)
    _lowers(_vg(lambda a, b, c: fa._flash_gqa(a, b, c, True, d**-0.5, S, hq, hk, False)),
            q, kv, kv)


@pytest.mark.parametrize("B,hq,hk,S,d,dv,window", [
    (1, 32, 4, 32768, 128, 128, None),  # trinity_mini_26b_a3b_train_1x32k, the global layer
    (1, 32, 4, 32768, 128, 128, 2048),  # and its windowed layers
    (4, 16, 16, 8192, 192, 128, None),  # moonlight_16b_a3b_train_4x8k: latent attention, 192-wide keys
], ids=["trinity_global", "trinity_window", "moonlight"])
def test_fused_backward_lowers_at_the_training_cell_shapes(B, hq, hk, S, d, dv, window):
    """The gradient's one fused kernel at the cells' shapes, bfloat16 and
    causal: the forward and the backward are two Mosaic calls, and a head's
    whole dQ, dK and dV are held in VMEM (48 MiB of float32 at Trinity's)."""
    assert fa._fused_bwd_fits(S, d, dv, 2)
    q = jax.ShapeDtypeStruct((B * hq, S, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B * hk, S, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B * hk, S, dv), jnp.bfloat16)
    w = () if window is None else (window,)
    before = fa.path_counts["bwd_fused"]
    exported = jax.export.export(jax.jit(_vg(
        lambda a, b, c: fa._flash_gqa(a, b, c, True, d**-0.5, S, hq, hk, False, *w))), platforms=["tpu"])(q, k, v)
    assert exported.mlir_module().count("tpu_custom_call") == 2
    assert fa.path_counts["bwd_fused"] == before + 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_kernels_lower_at_the_training_cell_shape(monkeypatch, dtype):
    """``kimi_linear_48b_a3b_train_2x8k``: 2 sequences x 32 heads of 128,
    S = 8192, chunk 64, a float32 decay: the forward kernel (with and without
    ``T`` among its results) and the backward kernel, as ``chunk_kda``'s
    ``custom_vjp`` calls them."""
    kda = importlib.import_module("heat_tpu.ops.kda")
    monkeypatch.setattr(kda, "platform_of", lambda q: "tpu")  # the kernels, not their interpreter
    wide = jax.ShapeDtypeStruct((2, 32, 8192, 128), dtype)
    tile = kda._pallas_gate(wide, wide, 64)
    assert tile == 8 and kda._backward_tile(tile, 64, 128) == 4

    def f(q, k, v, g, beta):
        def loss(*a):
            o, final = kda._chunk_kda(*a, 64, tile)
            return jnp.sum(o.astype(jnp.float32)) + jnp.sum(final)

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(
        wide, wide, wide, jax.ShapeDtypeStruct(wide.shape, jnp.float32),
        jax.ShapeDtypeStruct(wide.shape[:-1], jnp.float32))
    assert exported.mlir_module().count("tpu_custom_call") >= 3


def test_sparse_attention_kernels_lower_at_the_training_cell_shape(monkeypatch):
    """``minicpm_sala_9b_train_1x32k``: one sequence, 16 query heads over one
    key/value head of 128, S = 32,768, bfloat16, the selection as
    ``sparse_attention`` makes it: the forward kernel and the one-sweep
    backward kernel, with the unions' lengths as scalar-prefetch operands."""
    sa = importlib.import_module("heat_tpu.ops.sparse_attention")
    monkeypatch.setattr(sa, "platform_of", lambda q: "tpu")  # the kernels, not their interpreter
    q = jax.ShapeDtypeStruct((1, 16, 32768, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.bfloat16)
    before = dict(sa.path_counts)

    def f(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(sa.sparse_attention(*a)[0].astype(jnp.float32)),
                                  argnums=(0, 1, 2))(q, k, v)

    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(q, kv, kv)
    assert exported.mlir_module().count("tpu_custom_call") == 2
    assert sa.path_counts["pallas"] == before["pallas"] + 1


def test_lightning_kernels_lower_at_the_training_cell_shape(monkeypatch):
    """The same cell's lightning layers: 16 heads of 128, S = 32,768, chunk
    128, bfloat16: the chunk-local forward and backward kernels (the state's
    scan is XLA's)."""
    la = importlib.import_module("heat_tpu.ops.lightning")
    monkeypatch.setattr(la, "platform_of", lambda q: "tpu")
    wide = jax.ShapeDtypeStruct((1, 16, 32768, 128), jnp.bfloat16)
    assert la._pallas_gate(wide, wide, 128) == 4

    def f(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(la.lightning_attention(
            *a, -jnp.linspace(0.01, 0.8, 16)).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(wide, wide, wide)
    assert exported.mlir_module().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_convolution_kernels_lower_at_the_training_cell_shape(monkeypatch, dtype):
    """``kimi_linear_48b_a3b_train_2x8k``: a layer's ``qkv`` (2, 8192, 3 x 32
    x 128) through ``conv_silu_heads``' forward and backward kernels, a
    section each, as its ``custom_vjp`` calls them."""
    sc = importlib.import_module("heat_tpu.ops.short_conv")
    monkeypatch.setattr(sc, "platform_of", lambda q: "tpu")  # the kernels, not their interpreter
    monkeypatch.setattr(sc, "_kernel_mesh", lambda q: None)
    qkv = jax.ShapeDtypeStruct((2, 8192, 3 * 32 * 128), dtype)
    tile = sc._pallas_gate(qkv, 32, 3)
    assert tile == (512, 4)

    def f(qkv, taps):
        def loss(*a):
            heads = sc._conv_silu_heads(*a, 32, (True, True, False), (128**-0.5, 1, 1), 1e-6, tile)
            return sum(jnp.sum(t.astype(jnp.float32)) for t in heads)

        return jax.value_and_grad(loss, argnums=(0, 1))(qkv, taps)

    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(qkv, jax.ShapeDtypeStruct((3 * 32 * 128, 4), jnp.float32))
    assert exported.mlir_module().count("tpu_custom_call") >= 6


@pytest.mark.parametrize("cell, heads, length, norm, rope", [
    ("trinity_mini_26b_a3b_train_1x32k", 32, 32768, True, True),
    ("trinity_mini_26b_a3b_train_1x32k/global", 32, 32768, True, False),
    ("smallthinker_21b_a3b_train_1x16k", 28, 16384, False, True),
    ("smallthinker_21b_a3b_train_1x16k/global", 28, 16384, False, False)])
def test_position_heads_kernels_lower_at_the_training_cell_shape(monkeypatch, cell, heads, length, norm, rope):
    """A layer's packed projection (1, S, (H + 2 x 4) x 128) bfloat16 through
    ``position_heads``' forward and backward kernels, as its ``custom_vjp``
    calls them: Trinity's 32 query heads with QK norm, rotated on its
    windowed layers, and SmallThinker's 28 without the norm."""
    ph = importlib.import_module("heat_tpu.ops.position_heads")
    monkeypatch.setattr(ph, "platform_of", lambda q: "tpu")  # the kernels, not their interpreter
    monkeypatch.setattr(ph, "_kernel_mesh", lambda q: None)
    proj = jax.ShapeDtypeStruct((1, length, (heads + 8) * 128), jnp.bfloat16)
    tile = ph._pallas_gate(proj, heads, 4, True)
    assert tile == 512
    norms = (jax.ShapeDtypeStruct((128,), jnp.bfloat16),) * 2 if norm else ()

    def f(proj, *norms):
        def loss(proj, norms):
            out = ph._position_heads(proj, norms, heads, 4, 1e-5, 10000.0 if rope else None, tile, False)
            return sum(jnp.sum(t.astype(jnp.float32)) for t in out)

        return jax.value_and_grad(loss, argnums=(0, 1))(proj, norms)

    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(proj, *norms)
    assert exported.mlir_module().count("tpu_custom_call") >= 2


def _gqa_case():
    import numpy as np

    S, d = 40, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 6, S, d)), jnp.float32)  # 6 rows / 3 rows:
    k, v = (jnp.asarray(rng.normal(size=(1, 3, S, d)), jnp.float32)  # not multiples of 8
            for _ in range(2))

    def dense(q, k, v):
        g = q.shape[1] // k.shape[1]
        return fa._dense_attention(q, jnp.repeat(k, g, 1), jnp.repeat(v, g, 1),
                                   True, d**-0.5, S)

    def close(got, want, what):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                       atol=2e-5, err_msg=what)

    vg = lambda f: jax.value_and_grad(lambda *o: jnp.sum(f(*o) ** 2), argnums=(0, 1, 2))
    return q, k, v, dense, close, vg


def test_per_shard_rows_pad_in_step(monkeypatch):
    """On more than one chip the kernels run per shard of the batch·heads
    axis (``_kernel_mesh``: a Mosaic kernel cannot be auto-partitioned).
    The dispatch tail alone, over the CPU mesh, with a jnp stand-in for the
    kernel that uses the kernel's row mapping (query row b reads K/V row
    b // g, per shard): Q and K/V rows pad in step and the output comes
    back in order and unpadded (forward only; gradients in the slow test)."""
    from heat_tpu.core.devices import get_default_mesh

    q, k, v, dense, close, vg = _gqa_case()
    mesh = get_default_mesh()
    monkeypatch.setattr(fa, "_kernel_mesh", lambda q: mesh)

    def stand_in(qf, kf, vf, causal, scale, s_valid, hq, hk, interpret):
        assert qf.shape[0] == (hq // hk) * kf.shape[0]  # whole groups per shard
        grouped = qf.reshape(kf.shape[0], hq // hk, *qf.shape[1:])
        out = fa._dense_attention(grouped, kf[:, None], vf[:, None], causal, scale, s_valid)
        return out.reshape(qf.shape)

    monkeypatch.setattr(fa, "_flash_gqa", stand_in)
    close(fa.flash_attention_gqa(q, k, v, causal=True), dense(q, k, v), "gqa dispatch tail")


@pytest.mark.slow
def test_per_shard_kernels_match_dense(monkeypatch):
    """The same, with the real kernels in interpret mode: GQA forward and
    backward, and the positions block."""
    from heat_tpu.core.devices import get_default_mesh

    q, k, v, dense, close, vg = _gqa_case()
    mesh = get_default_mesh()
    monkeypatch.setattr(fa, "_kernel_mesh", lambda q: mesh)
    close(vg(lambda a, b, c: fa.flash_attention_gqa(a, b, c, causal=True))(q, k, v),
          vg(dense)(q, k, v), "gqa")
    S, d = q.shape[-2:]
    pos = jnp.arange(S, dtype=jnp.int32)
    out, lse = fa.flash_attention_block(q, q[:, ::-1], q * 0.5, pos, pos, causal=True,
                                        scale=d**-0.5, s_valid=S, impl="interpret")
    assert lse.shape == q.shape[:-1]
    close(out, dense(q, q[:, ::-1], q * 0.5), "block")


def test_kda_kernels_per_shard_match_the_xla_form(monkeypatch):
    """``chunk_kda``'s kernels (the interpreter here) a shard of the sequences
    a device of the CPU mesh, as across chips: forward and five gradients
    against the XLA form of the chunk-local part."""
    import numpy as np

    from heat_tpu.core.devices import get_default_mesh

    kda = importlib.import_module("heat_tpu.ops.kda")
    mesh = get_default_mesh()
    monkeypatch.setattr(kda, "_kernel_mesh", lambda q: mesh)
    keys = jax.random.split(jax.random.key(0), 5)
    shape = (mesh.size, 128, 128)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    args = (unit(jax.random.normal(keys[0], shape)) * 128**-0.5, unit(jax.random.normal(keys[1], shape)),
            jax.random.normal(keys[2], shape), -jax.random.uniform(keys[3], shape, minval=1e-3, maxval=0.5),
            jax.nn.sigmoid(jax.random.normal(keys[4], shape[:2])))
    assert kda._pallas_gate(args[0], args[2], 64) == 2

    def both(tile):
        def loss(*a):
            o, final = kda._chunk_kda(*a, 64, tile)
            return jnp.sum(jnp.sin(o)) + jnp.sum(final), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)

    with jax.default_matmul_precision("highest"):
        ((_, got), d_got), ((_, want), d_want) = both(2), both(0)
    for a, b in zip((got, *d_got), (want, *d_want)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=0)


def test_kda_convolution_kernels_per_shard_match_the_dense_executor(monkeypatch):
    """``conv_silu_heads``' kernels (the interpreter here) a shard of the
    sequences a device of the CPU mesh, as across chips: the heads and both
    gradients against the dense executor."""
    import numpy as np

    from heat_tpu.core.devices import get_default_mesh

    sc = importlib.import_module("heat_tpu.ops.short_conv")
    mesh = get_default_mesh()
    monkeypatch.setattr(sc, "_kernel_mesh", lambda q: mesh)
    keys = jax.random.split(jax.random.key(0), 2)
    qkv = jax.random.normal(keys[0], (mesh.size, 48, 3 * 2 * 128))
    taps = jax.random.uniform(keys[1], (3 * 2 * 128, 4), minval=-0.5, maxval=0.5)
    tile = sc._pallas_gate(qkv, 2, 3)
    assert tile == (48, 2) and sc._pallas_gate(qkv[1:], 2, 3) is None  # a sequence fewer does not divide

    def both(tile):
        def loss(*a):
            heads = sc._conv_silu_heads(*a, 2, (True, True, False), (128**-0.5, 1, 1), 1e-6, tile)
            return sum(jnp.sum(jnp.sin(t)) for t in heads), heads

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(qkv, taps)

    ((_, got), d_got), ((_, want), d_want) = both(tile), both(None)
    for a, b in zip((*got, *d_got), (*want, *d_want)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=0)


def test_position_heads_kernels_per_shard_match_the_composition(monkeypatch):
    """``position_heads``' kernels (the interpreter here) a shard of the
    sequences a device of the CPU mesh, as across chips: the heads, ``d proj``
    and both norms' weights' cotangents against the composition."""
    import numpy as np

    from heat_tpu.core.devices import get_default_mesh
    from heat_tpu.nn.attention import MultiheadAttention

    ph = importlib.import_module("heat_tpu.ops.position_heads")
    mesh = get_default_mesh()
    monkeypatch.setattr(ph, "_kernel_mesh", lambda q: mesh)
    op = MultiheadAttention(256, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=2, head_dim=128,
                            qk_norm=True)
    params = op.init(jax.random.key(0))
    proj = jax.random.normal(jax.random.key(1), (mesh.size, 48, 8 * 128))
    norms = (params["q_norm"]["weight"], params["k_norm"]["weight"])
    assert ph._pallas_gate(proj, 4, 2, True) == 48 and ph._pallas_gate(proj[1:], 4, 2, True) is None

    def both(fused):
        def loss(proj, norms):
            p = {**params, "q_norm": {"weight": norms[0]}, "k_norm": {"weight": norms[1]}}
            dense = lambda t: op._self_heads(p, t)  # noqa: E731
            heads = ph.position_heads(proj, 4, 2, dense, norms=norms, rope_base=op.rope_base) if fused else dense(proj)
            return sum(jnp.sum(jnp.sin(t)) for t in heads), heads

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(proj, norms)

    ((_, got), d_got), ((_, want), d_want) = both(True), both(False)
    for a, b in zip(jax.tree.leaves((got, d_got)), jax.tree.leaves((want, d_want))):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=0)


class TestNoQuietFallback:
    """On the platform the gate selected the kernel for, a kernel failure is
    the caller's failure: no ``except`` turns it into the dense/jnp result."""

    @staticmethod
    def _boom(*a, **k):
        raise RuntimeError("kernel refused")

    def test_flash_raises(self, monkeypatch):
        q = jnp.ones((2, 2, 64, 8), jnp.float32)
        monkeypatch.setattr(fa, "_flash", self._boom)
        monkeypatch.setattr(fa, "_flash_gqa", self._boom)
        dense = fa.path_counts["dense"]
        with pytest.raises(RuntimeError, match="kernel refused"):
            fa.flash_attention(q, q, q, causal=True)
        with pytest.raises(RuntimeError, match="kernel refused"):
            fa.flash_attention_gqa(q, q[:, :1], q[:, :1], causal=True)
        assert fa.path_counts["dense"] == dense

    def test_position_heads_raises(self, monkeypatch):
        ph = importlib.import_module("heat_tpu.ops.position_heads")
        monkeypatch.setattr(ph, "_heads_call", self._boom)
        proj = jnp.ones((1, 32, 4 * 128), jnp.float32)
        dense = ph.path_counts["dense"]
        with pytest.raises(RuntimeError, match="kernel refused"):
            ph.position_heads(proj, 2, 1, lambda p: (p, p, p), rope_base=10000.0)
        assert ph.path_counts["dense"] == dense
