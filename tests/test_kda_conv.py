"""``conv_silu_heads``: Kimi Delta Attention's convolution, SiLU and L2 norms
as one operator.  The kernels (under the interpreter here) against the dense
executor, the dense executor against autodiff of the expression the layer
had before, ragged lengths, causality, and the gate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.ops import short_conv as sc
from heat_tpu.ops.short_conv import conv_silu_heads

L = 4


def before_pr37(qkv, taps, heads):
    """``KimiDeltaAttention._qkv`` as it was: what autodiff is taken of."""
    b, s, cols = qkv.shape
    d = cols // 3 // heads
    mixed = jax.nn.silu(sc._conv(qkv.astype(jnp.float32), taps.astype(jnp.float32)))
    q, k, v = (t.reshape(b, s, heads, d).transpose(0, 2, 1, 3) for t in jnp.split(mixed, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    return tuple(t.astype(qkv.dtype) for t in (q, k, v))


def inputs(batch, length, heads, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 5)
    qkv = jax.random.normal(ks[0], (batch, length, 3 * heads * d)).astype(dtype)
    taps = jax.random.uniform(ks[1], (3 * heads * d, L), minval=-0.5, maxval=0.5)
    return qkv, taps, [jax.random.normal(k, (batch, heads, length, d)) for k in ks[2:]]


def both(fn, qkv, taps, weights):
    """``(fn(qkv, taps), its gradients by qkv and taps under fixed cotangents)``, one program."""
    def scalar(qkv, taps):
        out = fn(qkv, taps)
        return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(out, weights)), out

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, (0, 1), has_aux=True))(qkv, taps)
    return out, grads


def close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=tol * max(float(np.abs(want).max()), 1e-6), rtol=0)


def counted(fn):
    before = dict(sc.path_counts)
    out = fn()
    return out, {n: sc.path_counts[n] - before[n] for n in before}


@pytest.fixture
def tiles_of_32(monkeypatch):
    """Tiles of 32 rows in pieces of 16, so that a test-sized sequence has a
    first, an inner and a last tile, and a tile a loop of pieces."""
    monkeypatch.setattr(sc, "_TILE", 32)
    monkeypatch.setattr(sc, "_SUB", 16)
    sc._heads_call.clear_cache()
    sc._pull_call.clear_cache()
    yield
    sc._heads_call.clear_cache()
    sc._pull_call.clear_cache()


# (batch, length, heads, head width): one tile; three tiles of two heads a block; a ragged
# length; heads of two lane tiles; 48 rows a tile: a loop of one piece and a rest
SHAPES = {"one_tile": (2, 32, 2, 128), "three_tiles": (2, 96, 4, 128), "ragged": (1, 75, 2, 128),
          "wide_heads": (2, 64, 2, 256), "rest_of_a_tile": (1, 48, 1, 128),
          "toy_heads": (2, 40, 2, 16), "ragged_two_heads": (2, 40, 2, 128)}


WHAT = ("q", "k", "v", "d_qkv", "d_taps")
_PAIRS = {}


def pairs(key, reference, path):
    """``{what: (the operator's, the reference's)}`` for ``SHAPES[key]``, made
    once for the five cases that read it."""
    if key not in _PAIRS:
        _, _, heads, d = SHAPES[key]
        qkv, taps, weights = inputs(*SHAPES[key])
        (got, d_got), counts = counted(lambda: both(lambda a, b: conv_silu_heads(a, b, heads), qkv, taps, weights))
        assert counts == {"pallas": 0, "dense": 0, path: 1}
        want, d_want = both(lambda a, b: reference(a, b, heads, d), qkv, taps, weights)
        _PAIRS[key] = dict(zip(WHAT, zip((*got, *d_got), (*want, *d_want))))
    return _PAIRS[key]


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("shape", ["one_tile", "three_tiles", "ragged", "wide_heads", "rest_of_a_tile"])
def test_the_kernels_are_the_dense_executor(shape, what, tiles_of_32, monkeypatch):
    if shape == "rest_of_a_tile":
        monkeypatch.setattr(sc, "_TILE", 512)
        monkeypatch.setattr(sc, "_SUB", 32)
    dense = lambda a, b, heads, d: sc._dense_heads(a, b, heads, (True, True, False), (d ** -0.5, 1, 1), 1e-6)  # noqa: E731
    close(*pairs(shape, dense, "pallas")[what], 1e-5)


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("shape, path", [("toy_heads", "dense"), ("ragged_two_heads", "pallas")])
def test_the_operator_is_autodiff_of_the_expression_it_replaced(shape, path, what, tiles_of_32):
    """Both executors, outputs and gradients, against ``jax.grad`` of the
    layer's old body: the dense executor's backward is ``jax.vjp`` of the
    same expression, the kernels' is written out."""
    close(*pairs(shape, lambda a, b, heads, d: before_pr37(a, b, heads), path)[what], 1e-5)


@pytest.mark.parametrize("d, path", [(16, "dense"), (128, "pallas")])
def test_bfloat16_stays_near_float32(d, path, tiles_of_32):
    """One rounding at the end: a bfloat16 ``qkv`` gives bfloat16 heads and a
    bfloat16 cotangent within a bfloat16 step or two of float32's, and the
    taps' cotangent float32."""
    qkv, taps, weights = inputs(2, 64, 2, d)
    want, d_want = both(lambda a, b: before_pr37(a, b, 2), qkv.astype(jnp.bfloat16).astype(jnp.float32), taps, weights)
    (got, d_got), counts = counted(lambda: both(lambda a, b: conv_silu_heads(a, b, 2), qkv.astype(jnp.bfloat16), taps, weights))
    assert counts[path] == 1
    assert all(t.dtype == jnp.bfloat16 for t in (*got, d_got[0])) and d_got[1].dtype == jnp.float32
    for g, w in zip((*got, *d_got), (*want, *d_want)):
        close(g.astype(jnp.float32), w, 2e-2)


@pytest.mark.parametrize("normalise, scale", [((False, True, True), (1, 0.5, 2.0)), ((True,), (3.0,)), ((False, False), (1, 1))],
                         ids=["three", "one", "two_plain"])
def test_sections_norms_and_scales_are_the_callers(normalise, scale, tiles_of_32):
    n = len(normalise)
    ks = jax.random.split(jax.random.key(1), 3)
    qkv = jax.random.normal(ks[0], (2, 64, n * 2 * 128))
    taps = jax.random.uniform(ks[1], (n * 2 * 128, 3), minval=-0.5, maxval=0.5)  # three taps
    weights = [jax.random.normal(ks[2], (2, 2, 64, 128))] * n
    op = lambda a, b: conv_silu_heads(a, b, 2, normalise=normalise, scale=scale, eps=1e-3)  # noqa: E731
    (got, d_got), counts = counted(lambda: both(op, qkv, taps, weights))
    assert counts == {"pallas": 1, "dense": 0} and len(got) == n
    want, d_want = both(lambda a, b: sc._dense_heads(a, b, 2, normalise, scale, 1e-3), qkv, taps, weights)
    for g, w in zip((*got, *d_got), (*want, *d_want)):
        close(g, w, 1e-5)
    silu = jax.nn.silu(sc._conv(qkv, taps))[:, :, :128]  # section 0, head 0
    first = silu * (jax.lax.rsqrt(jnp.sum(silu * silu, -1, keepdims=True) + 1e-3) if normalise[0] else 1) * scale[0]
    close(got[0][:, 0], first, 1e-5)


@pytest.mark.parametrize("d", [16, 128], ids=["dense", "pallas"])
def test_causal_and_nothing_crosses_sequences(d, tiles_of_32):
    qkv, taps, _ = inputs(2, 80, 2, d)
    op = jax.jit(lambda a: conv_silu_heads(a, taps, 2))
    base = op(qkv)
    for t in (0, 31, 32, 50):  # the first position, either side of a tile's edge, inside a tile
        moved = op(qkv.at[0, t].add(1.0))
        for b, m in zip(base, moved):
            np.testing.assert_array_equal(m[0, :, :t], b[0, :, :t])
            np.testing.assert_array_equal(m[1], b[1])
            assert float(jnp.abs(m[0, :, t:t + L] - b[0, :, t:t + L]).max()) > 0
            np.testing.assert_array_equal(m[0, :, t + L:], b[0, :, t + L:])  # four taps see four positions
    alone = op(qkv[1:])
    for b, a in zip(base, alone):
        np.testing.assert_array_equal(a[0], b[1])
    # the first L - 1 positions see zeros before them: position 0 is its own tap alone
    v0 = jax.nn.silu(qkv[:, 0, 2 * 2 * d:] * taps[2 * 2 * d:, L - 1]).reshape(2, 2, d)
    close(base[2][:, :, 0], v0, 1e-6)


def test_the_gate_reads_platform_and_shapes(monkeypatch):
    """The platform of the data and the shapes and nothing else: ``(rows,
    heads)`` of a grid step, or None for the dense executor."""
    probe = lambda batch, length, heads, d: jax.ShapeDtypeStruct((batch, length, 3 * heads * d), jnp.bfloat16)  # noqa: E731
    assert sc._pallas_gate(probe(2, 512, 32, 128), 32, 3) == (512, 4)
    assert sc._pallas_gate(probe(2, 75, 2, 128), 2, 3) == (80, 2)  # padded to whole halo blocks
    assert sc._pallas_gate(probe(2, 64, 6, 256), 6, 3) == (64, 2)  # half as many of heads twice as wide
    assert sc._pallas_gate(probe(2, 64, 3, 128), 3, 3) == (64, 3)  # heads a block divide the heads
    assert sc._pallas_gate(probe(2, 64, 2, 1024), 2, 3) == (64, 1)
    assert sc._pallas_gate(probe(2, 8192, 32, 128), 32, 3) is None  # the interpreter, at test scale only
    for d in (16, 64, 192):
        assert sc._pallas_gate(probe(2, 64, 2, d), 2, 3) is None
    assert sc._pallas_gate(probe(2, 64, 2, 128), 2, 3, n_taps=17) and sc._pallas_gate(probe(2, 64, 2, 128), 2, 3, n_taps=18) is None
    monkeypatch.setattr(sc, "platform_of", lambda q: "tpu")
    monkeypatch.setattr(sc, "_kernel_mesh", lambda q: None)
    assert sc._pallas_gate(probe(2, 8192, 32, 128), 32, 3) == (512, 4)
    four = type("Mesh", (), {"size": 4})()
    monkeypatch.setattr(sc, "_kernel_mesh", lambda q: four)
    assert sc._pallas_gate(probe(8, 8192, 32, 128), 32, 3) == (512, 4)
    assert sc._pallas_gate(probe(2, 8192, 32, 128), 32, 3) is None  # two sequences do not divide among four chips
    monkeypatch.setattr(sc, "platform_of", lambda q: "gpu")
    monkeypatch.setattr(sc, "_kernel_mesh", lambda q: None)
    assert sc._pallas_gate(probe(2, 64, 2, 128), 2, 3) is None
    qkv, taps, _ = inputs(2, 64, 2, 128)
    _, counts = counted(lambda: conv_silu_heads(qkv, taps, 2))
    assert counts == {"pallas": 0, "dense": 1}


def test_only_the_inputs_are_kept_for_the_backward():
    """The forward's residuals are ``qkv`` and ``taps`` whichever executor
    runs: nothing of the float32 intermediates is live between the passes."""
    for d in (16, 128):
        qkv, taps, _ = inputs(1, 32, 2, d)
        _, pull = jax.vjp(lambda a, b: conv_silu_heads(a, b, 2), qkv, taps)
        kept = [t for t in jax.tree.leaves(pull) if hasattr(t, "shape")]
        assert sorted(t.shape for t in kept) == sorted([qkv.shape, taps.shape])
