"""``position_heads``: attention's query, key and value heads from the packed
projection, normalised and rotated, in one pass each way.  The kernels (under
the interpreter here) against the composition they stand for
(``MultiheadAttention._self_heads``: split, transpose, ``rms_normalize``,
``apply_rope``), forward and every cotangent; the gate and its tiles; one
rounding in bfloat16; and the attention module through the kernels against
its masked dense path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.nn.attention import MultiheadAttention
from heat_tpu.ops import position_heads as ph

# what a layer does to its query and key heads: (QK norm, rotation)
VARIANTS = {"norm_rope": (True, True), "rope": (False, True), "norm": (True, False), "neither": (False, False)}
# query heads, key/value heads: 8 and 7 query heads a key/value head
GROUPS = {"eight_a_group": (16, 2), "seven_a_group": (7, 1)}
BATCH, LENGTH, D = 2, 75, 128  # 75 rows: three tiles of 32, the last ragged
WHAT = ("q", "k", "v", "d_proj", "d_norms")


@pytest.fixture
def tiles_of_32(monkeypatch):
    """Tiles of 32 rows in pieces of 16, so that a test-sized sequence has
    three tiles and a tile a loop of pieces."""
    monkeypatch.setattr(ph, "_TILE", 32)
    monkeypatch.setattr(ph, "_SUB", 16)
    ph._heads_call.clear_cache()
    ph._pull_call.clear_cache()
    yield
    ph._heads_call.clear_cache()
    ph._pull_call.clear_cache()


def layer(heads, kv_heads, norm, rope, d=D, pairing="half"):
    """An attention module and its parameters, the norms' weights drawn away from one."""
    op = MultiheadAttention(2 * d, heads, bias=False, rope=rope, rope_pairing=pairing, num_kv_heads=kv_heads,
                            head_dim=d, qk_norm=norm, rope_base=500.0)
    params = op.init(jax.random.key(0))
    if norm:
        for i, name in enumerate(("q_norm", "k_norm")):
            params[name] = {"weight": 1 + 0.3 * jax.random.normal(jax.random.key(7 + i), (d,))}
    return op, params


def heads_of(op, params, fused: bool):
    """``(proj, norms) -> (q, k, v)``: the operator, or the composition."""
    def f(proj, norms):
        p = {**params, "q_norm": {"weight": norms[0]}, "k_norm": {"weight": norms[1]}} if norms else params
        dense = functools.partial(op._self_heads, p)
        if not fused:
            return dense(proj)
        return ph.position_heads(proj, op.num_heads, op.num_kv_heads, dense, norms=norms, eps=op.qk_norm_eps,
                                 rope_base=op.rope_base if op.rope else None, rope_pairing=op.rope_pairing)
    return f


def both(f, proj, norms, weights):
    """``(f(proj, norms), its cotangents under fixed weights)``, one program."""
    def scalar(proj, norms):
        out = f(proj, norms)
        return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(out, weights)), out

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, (0, 1), has_aux=True))(proj, norms)
    return out, grads


def inputs(op, params, length=LENGTH, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(1), 4)
    cols = op.q_dim + 2 * op.kv_dim
    proj = (2 * jax.random.normal(keys[0], (BATCH, length, cols))).astype(dtype)
    weights = [jax.random.normal(k, (BATCH, n, length, op.head_dim))
               for k, n in zip(keys[1:], (op.num_heads, op.num_kv_heads, op.num_kv_heads))]
    norms = (params["q_norm"]["weight"], params["k_norm"]["weight"]) if op.qk_norm else ()
    return proj, norms, weights


def counted(fn):
    before = dict(ph.path_counts)
    out = fn()
    return out, {n: ph.path_counts[n] - before[n] for n in before}


def close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=tol * max(float(np.abs(want).max()), 1e-6), rtol=0)


_PAIRS = {}


def pairs(variant, group):
    """``{what: (the operator's, the composition's)}``, made once for the
    five cases that read it."""
    if (variant, group) not in _PAIRS:
        op, params = layer(*GROUPS[group], *VARIANTS[variant])
        proj, norms, weights = inputs(op, params)
        (got, (d_proj, d_norms)), counts = counted(lambda: both(heads_of(op, params, True), proj, norms, weights))
        assert counts == {"pallas": 1, "dense": 0}
        want, (w_proj, w_norms) = both(heads_of(op, params, False), proj, norms, weights)
        _PAIRS[variant, group] = dict(zip(WHAT, zip((*got, d_proj, d_norms), (*want, w_proj, w_norms))))
    return _PAIRS[variant, group]


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("group", list(GROUPS))
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_the_kernels_are_the_composition(variant, group, what, tiles_of_32):
    """Float32 throughout: the heads, ``d proj`` and both norms' weights'
    cotangents (none without the norm) agree to float32's rounding."""
    got, want = pairs(variant, group)[what]
    if what == "d_norms":
        assert len(got) == len(want) == (2 if VARIANTS[variant][0] else 0)
        for g, w in zip(got, want):
            close(g, w, 1e-5)
    else:
        close(got, want, 1e-5)


@pytest.mark.parametrize("length", [48, 64])
def test_a_piece_and_a_rest_and_a_single_tile(length, monkeypatch):
    """A tile of 48 rows walked in a piece of 32 and a rest of 16; a tile of
    the whole 64-row sequence in two pieces."""
    monkeypatch.setattr(ph, "_TILE", 512)
    monkeypatch.setattr(ph, "_SUB", 32)
    ph._heads_call.clear_cache()
    ph._pull_call.clear_cache()
    op, params = layer(4, 2, True, True)
    proj, norms, weights = inputs(op, params, length)
    assert ph._pallas_gate(proj, 4, 2, True) == length
    got, d_got = both(heads_of(op, params, True), proj, norms, weights)
    want, d_want = both(heads_of(op, params, False), proj, norms, weights)
    for a, b in zip(jax.tree.leaves((got, d_got)), jax.tree.leaves((want, d_want))):
        close(a, b, 1e-5)
    ph._heads_call.clear_cache()
    ph._pull_call.clear_cache()


@pytest.mark.parametrize("d, pairing, path", [(128, "half", "pallas"), (16, "half", "dense"),
                                              (128, "interleaved", "dense"), (64, "half", "dense")])
def test_the_gate(d, pairing, path):
    """Heads of whole lane tiles with a rotation by halves take the kernels;
    toy heads, heads of 64 and the interleaved rotation the composition,
    which then is what the operator returns."""
    op, params = layer(4, 2, True, True, d=d, pairing=pairing)
    proj, norms, _ = inputs(op, params, 32)
    got, counts = counted(lambda: heads_of(op, params, True)(proj, norms))
    assert counts == {"pallas": 0, "dense": 0, path: 1}
    if path == "dense":
        for a, b in zip(got, heads_of(op, params, False)(proj, norms)):
            np.testing.assert_array_equal(a, b)


def test_no_rotation_takes_the_kernels_whatever_the_pairing():
    """Without a rotation the pairing is moot: the interleaved setting of an
    unrotated layer does not send it to the composition."""
    op, params = layer(4, 2, True, False, pairing="interleaved")
    proj, norms, _ = inputs(op, params, 32)
    _, counts = counted(lambda: heads_of(op, params, True)(proj, norms))
    assert counts == {"pallas": 1, "dense": 0}


def test_long_sequences_on_the_cpu_take_the_composition():
    """The interpreter is for test scale: past 512 rows the CPU composes."""
    op, params = layer(2, 1, False, True)
    proj = jax.ShapeDtypeStruct((1, 1024, op.q_dim + 2 * op.kv_dim), jnp.float32)
    assert ph._pallas_gate(proj, 2, 1, True) is None


@pytest.mark.parametrize("variant", ["norm_rope", "neither"])
def test_bfloat16_rounds_once(variant, tiles_of_32):
    """A bfloat16 ``proj`` gives bfloat16 heads and a bfloat16 ``d proj``
    within a bfloat16 step of the float32 composition's on the same numbers;
    the composition in bfloat16, which rounds after the norm and again after
    the rotation, is no closer.  The norms' weights' cotangents stay float32,
    summed from bfloat16 cotangents of the heads."""
    op, params = layer(8, 1, *VARIANTS[variant])
    proj, norms, weights = inputs(op, params, dtype=jnp.bfloat16)
    want, d_want = both(heads_of(op, params, False), proj.astype(jnp.float32), norms, weights)
    got, d_got = both(heads_of(op, params, True), proj, norms, weights)
    composed, d_composed = both(heads_of(op, params, False), proj, norms, weights)
    for a, b, c in zip((*got, d_got[0]), (*want, d_want[0]), (*composed, d_composed[0])):
        assert a.dtype == jnp.bfloat16
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        assert err <= 2.0 ** -8 * float(jnp.max(jnp.abs(b)))
        assert err <= float(jnp.max(jnp.abs(c.astype(jnp.float32) - b)))
    for a, b in zip(d_got[1], d_want[1]):
        close(a, b, 1e-2)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_attention_through_the_kernels_is_its_masked_dense_path(variant, tiles_of_32, monkeypatch):
    """The module's value and gradients with the heads from the kernels
    against the same module sent down the masked dense path (a float mask of
    zeros) with the heads composed.  A mask changes the scores, not the
    heads: the masked call takes the kernels too, where the gate lets it."""
    op, params = layer(8, 2, *VARIANTS[variant])
    x = jax.random.normal(jax.random.key(3), (BATCH, 40, op.embed_dim))

    def run(**mask):
        loss = lambda p, x: jnp.sum(jnp.sin(op.apply(p, x, causal=True, **mask)))  # noqa: E731
        return jax.jit(jax.value_and_grad(loss, (0, 1)))(params, x)

    got, counts = counted(run)
    assert counts == {"pallas": 1, "dense": 0}
    masked, counts = counted(lambda: run(attn_mask=jnp.zeros((40, 40))))
    assert counts == {"pallas": 1, "dense": 0}
    monkeypatch.setattr(ph, "_pallas_gate", lambda *a: None)
    want, counts = counted(lambda: run(attn_mask=jnp.zeros((40, 40))))
    assert counts == {"pallas": 0, "dense": 1}
    for a, b, c in zip(jax.tree.leaves(got), jax.tree.leaves(masked), jax.tree.leaves(want)):
        close(a, c, 1e-4)
        close(b, c, 1e-4)


@pytest.mark.parametrize("heads, kv_heads, dtype, tile", [
    (32, 4, jnp.bfloat16, 512),  # Trinity-Mini's layer, as the cell runs it
    (28, 4, jnp.bfloat16, 512),  # SmallThinker's
    (32, 4, jnp.float32, 256),   # Trinity-Mini's in float32 activations
    (32, 32, jnp.bfloat16, 256),  # 32 heads of 128 each way, QK norm
    (32, 32, jnp.float32, 128),
    (2040, 4, jnp.float32, None)])  # no tile of 16 rows fits: the composition
def test_the_gate_halves_the_tile_to_fit_vmem(heads, kv_heads, dtype, tile, monkeypatch):
    """On a TPU the tile halves until a grid step's blocks fit the kernels'
    VMEM (the tiles here compiled for a v5e; a tile twice as large ran out
    at each halved shape), and no tile at all sends the call to the
    composition."""
    monkeypatch.setattr(ph, "platform_of", lambda q: "tpu")
    monkeypatch.setattr(ph, "_kernel_mesh", lambda q: None)
    cols = (heads + 2 * kv_heads) * D
    proj = jax.ShapeDtypeStruct((1, 32768, cols), dtype)
    assert ph._pallas_gate(proj, heads, kv_heads, True) == tile
    if tile:
        assert ph._block_bytes(tile, cols, D, jnp.dtype(dtype).itemsize) <= ph._VMEM_BLOCKS
        if tile < ph._TILE:
            assert ph._block_bytes(2 * tile, cols, D, jnp.dtype(dtype).itemsize) > ph._VMEM_BLOCKS
