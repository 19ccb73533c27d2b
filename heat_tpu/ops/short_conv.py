"""Short causal depthwise convolutions fused with what stands around them:
``gated_short_conv`` (between two element-wise gates, the sequence operator
of convolution/attention hybrids) and ``conv_silu_heads`` (under SiLU and L2
norms, split into heads: the front of Kimi Delta Attention), further down.

Gated short convolution.

``bcu`` is ``(..., S, 3 * D)``, three gates of one input projection laid side
by side (``B``, ``C``, ``u``, in that order), ``taps`` is ``(D, L)``:

    v = B * u
    c[t] = sum_j taps[:, j] * v[t - (L - 1) + j]      (zero before the start)
    y = C * c

Position ``t`` sees ``t - (L - 1) .. t`` of its own sequence only, so the
operator is causal and no value crosses from one sequence to the next.  It
moves ``4 D`` values a position and multiplies a handful: memory-bound, and
XLA fuses the shifted multiply-adds into one pass over ``bcu``, so this is
plain ``jax.numpy`` with the backward pass written out (``jax.custom_vjp``):
the transposed convolution runs the taps the other way, and only the inputs
are kept for it, not ``v`` and ``c``.  Arithmetic is float32 whatever the
dtype of ``bcu``; the result has ``bcu``'s dtype.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.devices import platform_of
from .flash_attention import _kernel_mesh, _per_shard

__all__ = ["gated_short_conv", "conv_silu_heads"]


def _shift(x, by: int):
    """``x`` moved ``by`` positions along the sequence axis (-2), zeros
    entering: ``by > 0`` towards later positions."""
    if by == 0:
        return x
    length = x.shape[-2]
    pad = [(0, 0)] * x.ndim
    if by > 0:
        pad[-2] = (by, 0)
        return jnp.pad(x, pad)[..., :length, :]
    pad[-2] = (0, -by)
    return jnp.pad(x, pad)[..., -length:, :]


def _conv(v, taps):
    n_taps = taps.shape[1]
    return sum(taps[:, j] * _shift(v, n_taps - 1 - j) for j in range(n_taps))


def _forward(bcu, taps):
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    return (c * _conv(b * u, taps.astype(jnp.float32))).astype(bcu.dtype)


@jax.custom_vjp
def gated_short_conv(bcu, taps):
    """``C * causal_depthwise_conv(B * u, taps)`` for ``bcu = [B, C, u]``
    of shape ``(..., S, 3 * D)`` and ``taps`` of shape ``(D, L)``."""
    return _forward(bcu, taps)


def _fwd(bcu, taps):
    return _forward(bcu, taps), (bcu, taps)


def _bwd(res, dy):
    bcu, taps = res
    n_taps = taps.shape[1]
    k = taps.astype(jnp.float32)
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    dy = dy.astype(jnp.float32)
    v = b * u
    dconv = dy * c
    # v[s] reaches c[s + (L - 1) - j] through tap j
    dv = sum(k[:, j] * _shift(dconv, -(n_taps - 1 - j)) for j in range(n_taps))
    lead = tuple(range(bcu.ndim - 1))
    dtaps = jnp.stack(
        [jnp.sum(dconv * _shift(v, n_taps - 1 - j), axis=lead) for j in range(n_taps)], axis=1)
    dbcu = jnp.concatenate([dv * u, dy * _conv(v, k), dv * b], axis=-1)
    return dbcu.astype(bcu.dtype), dtaps.astype(taps.dtype)


gated_short_conv.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# conv_silu_heads: a convolution's sections as normalised heads
#
#     c[t]  = sum_j taps[:, j] * qkv[t - (L - 1) + j]        (zero before the start)
#     a     = c * sigmoid(c)
#     y_i   = a_i * rsqrt(sum_head(a_i^2) + eps) * scale_i   (section i, where normalise[i])
#
# as ``(B, H, S, d)`` a section, float32 throughout and rounded once.  One
# operator under a ``jax.custom_vjp`` that keeps ``qkv`` and the taps, with two
# executors chosen from the platform of the data and the shapes (``_pallas_gate``;
# no flag):
#
# - ``_dense_heads``, plain ``jax.numpy``: the whole convolution in float32
#   through HBM, split, transposed and normalised by XLA; its backward is
#   ``jax.vjp`` of itself.  Any head width.
# - Two Pallas kernels, one a direction, called a section at a time, for heads
#   of whole lane tiles.  A grid step takes ``(rows, heads * d)`` of ``qkv`` in
#   its own ``(B, S, n * P)`` layout with the ``_HALO`` rows before it (zeros at
#   a sequence's start), stages them as float32 in VMEM (a scratch tiled by
#   rows, so the taps' shifted reads are plain loads), and walks them ``_SUB``
#   rows of a head at a time: four multiply-adds, SiLU, the lane sum, ``rsqrt``,
#   one store at ``(b, h, tile)`` of the head-major result.  The split and the
#   transpose are index maps.  The backward loads the ``_HALO`` rows after the
#   tile too (of ``qkv`` and of the cotangent; nothing comes back from beyond a
#   sequence's end), makes the pre-activation again, goes back through scale,
#   norm and SiLU into a scratch, runs the taps the other way out of it, and
#   adds the tile's part of the taps' cotangent into a block the sequence axis
#   revisits.  The sections write their columns of one ``d qkv`` one after
#   another (``input_output_aliases``): an output takes one block a grid step.
#
# On a v5e (my chip runs, PR 37; a layer of ``kimi_linear_48b_a3b_train_2x8k``:
# ``qkv`` (2, 8192, 12288) bfloat16, 32 heads of 128): the expression this
# replaced 14.3 ms forward and 31.8 forward and backward under its
# ``jax.checkpoint``; the kernels 1.79 and 2.78 ms (805 MB and 1.21 GB: 55%
# and 53% of the HBM's rate).  Blocks of one head (256-byte rows) take 3.46 and
# 5.06; the lane sums as products with a matrix of ones on the MXU 2.41 and
# 4.38; pieces of 32 rows 2.26 and 3.46.
# ---------------------------------------------------------------------------

# rows of the blocks before and after a tile (a bfloat16 tile's sublanes; no
# fewer than ``L - 1``), rows of a tile, rows of a head the kernels' bodies work
# on at a time (8 vregs a value at 128 lanes: what stays in registers), lanes of
# a block (rows of 1 KiB for the DMA)
_HALO, _TILE, _SUB, _LANES = 16, 512, 64, 512

# engagement counter, flash attention's contract: which executor a call of
# ``conv_silu_heads`` took, counted at trace time
path_counts = {"pallas": 0, "dense": 0}


def _dense_heads(qkv, taps, num_heads, normalise, scale, eps):
    """The operator in plain ``jax.numpy``: every section of the whole
    convolution in float32 through HBM."""
    *lead, length, _ = qkv.shape
    mixed = jax.nn.silu(_conv(qkv.astype(jnp.float32), taps.astype(jnp.float32)))
    out = []
    for t, norm, by in zip(jnp.split(mixed, len(normalise), axis=-1), normalise, scale):
        t = jnp.moveaxis(t.reshape(*lead, length, num_heads, -1), -2, -3)
        if norm:
            t = t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + eps)
        out.append((t if by == 1 else t * by).astype(qkv.dtype))
    return tuple(out)


def _lane_sum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _add(terms):
    """The terms' sum from the first on (``sum`` starts from a zero, which a
    kernel then adds to every vector)."""
    return functools.reduce(operator.add, terms)


def _walk(rows: int, body, carry=None):
    """``body(first row, rows, carry)`` over ``_SUB`` rows of a tile at a
    time (a loop on the device: the kernels' bodies stay a piece long), then
    over what is left of the tile."""
    whole, rest = divmod(rows, _SUB)
    if whole:
        carry = jax.lax.fori_loop(0, whole, lambda i, c: body(pl.multiple_of(i * _SUB, _SUB), _SUB, c), carry)
    return body(whole * _SUB, rest, carry) if rest else carry


def _stage(xs_ref, blocks, width: int, zero_first):
    """``qkv``'s blocks, one after another along the rows, as float32 in the
    scratch ``(heads, rows, d)``; zeros for the first where nothing is before it."""
    at = 0
    for i, block in enumerate(blocks):
        for h in range(xs_ref.shape[0]):
            x = block[0, :, h * width:(h + 1) * width].astype(jnp.float32)
            xs_ref[h, at:at + block.shape[1]] = jnp.where(zero_first, 0.0, x) if i == 0 else x
        at += block.shape[1]


def _conv_rows(xs_ref, taps_ref, h: int, at, rows: int):
    """``rows`` rows of head ``h``'s convolution from row ``at`` of the
    scratch on: row ``r`` sees ``r - (L - 1) .. r``, tap ``j`` the row
    ``L - 1 - j`` before."""
    n_taps, width = taps_ref.shape[0], xs_ref.shape[2]
    first = at - (n_taps - 1)
    return _add(taps_ref[j:j + 1, h * width:(h + 1) * width] * xs_ref[h, pl.ds(first + j, rows)] for j in range(n_taps))


def _heads_kernel(prev_ref, cur_ref, taps_ref, out_ref, xs_ref, *, normalise, scale, eps):
    """A tile of one section, ``(rows, heads * d)`` of ``qkv`` in its own
    layout with the block of ``_HALO`` rows before it, to ``(heads, rows, d)``
    of the head-major result."""
    heads, rows, width = out_ref.shape[1:]
    _stage(xs_ref, (prev_ref, cur_ref), width, pl.program_id(2) == 0)

    def piece(r, n, _):
        for h in range(heads):
            c = _conv_rows(xs_ref, taps_ref, h, _HALO + r, n)
            y = c * jax.nn.sigmoid(c)
            if normalise:
                y = y * jax.lax.rsqrt(_lane_sum(y * y) + eps)
            out_ref[0, h, pl.ds(r, n)] = (y if scale == 1 else y * scale).astype(out_ref.dtype)

    _walk(rows, piece)


def _pull_kernel(*refs, normalise, scale, eps):
    """The backward of ``_heads_kernel`` on a tile: the pre-activation again
    from ``qkv`` (its rows and ``_HALO`` on either side), the cotangent of
    the convolution's output on the tile's rows and the ``_HALO`` after them
    (scratch), the taps the other way, and the tile's part of the taps'
    cotangent added into a block the sequence axis revisits."""
    prev_ref, cur_ref, next_ref, taps_ref, dy_ref, dy_next_ref = refs[:6]
    dx_ref, dtaps_ref, xs_ref, dc_ref = refs[-4:]
    heads, rows, width = dy_ref.shape[1:]
    tile, tiles = pl.program_id(2), pl.num_programs(2)
    _stage(xs_ref, (prev_ref, cur_ref, next_ref), width, tile == 0)
    n_taps = taps_ref.shape[0]

    def through(r, n, dy_of):  # rows r .. r + n of the convolution's cotangent
        for h in range(heads):
            dy = dy_of(h)
            c = _conv_rows(xs_ref, taps_ref, h, _HALO + r, n)
            s = jax.nn.sigmoid(c)
            if normalise:
                a = c * s
                inv = jax.lax.rsqrt(_lane_sum(a * a) + eps)
                dy = inv * (dy - a * (inv * inv * _lane_sum(dy * a)))
            dc_ref[h, pl.ds(r, n)] = (dy if scale == 1 else dy * scale) * (s * (1.0 + c * (1.0 - s)))

    _walk(rows, lambda r, n, _: through(r, n, lambda h: dy_ref[0, h, pl.ds(r, n)].astype(jnp.float32)))
    # nothing comes back from beyond the sequence's end
    through(rows, _HALO, lambda h: jnp.where(tile == tiles - 1, 0.0, dy_next_ref[0, h].astype(jnp.float32)))

    def back(r, n, d_taps):
        out = []
        for h, acc in enumerate(d_taps):
            lanes = slice(h * width, (h + 1) * width)
            # x[s] reaches c[s + (L - 1) - j] through tap j
            dx_ref[0, pl.ds(r, n), lanes] = _add(
                taps_ref[j:j + 1, lanes] * dc_ref[h, pl.ds(r + n_taps - 1 - j, n)] for j in range(n_taps)
            ).astype(dx_ref.dtype)
            dc = dc_ref[h, pl.ds(r, n)]
            first = _HALO + r - (n_taps - 1)
            out.append([a + jnp.sum(dc * xs_ref[h, pl.ds(first + j, n)], axis=0, keepdims=True)
                        for j, a in enumerate(acc)])
        return out

    d_taps = _walk(rows, back, [[jnp.zeros((1, width), jnp.float32)] * n_taps] * heads)

    @pl.when(tile == 0)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    dtaps_ref[0] += jnp.concatenate([jnp.concatenate(acc, axis=0) for acc in d_taps], axis=1)


def _layout(section: int, num_heads: int, sections: int, tile, shape, n_taps: int):
    """``(grid, head width, block specs)`` of a section's tiles: ``qkv``'s own
    ``(B, S, sections * P)`` layout (the tile, the halo before it, the halo
    after it, the taps' and their cotangent's columns) and the head-major
    ``(B, H, S, d)`` (the tile, the halo after it).  The split and the
    transpose are these index maps."""
    batch, length, cols = shape
    rows, heads = tile
    width = cols // sections // num_heads
    per = rows // _HALO
    col = lambda h: section * (num_heads // heads) + h  # noqa: E731
    flat = lambda shape, at: pl.BlockSpec((1, shape, heads * width), lambda b, h, t: (b, at(t), col(h)))  # noqa: E731
    major = lambda shape, at: pl.BlockSpec((1, heads, shape, width), lambda b, h, t: (b, h, at(t), 0))  # noqa: E731
    after = lambda t: jnp.minimum((t + 1) * per, length // _HALO - 1)  # noqa: E731
    spec = {"tile": flat(rows, lambda t: t), "before": flat(_HALO, lambda t: jnp.maximum(t * per - 1, 0)),
            "after": flat(_HALO, after), "heads": major(rows, lambda t: t), "heads_after": major(_HALO, after),
            "taps": pl.BlockSpec((n_taps, heads * width), lambda b, h, t: (0, col(h))),
            "d_taps": pl.BlockSpec((1, n_taps, heads * width), lambda b, h, t: (b, 0, h))}
    return (batch, num_heads // heads, length // rows), width, spec


_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnums=range(8))
def _heads_call(section, num_heads, sections, normalise, scale, eps, tile, interpret, qkv, taps_t):
    """One section of ``qkv (B, S, sections * P)`` as heads ``(B, H, S, d)``.
    A program of its own (as ``ops/kda._grid_call``), so that the layers of a
    model and a layer's forward and recomputed forward trace and lower each
    section's kernel once."""
    grid, width, spec = _layout(section, num_heads, sections, tile, qkv.shape, taps_t.shape[0])
    rows, heads = tile
    return pl.pallas_call(
        functools.partial(_heads_kernel, normalise=normalise, scale=scale, eps=eps),
        grid=grid,
        in_specs=[spec["before"], spec["tile"], spec["taps"]],
        out_specs=spec["heads"],
        out_shape=jax.ShapeDtypeStruct((grid[0], num_heads, qkv.shape[1], width), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((heads, _HALO + rows, width), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(qkv, qkv, taps_t)


@functools.partial(jax.jit, static_argnums=range(8))
def _pull_call(section, num_heads, sections, normalise, scale, eps, tile, interpret, qkv, dy, *rest):
    """``(d qkv, d taps a sequence (B, L, P))`` of one section.  ``rest`` is
    ``(taps_t,)`` or ``(d qkv so far, taps_t)``: the sections write their
    columns of one ``d qkv`` one after another, each call's result the next
    one's buffer."""
    *so_far, taps_t = rest
    n_taps = taps_t.shape[0]
    grid, width, spec = _layout(section, num_heads, sections, tile, qkv.shape, n_taps)
    rows, heads = tile
    return pl.pallas_call(
        functools.partial(_pull_kernel, normalise=normalise, scale=scale, eps=eps),
        grid=grid,
        in_specs=[spec["before"], spec["tile"], spec["after"], spec["taps"], spec["heads"], spec["heads_after"]]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(so_far),
        out_specs=[spec["tile"], spec["d_taps"]],
        out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct((grid[0], n_taps, num_heads * width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, 2 * _HALO + rows, width), jnp.float32),
                        pltpu.VMEM((heads, _HALO + rows, width), jnp.float32)],
        input_output_aliases={6: 0} if so_far else {},
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(qkv, qkv, qkv, taps_t, dy, dy, *so_far)


def _sharded(call, operands, n_batched: int):
    """``call``, a shard of the sequences a chip where the program spans
    several: a Mosaic kernel is not partitioned for it."""
    mesh = _kernel_mesh(operands[0])
    return (call if mesh is None else _per_shard(call, mesh, n_batched))(*operands)


def _statics(num_heads, normalise, scale, eps, tile, qkv):
    """The static arguments of each section's kernel calls."""
    return [(s, num_heads, len(normalise), normalise[s], scale[s], eps, tile, platform_of(qkv) != "tpu")
            for s in range(len(normalise))]


def _heads(qkv, taps, num_heads, normalise, scale, eps, tile):
    if not tile:
        return _dense_heads(qkv, taps, num_heads, normalise, scale, eps)
    taps_t = taps.astype(jnp.float32).T
    return tuple(_sharded(functools.partial(_heads_call, *static), (qkv, taps_t), 1)
                 for static in _statics(num_heads, normalise, scale, eps, tile, qkv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _conv_silu_heads(qkv, taps, num_heads, normalise, scale, eps, tile):
    return _heads(qkv, taps, num_heads, normalise, scale, eps, tile)


def _heads_fwd(qkv, taps, num_heads, normalise, scale, eps, tile):
    return _heads(qkv, taps, num_heads, normalise, scale, eps, tile), (qkv, taps)


def _heads_bwd(num_heads, normalise, scale, eps, tile, res, cotangents):
    qkv, taps = res
    if not tile:
        return jax.vjp(lambda *a: _dense_heads(*a, num_heads, normalise, scale, eps), qkv, taps)[1](cotangents)
    taps_t = taps.astype(jnp.float32).T
    so_far, d_taps = (), []
    for static, dy in zip(_statics(num_heads, normalise, scale, eps, tile, qkv), cotangents):
        operands = (qkv, dy, *so_far, taps_t)
        *so_far, part = _sharded(functools.partial(_pull_call, *static), operands, len(operands) - 1)
        d_taps.append(jnp.sum(part, axis=0))
    return so_far[0], jnp.concatenate(d_taps, axis=1).T.astype(taps.dtype)


_conv_silu_heads.defvjp(_heads_fwd, _heads_bwd)


def _pallas_gate(qkv, num_heads: int, sections: int, n_taps: int = 4):
    """``(rows, heads)`` of a grid step of the kernels, None for the dense
    executor: the kernels on a TPU and, at test scale, under the interpreter
    on a CPU, where the shapes are theirs (heads of whole lane tiles; taps
    that a halo holds; across chips as many sequences as divide among them)."""
    platform = platform_of(qkv)
    batch, length, cols = qkv.shape
    width = cols // sections // num_heads
    mesh = _kernel_mesh(qkv)
    fits = width % 128 == 0 and n_taps - 1 <= _HALO and (mesh is None or batch % mesh.size == 0)
    if not fits or not (platform == "tpu" or (platform == "cpu" and length <= 512)):
        return None
    most = max(1, _LANES // width)
    return (min(_TILE, -(-length // _HALO) * _HALO), max(h for h in range(1, most + 1) if num_heads % h == 0))


def conv_silu_heads(qkv, taps, num_heads: int, *, normalise=(True, True, False), scale=None, eps: float = 1e-6):
    """The sections of ``SiLU(causal_depthwise_conv(qkv, taps))`` as heads.

    ``qkv``: ``(B, S, n * P)``, ``n = len(normalise)`` sections of ``P =
    num_heads * d`` channels side by side; ``taps``: ``(n * P, L)``.  Returns
    ``n`` arrays ``(B, num_heads, S, d)`` in ``qkv``'s dtype: section ``i``
    L2-normalised a head (``x * rsqrt(sum(x^2) + eps)``) where
    ``normalise[i]``, times ``scale[i]`` (default: ``d ** -0.5`` for the
    first section, 1 for the others, what Kimi Delta Attention wants of ``q,
    k, v``).  Float32 arithmetic, one rounding at the end.  Only the inputs
    are kept for the backward pass.
    """
    sections = len(normalise)
    _, length, cols = qkv.shape
    if scale is None:
        scale = (float(cols // sections // num_heads) ** -0.5,) + (1,) * (sections - 1)
    tile = _pallas_gate(qkv, num_heads, sections, taps.shape[1])
    path_counts["pallas" if tile else "dense"] += 1
    pad = -length % tile[0] if tile else 0
    if pad:  # zeros after the end reach nothing before it
        qkv = jnp.pad(qkv, ((0, 0), (0, pad), (0, 0)))
    out = _conv_silu_heads(qkv, taps, num_heads, tuple(normalise), tuple(scale), eps, tile)
    return tuple(t[:, :, :length] for t in out) if pad else out
