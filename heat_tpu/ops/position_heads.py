"""Query, key and value heads from attention's packed projection, normalised
and rotated, in one pass each way.

``proj`` is ``(B, S, (H + 2 K) d)``, the packed projection of self-attention:
``H`` query heads, then ``K`` key heads, then ``K`` value heads, each ``d``
wide.  The operator returns ``(B, H, S, d)``, ``(B, K, S, d)`` and ``(B, K, S,
d)``, the flash kernels' layout:

    y = x * rsqrt(mean(x^2) + eps) * w     (each query and key head, where the norms'
                                            weights are given: the query's or the key's)
    y = y * [cos, cos] + roll(y, d/2) * [-sin, sin]
                                           (each query and key head, where rope_base is given:
                                            channels i and i + d/2 by position * base^(-2i/d))

and the values as they are.  The arithmetic is float32, with
``nn.attention.apply_rope``'s angles, and rounds once, to ``proj``'s dtype, at
the end.  A ``jax.custom_vjp`` that keeps only ``proj`` and the norms'
weights, with two executors chosen from the platform of the data and the
shapes (``_pallas_gate``; no flag):

- the caller's ``dense``, the composition the operator stands for (split,
  transpose, ``rms_normalize``, ``apply_rope``: float32 passes through HBM,
  each rounding at its end), for any head width and the interleaved rotation;
- two Pallas kernels, one a direction, for heads of whole lane tiles.  A grid
  step takes ``(rows, (H + 2 K) d)`` of ``proj`` and the rows' angles as two
  ``(rows, d)`` tables, and walks the rows ``_SUB`` at a time: for each query
  and key head the lane sum, ``rsqrt``, the weight, a lane roll by ``d / 2``
  against the tables and one store at ``(b, head, tile)`` of the head-major
  result; the values are copied.  The split and the transpose are the
  blocks' index maps.  The backward reads ``proj`` and the three cotangents
  once, rotates back by the negative angle, goes back through the norm and
  writes ``d proj`` in ``proj``'s own layout, which the projection's weight
  gradient reads; each grid step writes its part of the norms' weights'
  cotangents, added outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.devices import platform_of
from ..nn.attention import rope_angles
from .flash_attention import _kernel_mesh
from .short_conv import _sharded

__all__ = ["position_heads"]

# rows of a grid step (a multiple of a bfloat16 tile's 16 sublanes), rows of
# a head the kernels' bodies work on at a time.  On a TPU v5e, at a Trinity-Mini
# layer (``proj`` (1, 32768, 5120) bfloat16, 32 + 4 + 4 heads of 128, QK norm
# and rotation), the composition this replaces took 8.61 ms forward and 17.61
# forward and backward, the kernels 1.61 and 3.33 at 512 rows in pieces of
# 128; 2.58 and 5.29 at 256 in pieces of 64, 4.42 and 9.02 in pieces of 32:
# what a piece pays is paid per piece, not per row
_TILE, _SUB = 512, 128

# what a grid step's blocks may take of the kernels' 64 MiB of VMEM
# (``_block_bytes``), the rest left to the pieces.  Compiled for a v5e, a
# Trinity-Mini layer's backward fits at 512 rows in bfloat16 (31 MiB of
# blocks) and at 1,024 (62 MiB), and runs out at 512 rows in float32 (62
# MiB); a layer of 32 + 32 + 32 heads with QK norm runs out at 512 rows in
# bfloat16 (73 MiB) and fits at 256
_VMEM_BLOCKS = 48 * 2**20

# engagement counter, flash attention's contract: which executor a call of
# ``position_heads`` took, counted at trace time
path_counts = {"pallas": 0, "dense": 0}

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 2**20)


def _tables(length: int, width: int, base: float):
    """``([cos, cos], [-sin, sin])`` of the angles ``apply_rope`` gives
    positions ``0 .. length - 1``, each ``(length, width)`` float32."""
    ang = rope_angles(jnp.arange(length), width, base)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def _walk(rows: int, body, carry=()):
    """``body(first row, rows, carry)`` over ``_SUB`` rows of a tile at a
    time (a loop on the device), then over what is left of the tile."""
    whole, rest = divmod(rows, _SUB)
    if whole:
        carry = jax.lax.fori_loop(0, whole, lambda i, c: body(pl.multiple_of(i * _SUB, _SUB), _SUB, c), carry)
    return body(whole * _SUB, rest, carry) if rest else carry


def _split(refs, norm: bool, rope: bool):
    """``(weights, tables, the rest)`` of what follows the row blocks among a
    kernel's references."""
    weights, refs = (refs[:2], refs[2:]) if norm else ((), refs)
    tables, refs = (refs[:2], refs[2:]) if rope else ((), refs)
    return weights, tables, refs


def _forward_kernel(proj_ref, *refs, heads: int, norm: bool, rope: bool, eps: float):
    """A tile of ``proj`` to the tile's rows of every head."""
    weights, tables, (q_ref, k_ref, v_ref) = _split(refs, norm, rope)
    kv_heads, rows, width = k_ref.shape[1:]
    for j in range(kv_heads):  # the values: the layout alone
        col = (heads + kv_heads + j) * width
        v_ref[0, j] = proj_ref[0, :, col:col + width]

    def piece(r, n, carry):
        cos, sin = (t[pl.ds(r, n)] for t in tables) if rope else (None, None)
        for h in range(heads + kv_heads):
            y = proj_ref[0, pl.ds(r, n), h * width:(h + 1) * width].astype(jnp.float32)
            if norm:
                y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * weights[h >= heads][...]
            if rope:
                y = y * cos + pltpu.roll(y, width // 2, 1) * sin
            out_ref, i = (q_ref, h) if h < heads else (k_ref, h - heads)
            out_ref[0, i, pl.ds(r, n)] = y.astype(out_ref.dtype)
        return carry

    _walk(rows, piece)


def _backward_kernel(*refs, heads: int, norm: bool, rope: bool, eps: float):
    """The cotangents of a tile's rows of every head to the tile of ``d
    proj``, and the tile's part of the norms' weights' cotangents; ``proj``'s
    tile only where a norm is to be gone back through."""
    proj_ref, refs = (refs[0], refs[1:]) if norm else (None, refs)
    dq_ref, dk_ref, dv_ref, *refs = refs
    weights, tables, (dproj_ref, *dw_refs) = _split(refs, norm, rope)
    kv_heads, rows, width = dk_ref.shape[1:]
    for j in range(kv_heads):
        col = (heads + kv_heads + j) * width
        dproj_ref[0, :, col:col + width] = dv_ref[0, j]

    def piece(r, n, sums):
        cos, sin = (t[pl.ds(r, n)] for t in tables) if rope else (None, None)
        sums = list(sums)
        for h in range(heads + kv_heads):
            lanes = slice(h * width, (h + 1) * width)
            g_ref, i = (dq_ref, h) if h < heads else (dk_ref, h - heads)
            g = g_ref[0, i, pl.ds(r, n)].astype(jnp.float32)
            if rope:  # the rotation by the negative angle
                g = g * cos - pltpu.roll(g, width // 2, 1) * sin
            if norm:
                x = proj_ref[0, pl.ds(r, n), lanes].astype(jnp.float32)
                inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
                y = x * inv
                sums[h >= heads] = sums[h >= heads] + jnp.sum(g * y, axis=0, keepdims=True)
                g = g * weights[h >= heads][...]
                g = inv * (g - y * jnp.mean(g * y, axis=-1, keepdims=True))
            dproj_ref[0, pl.ds(r, n), lanes] = g.astype(dproj_ref.dtype)
        return tuple(sums)

    sums = _walk(rows, piece, (jnp.zeros((1, width), jnp.float32),) * 2 if norm else ())
    for ref, total in zip(dw_refs, sums):
        ref[0] = total


def _specs(shape, heads: int, kv_heads: int, tile: int):
    """``(grid, head width, block specs)`` of a call over ``proj``'s shape:
    its row tiles whole, the head-major arrays' rows of a tile, the norms'
    weights, the tables' rows and a grid step's part of a weight's cotangent."""
    batch, length, cols = shape
    width, tiles = cols // (heads + 2 * kv_heads), length // tile
    spec = {"rows": pl.BlockSpec((1, tile, cols), lambda b, t: (b, t, 0)),
            "weight": pl.BlockSpec((1, width), lambda b, t: (0, 0)),
            "table": pl.BlockSpec((tile, width), lambda b, t: (t, 0)),
            "part": pl.BlockSpec((1, 1, width), lambda b, t: (b * tiles + t, 0, 0)),
            **{n: pl.BlockSpec((1, count, tile, width), lambda b, t: (b, 0, t, 0))
               for n, count in (("q", heads), ("kv", kv_heads))}}
    return (batch, tiles), width, spec


@functools.partial(jax.jit, static_argnums=range(6))
def _heads_call(heads, kv_heads, eps, base, tile, interpret, proj, *norms):
    """``(q, k, v)`` heads of ``proj``; ``norms`` the query's and the key's
    weights as ``(1, d)`` float32, or nothing.  A program of its own (as
    ``short_conv._heads_call``), so that a model's layers and a layer's
    forward and recomputed forward trace and lower the kernel once."""
    grid, width, spec = _specs(proj.shape, heads, kv_heads, tile)
    tables = _tables(proj.shape[1], width, base) if base is not None else ()
    return pl.pallas_call(
        functools.partial(_forward_kernel, heads=heads, norm=bool(norms), rope=bool(tables), eps=eps),
        grid=grid,
        in_specs=[spec["rows"]] + [spec["weight"]] * len(norms) + [spec["table"]] * len(tables),
        out_specs=[spec["q"], spec["kv"], spec["kv"]],
        out_shape=[jax.ShapeDtypeStruct((grid[0], n, proj.shape[1], width), proj.dtype)
                   for n in (heads, kv_heads, kv_heads)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(proj, *norms, *tables)


@functools.partial(jax.jit, static_argnums=range(6))
def _pull_call(heads, kv_heads, eps, base, tile, interpret, dq, dk, dv, *rest):
    """``(d proj, a grid step's part of each norm weight's cotangent)``;
    ``rest`` is ``(proj, the two weights)`` with the norm, else nothing."""
    (batch, _, length, width), proj = dq.shape, rest[:1]
    norms = rest[1:]
    shape = (batch, length, (heads + 2 * kv_heads) * width)
    grid, _, spec = _specs(shape, heads, kv_heads, tile)
    tables = _tables(length, width, base) if base is not None else ()
    return pl.pallas_call(
        functools.partial(_backward_kernel, heads=heads, norm=bool(norms), rope=bool(tables), eps=eps),
        grid=grid,
        in_specs=[spec["rows"]] * len(proj) + [spec["q"], spec["kv"], spec["kv"]] + [spec["weight"]] * len(norms)
        + [spec["table"]] * len(tables),
        out_specs=[spec["rows"]] + [spec["part"]] * len(norms),
        out_shape=[jax.ShapeDtypeStruct(shape, dq.dtype)]
        + [jax.ShapeDtypeStruct((grid[0] * grid[1], 1, width), jnp.float32)] * len(norms),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*proj, dq, dk, dv, *norms, *tables)


def _rows(norms):
    return tuple(w.astype(jnp.float32).reshape(1, -1) for w in norms)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _position_heads(proj, norms, heads, kv_heads, eps, base, tile, interpret):
    call = functools.partial(_heads_call, heads, kv_heads, eps, base, tile, interpret)
    return tuple(_sharded(call, (proj, *_rows(norms)), 1))


def _heads_fwd(proj, norms, heads, kv_heads, eps, base, tile, interpret):
    # without a norm the backward is a rotation and the layout: no ``proj``
    return _position_heads(proj, norms, heads, kv_heads, eps, base, tile, interpret), (proj if norms else None, norms)


def _heads_bwd(heads, kv_heads, eps, base, tile, interpret, res, cotangents):
    proj, norms = res
    call = functools.partial(_pull_call, heads, kv_heads, eps, base, tile, interpret)
    kept = (proj, *_rows(norms)) if norms else ()
    d_proj, *parts = _sharded(call, (*cotangents, *kept), 4 if norms else 3)
    return d_proj, tuple(jnp.sum(p, axis=(0, 1)).astype(w.dtype) for p, w in zip(parts, norms))


_position_heads.defvjp(_heads_fwd, _heads_bwd)


def _block_bytes(tile: int, cols: int, width: int, itemsize: int) -> int:
    """VMEM of a grid step's blocks in the larger of the kernels, the
    backward with a norm and a rotation, double-buffered: ``proj``'s rows,
    the cotangents' and ``d proj``'s (each ``tile x cols`` in ``proj``'s
    dtype) and the two float32 tables."""
    return 2 * tile * (3 * cols * itemsize + 2 * width * 4)


def _pallas_gate(proj, heads: int, kv_heads: int, half: bool):
    """Rows of a grid step of the kernels, None for the dense executor: the
    kernels on a TPU and, at test scale, under the interpreter on a CPU,
    where the shapes are theirs (heads of whole lane tiles, a rotation by
    halves or none; across chips as many sequences as divide among them).
    The tile halves from ``_TILE`` until its blocks fit ``_VMEM_BLOCKS``."""
    platform = platform_of(proj)
    batch, length, cols = proj.shape
    width = cols // (heads + 2 * kv_heads)
    mesh = _kernel_mesh(proj)
    fits = width % 128 == 0 and half and (mesh is None or batch % mesh.size == 0)
    if not fits or not (platform == "tpu" or (platform == "cpu" and length <= 512)):
        return None
    tile, itemsize = _TILE, jnp.dtype(proj.dtype).itemsize
    while _block_bytes(tile, cols, width, itemsize) > _VMEM_BLOCKS:
        if tile <= 16:  # a bfloat16 tile's sublanes
            return None
        tile //= 2
    return min(tile, -(-length // 16) * 16)


def position_heads(proj, num_heads: int, num_kv_heads: int, dense, *, norms=(), eps: float = 1e-5,
                   rope_base: float = None, rope_pairing: str = "half"):
    """``(q, k, v)`` heads of self-attention's packed projection, normalised
    and rotated.

    ``proj``: ``(B, S, (num_heads + 2 num_kv_heads) d)``; ``norms``: the
    query's and the key's RMS-norm weights (``(d,)`` each, epsilon ``eps``),
    or nothing; ``rope_base``: the rotation's base, or None for none;
    ``rope_pairing`` as :func:`~heat_tpu.nn.attention.apply_rope`'s.  Returns
    ``(B, num_heads, S, d)``, ``(B, num_kv_heads, S, d)`` twice, in ``proj``'s
    dtype.  ``dense(proj)`` is the composition the operator stands for; it
    runs wherever the kernels do not take the call (``path_counts`` says
    which ran).
    """
    tile = _pallas_gate(proj, num_heads, num_kv_heads, rope_base is None or rope_pairing == "half")
    path_counts["pallas" if tile else "dense"] += 1
    if not tile:
        return dense(proj)
    length = proj.shape[1]
    pad = -length % tile
    if pad:  # rows after the end reach nothing before it
        proj = jnp.pad(proj, ((0, 0), (0, pad), (0, 0)))
    out = _position_heads(proj, tuple(norms), num_heads, num_kv_heads, eps, rope_base, tile,
                          platform_of(proj) != "tpu")
    return tuple(t[:, :, :length] for t in out) if pad else out
