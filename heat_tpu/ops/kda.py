"""Kimi Delta Attention: a gated delta rule with a decay a channel, chunk by
chunk (the sequence operator of linear-attention hybrids such as Kimi Linear,
arXiv:2510.26692).

A head keeps a state ``S`` of ``d_k x d_v``, zero at the start of a sequence:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g <= 0`` is the log-decay of each key channel, ``beta`` in (0, 1) the write
strength of each token.  A scan over the tokens runs ``S`` through the VPU one
rank-one update at a time; the chunk form below computes the same numbers
with matrix products.  Inside a chunk of ``C`` tokens with start state
``S_0`` and ``G_i = g_1 + ... + g_i`` a channel:

    A_ij   = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])       (j < i)
    T      = (I + A)^-1                  (unit lower triangular, a solve)
    W, U_v = T (beta * k * exp(G)),  T (beta * v)
    U      = U_v - W S_0                 (what each token writes)
    o      = (q * exp(G)) S_0 + M U,     M_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])   (j <= i)
    S_C    = Diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U

Everything up to ``W`` and ``U_v`` needs no state and is computed for all
chunks at once (``_prepare``); only the three lines with ``S_0`` are a
sequential ``lax.scan`` over the chunks (``_recur``), five small products a
step.  Only differences ``G_i - G_j`` with ``i >= j`` are ever exponentiated:
``-G`` reaches hundreds inside a chunk (``exp(A_log)`` up to 16, a step of up
to 1), so ``exp(-G_j)`` alone is no float32.  ``A`` and ``M`` are therefore
built from sub-blocks of ``_SUB`` rows: a block below the diagonal is the
product ``(k_I exp(G_I - r_I)) (k_J exp(r_I - G_J))^T`` with ``r_I`` the
block's first row of ``G`` (both exponents are <= 0), a block on the diagonal
sums ``exp(G_i - G_j)`` channel by channel under its mask.

The backward pass is a custom rule: the forward keeps the five inputs and
the state at the start of each chunk, the backward makes the chunks' parts
again, runs the recurrence's transpose as a reverse scan over the chunks (the
cotangent of ``S`` its carry) and pulls the result back through the
chunk-local part.  Matrix products take their operands in ``q``'s dtype
(bfloat16 on the chip) and add up in float32; the decays, the cumulative
sums, the solve and the state are float32.

The chunk-local part has two executors, chosen from the platform of the data
and the shapes (``_pallas_gate``; no flag):

- ``_prepare``, plain XLA over all chunks at once, for any chunk length and
  head width (XLA's triangular solve).  It makes a dozen float32 arrays of
  ``q``'s size, so with a batch axis before the heads it is walked one
  sequence at a time.
- Two Pallas kernels over tiles of whole chunks of one head, for heads of whole
  lane tiles and chunks of ``_SUB * 2^j`` tokens: ``_chunk_parts`` is the same
  mathematics one chunk at a time in forms Mosaic lowers (2-D tiles, static
  row slices, selects over iotas, the cumulative sum as a product with a
  triangle of ones, ``T`` by doubling the inverted diagonal blocks from side
  1 up, every float32 product at ``fp32`` contract precision), so ``G``, the
  sub-blocks' exponentials, ``A``, ``M``, ``T`` and the right-hand sides live
  in VMEM and only the six parts reach HBM.  The forward kernel maps it over
  its tile's chunks; the backward kernel loads the inputs, the forward
  kernel's ``T`` and the parts' cotangents and stores ``jax.vjp`` of the same
  function, traced into its body (the diagonal sub-blocks and the solve have
  rules of their own: the first takes its exponentials again column by
  column where autodiff would keep all sixteen, the second is
  ``-T^T ct (T rhs)^T``).  The chunks of a tile go through together because
  one chunk's float32 products wait on one another.  ``_prepare`` is kept
  beside it and not replaced by it: the tile function wants power-of-two
  chunks and pays Mosaic's forms, XLA's solve takes any chunk.

On a v5e (my chip runs, PR 33; 32 heads of 128, 8,192 tokens, chunk 64, one
sequence, bfloat16): ``_prepare`` 16.3 ms and its ``jax.vjp`` 38.3; the
forward kernel 5.45 (of it the diagonal sub-blocks 0.9, ``T`` 2.3; 8.97 with
the chunks one after another), 5.75 writing ``T``, the backward kernel 8.60
(the diagonal sub-blocks 2.9).  Two sequences through ``chunk_kda``: forward
15.8 ms (36.6 by ``_prepare``), forward and backward 57.7 (146.1), of which
the two scans are about 5 and 12.  In the training step of
``kimi_linear_48b_a3b_train_2x8k`` (four such layers, each forward twice and
backward once): 295 ms under ``ht.kda`` where ``_prepare`` gave 678.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.devices import platform_of
from .flash_attention import _kernel_mesh, _per_shard

__all__ = ["chunk_kda"]

_SUB = 16
# tokens of a 128-wide head a grid step of the kernels, (forward, backward): what
# Mosaic's 16 MiB of scoped VMEM hold (twice either overflows it at chunk 64)
_ROWS = (512, 256)
_HI = jax.lax.Precision.HIGHEST

# engagement counter, flash attention's contract: which implementation the
# chunk-local part of a call took, counted at trace time
path_counts = {"pallas": 0, "dense": 0}


def _mm(spec: str, a, b, dtype):
    """``einsum`` with operands rounded to ``dtype`` and a float32 sum."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _within(qc, kc, cum, sub: int, dtype):
    """``(sum_c k_i k_j exp(G_i - G_j) for j < i, M)``: the two ``(C, C)``
    matrices of every chunk from float32 ``q``, ``k`` and ``G`` by chunk."""
    *lead, chunk, dk = kc.shape
    blocks = chunk // sub

    def by_block(t):
        return t.reshape(*lead, blocks, sub, dk)

    qb, kb, gb = by_block(qc), by_block(kc), by_block(cum)
    first = gb[..., :1, :]  # r_I
    # below the diagonal blocks: rows of block I against every key of the
    # chunk, decayed from r_I (the keys at or after r_I are masked below)
    fall = jnp.exp(gb - first)
    rows = jnp.concatenate([kb * fall, qb * fall], axis=-2)  # (.., I, 2 sub, dk)
    keys = kc[..., None, :, :] * jnp.exp(jnp.minimum(first - cum[..., None, :, :], 0.0))
    below = _mm("...ad,...jd->...aj", rows, keys, dtype)  # (.., I, 2 sub, chunk)
    block_of = jnp.arange(chunk) // sub
    earlier = block_of[None, None, :] < jnp.arange(blocks)[:, None, None]
    below = jnp.where(earlier, below, 0.0)
    # on the diagonal blocks: exp(G_a - G_b) a channel, a >= b
    a_ge_b = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where(a_ge_b[..., None], gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    kk = jnp.sum(kb[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
    qk = jnp.sum(qb[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
    eye = jnp.eye(blocks, dtype=jnp.float32)[:, None, :, None]  # (I, 1, J, 1)

    def square(off, diag):
        """The chunk's ``(C, C)`` matrix from the rows below the diagonal
        blocks and the diagonal blocks."""
        full = off.reshape(*lead, blocks, sub, blocks, sub) + diag[..., :, :, None, :] * eye
        return full.reshape(*lead, chunk, chunk)

    strictly = jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :]
    return jnp.where(strictly, square(below[..., :sub, :], kk), 0.0), square(below[..., sub:, :], qk)


def _prepare(q, k, v, g, beta, chunk: int):
    """What the recurrence needs of every chunk, none of it depending on the
    state: ``(q exp(G), M, W, U_v, k exp(G_C - G), exp(G_C))`` with a chunk
    axis before the token axis."""
    *lead, length, dk = k.shape
    n = length // chunk

    def chunks(t):
        return t.reshape(*lead, n, chunk, t.shape[-1])

    qc, kc, vc = (chunks(t).astype(jnp.float32) for t in (q, k, v))
    bc = beta.astype(jnp.float32).reshape(*lead, n, chunk)
    cum = jnp.cumsum(chunks(g).astype(jnp.float32), axis=-2)  # G, inclusive
    # rematerialised: its transpose then needs q, k and G and not the sub-blocks'
    # exp(G_a - G_b) a channel, sixteen times the size of k
    a, m = jax.checkpoint(functools.partial(_within, sub=min(_SUB, chunk), dtype=q.dtype))(qc, kc, cum)
    # (I + A) [W, U_v] = beta * [k exp(G), v]
    rhs = jnp.concatenate([kc * jnp.exp(cum), vc], axis=-1) * bc[..., None]
    solved = jax.lax.linalg.triangular_solve(
        a * bc[..., None], rhs, left_side=True, lower=True, unit_diagonal=True)
    w, uv = solved[..., :dk], solved[..., dk:]
    last = cum[..., -1:, :]
    # the recurrence's product operands in the dtype its products take them in
    low = lambda t: t.astype(q.dtype)  # noqa: E731
    return low(qc * jnp.exp(cum)), low(m), low(w), uv, low(kc * jnp.exp(last - cum)), jnp.exp(last[..., 0, :])


# ---------------------------------------------------------------------- #
# the same chunk-local mathematics one chunk at a time, in forms Mosaic
# lowers: 2-D tiles, static slices, selects over iotas, products
# ---------------------------------------------------------------------- #
def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot(a, b, contract=((1,), (0,)), precision=_HI):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _pick(x, i: int, rows: int):
    return x[i:i + 1, :]


_pick.defvjp(lambda x, i, rows: (x[i:i + 1, :], None),
             lambda i, rows, _, ct: (jnp.where(_iota((rows, ct.shape[1]), 0) == i, ct, 0.0),))


def _row(x, i: int):
    """Row ``i`` of ``x`` as ``(1, d)``.  Its transpose is a select over the
    rows, where a slice's would be a pad at an unaligned row."""
    return _pick(x, i, x.shape[0])


def _block_rows(x, b: int):
    """``x``'s row ``b`` of every sub-block, over the rows of its sub-block."""
    return jnp.concatenate([jnp.broadcast_to(x[lo + b:lo + b + 1, :], (_SUB, x.shape[1]))
                            for lo in range(0, x.shape[0], _SUB)], axis=0)


def _block_sums(x):
    """Every sub-block's sum over its rows, over the rows of the sub-block."""
    return jnp.concatenate([jnp.broadcast_to(jnp.sum(x[lo:lo + _SUB, :], axis=0, keepdims=True), (_SUB, x.shape[1]))
                            for lo in range(0, x.shape[0], _SUB)], axis=0)


def _diagonal_pass(q, k, cum, pull=None):
    """The sub-blocks on the diagonal, a column ``b`` of all of them at a
    time: ``exp(G_a - G_b)`` a channel for the rows ``a >= b`` of ``b``'s
    sub-block, summed over the channels against ``k_a k_b`` and ``q_a k_b``.
    With ``pull = (d_kk, d_qk)`` the same pass backwards, which takes the
    exponentials again where autodiff would keep all ``_SUB`` of them."""
    size, d = k.shape
    within = _iota((size, d), 0) % _SUB
    col = _iota((size, size), 1) % _SUB
    same = _iota((size, size), 0) // _SUB == _iota((size, size), 1) // _SUB
    if pull is None:
        kk = qk = jnp.zeros((size, size), jnp.float32)
    else:
        d_kk, d_qk = (jnp.where(same, t, 0.0) for t in pull)
        d_q = d_k = d_cum = rows_k = rows_cum = jnp.zeros((size, d), jnp.float32)
    for b in range(_SUB):
        gap = cum - _block_rows(cum, b)
        decay = jnp.exp(jnp.where(within >= b, gap, -jnp.inf) if b else gap)
        ek = decay * _block_rows(k, b)
        if pull is None:
            kk = jnp.where(col == b, jnp.sum(k * ek, axis=1, keepdims=True), kk)
            qk = jnp.where(col == b, jnp.sum(q * ek, axis=1, keepdims=True), qk)
            continue
        c_k = jnp.sum(jnp.where(col == b, d_kk, 0.0), axis=1, keepdims=True)
        c_q = jnp.sum(jnp.where(col == b, d_qk, 0.0), axis=1, keepdims=True)
        d_ek = c_k * k + c_q * q
        d_gap = d_ek * ek
        d_q, d_k, d_cum = d_q + c_q * ek, d_k + c_k * ek, d_cum + d_gap
        # what row b of each sub-block gets back from the rows it was spread over
        rows_k = jnp.where(within == b, _block_sums(d_ek * decay), rows_k)
        rows_cum = jnp.where(within == b, _block_sums(d_gap), rows_cum)
    if pull is None:
        return jnp.where(same, kk, 0.0), jnp.where(same, qk, 0.0)
    return d_q, d_k + rows_k, d_cum - rows_cum


@jax.custom_vjp
def _diagonal(q, k, cum):
    return _diagonal_pass(q, k, cum)


_diagonal.defvjp(lambda q, k, cum: (_diagonal_pass(q, k, cum), (q, k, cum)),
                 lambda res, cts: _diagonal_pass(*res, pull=cts))


def _inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` whose side is a
    power of two, float32: the inverses of the diagonal blocks of side ``s``
    give those of side ``2 s``, ``[[T1, 0], [-T2 a21 T1, T2]]``, from
    ``s = 1`` (the identity) up."""
    size = a.shape[0]
    row, col = _iota(a.shape, 0), _iota(a.shape, 1)
    inv = jnp.where(row == col, 1.0, 0.0) - jnp.where(row // 2 == col // 2, a, 0.0)
    side = 2
    while side < size:
        pair = (row // (2 * side) == col // (2 * side)) & (row // side != col // side)
        inv = inv - _dot(_dot(inv, jnp.where(pair, a, 0.0)), inv)
        side *= 2
    return inv


@jax.custom_vjp
def _solve(a, rhs, inv):
    """``(I + a)^-1 rhs`` with ``inv = (I + a)^-1`` given (no cotangent goes
    to it: ``a``'s is ``-T^T (ct rhs^T) T^T = -(T^T ct) (T rhs)^T``)."""
    return _dot(inv, rhs)


def _solve_fwd(a, rhs, inv):
    out = _dot(inv, rhs)
    return out, (inv, out)


def _solve_bwd(res, ct):
    inv, out = res
    d_rhs = _dot(inv, ct, ((0,), (0,)))
    return -_dot(d_rhs, out, ((1,), (1,))), d_rhs, jnp.zeros_like(inv)


_solve.defvjp(_solve_fwd, _solve_bwd)


def _chunk_parts(q, k, v, g, beta, inv=None):
    """``_prepare`` of one chunk: ``q, k, g (C, d_k)``, ``v (C, d_v)``,
    ``beta (1, C)`` to ``(q exp(G), M, W, U_v, k exp(G_C - G), exp(G_C)
    (1, d_k))`` and ``T = (I + A)^-1``, the same numbers by the same
    sub-blocks, with every intermediate a value of the kernel that calls it.
    ``inv``, where given, is ``T`` from an earlier call."""
    size, dk = k.shape
    dtype = q.dtype
    # products of operands the caller brought in float32 stay float32
    precision = _HI if dtype == jnp.float32 else None
    qf, kf, vf, g = (t.astype(jnp.float32) for t in (q, k, v, g))
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    cum = _dot(jnp.where(row >= col, 1.0, 0.0), g)  # G, inclusive
    beta = jnp.sum(jnp.where(row == col, beta.astype(jnp.float32), 0.0), axis=1, keepdims=True)  # (C, 1)
    firsts = [_row(cum, lo) for lo in range(0, size, _SUB)]  # r_I
    fall = jnp.exp(cum - jnp.concatenate([jnp.broadcast_to(r, (_SUB, dk)) for r in firsts], axis=0))
    rows = jnp.concatenate([kf * fall, qf * fall], axis=0).astype(dtype)
    kk, qk = _diagonal(qf, kf, cum)
    for block, first in enumerate(firsts[1:], 1):
        keys = (kf * jnp.exp(jnp.minimum(first - cum, 0.0))).astype(dtype)
        below = _dot(rows, keys, ((1,), (1,)), precision)  # (2 C, C)
        here = (row // _SUB == block) & (col // _SUB < block)
        kk, qk = jnp.where(here, below[:size], kk), jnp.where(here, below[size:], qk)
    # (I + A) [W, U_v] = beta * [k exp(G), v]
    a = jnp.where(row > col, kk, 0.0) * beta
    if inv is None:
        inv = jax.lax.stop_gradient(_inverse(a))
    solved = _solve(a, jnp.concatenate([kf * jnp.exp(cum), vf], axis=1) * beta, inv)
    last = _row(cum, size - 1)
    return ((qf * jnp.exp(cum)).astype(dtype), qk.astype(dtype), solved[:, :dk].astype(dtype), solved[:, dk:],
            (kf * jnp.exp(last - cum)).astype(dtype), jnp.exp(last), inv)


def _forward_kernel(*refs):
    """A tile of whole chunks of one head, ``(tile, C, width)`` each operand:
    the five inputs in, the parts out (``T`` among them where the backward
    kernel is to have it).  The chunks go through ``_chunk_parts`` together:
    each one's float32 products hang on one another, and the next chunk's
    fill the units meanwhile."""
    for ref, part in zip(refs[5:], jax.vmap(_chunk_parts)(*(ref[0] for ref in refs[:5]))):
        ref[0] = part


def _backward_kernel(*refs):
    """The five inputs, ``T`` and the six parts' cotangents in, the inputs'
    cotangents out: the tile's forward again but for ``T``, and ``jax.vjp`` of
    it, traced into the body."""
    *in_refs, inv_ref = refs[:6]
    _, pull = jax.vjp(lambda *a: jax.vmap(_chunk_parts)(*a, inv_ref[0])[:6],
                      *(ref[0] for ref in in_refs))
    for ref, d in zip(refs[12:], pull(tuple(ref[0] for ref in refs[6:12]))):
        ref[0] = d


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _grid_call(kernel, outs, tile: int, interpret: bool, *operands):
    """``kernel`` over the grid ``(sequences, tiles of `tile` chunks)``.  Every
    operand is ``(sequences, n, rows, width)``, a chunk's rows last (one row
    for ``beta`` and ``exp(G_C)``: Mosaic wants the last two dims of a block
    multiples of (8, 128) or whole, which a row a chunk is only with an axis
    of one before it); ``outs`` are ``(rows, width, dtype)`` of the results.
    A program of its own, so that the layers of a model (and a layer's
    forward, recomputed forward and backward) trace and lower each kernel
    once: 2.7 s a layer otherwise, in every start over a warm compile cache."""
    count, n = operands[0].shape[:2]
    spec = lambda rows, width: pl.BlockSpec((1, tile, rows, width), lambda b, i: (b, i, 0, 0))  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(count, n // tile),
        in_specs=[spec(*t.shape[2:]) for t in operands],
        out_specs=[spec(rows, width) for rows, width, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((count, n, rows, width), dtype) for rows, width, dtype in outs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)


def _tiled_call(kernel, operands, outs, tile: int, interpret: bool):
    """``_grid_call``, a shard of the sequences a chip where the program spans
    several: a Mosaic kernel is not partitioned for it."""
    call = functools.partial(_grid_call, kernel, tuple(outs), tile, interpret)
    mesh = _kernel_mesh(operands[0])
    return (call if mesh is None else _per_shard(call, mesh, len(operands)))(*operands)


def _by_chunk(t, lead: int, chunk: int):
    """``(*lead, S, width)`` or ``(*lead, S)`` as ``(sequences, n, chunk, width)`` or ``(sequences, n, 1, chunk)``."""
    tail = (chunk, t.shape[-1]) if t.ndim > lead + 1 else (1, chunk)
    return t.reshape(-1, t.shape[lead] // chunk, *tail)


def _prepare_kernel(q, k, v, g, beta, chunk: int, tile: int, keep_inverse: bool = False):
    """``_prepare`` by the forward kernel, ``tile`` chunks a grid step; with
    ``keep_inverse`` also ``T`` of every chunk, ``(sequences, n, C, C)``, for
    ``_pull_kernel``."""
    lead, dk, dv, dtype = q.shape[:-2], q.shape[-1], v.shape[-1], q.dtype
    outs = [(chunk, dk, dtype), (chunk, chunk, dtype), (chunk, dk, dtype), (chunk, dv, jnp.float32),
            (chunk, dk, dtype), (1, dk, jnp.float32)] + [(chunk, chunk, jnp.float32)] * keep_inverse
    outs = [(rows, width, jnp.dtype(dt)) for rows, width, dt in outs]
    found = _tiled_call(_forward_kernel, [_by_chunk(t, len(lead), chunk) for t in (q, k, v, g, beta)],
                        outs, tile, platform_of(q) != "tpu")
    parts = (*(t.reshape(*lead, *t.shape[1:]) for t in found[:5]), found[5].reshape(*lead, -1, dk))
    return (parts, found[6]) if keep_inverse else parts


def _pull_kernel(inputs, inverse, d_parts, chunk: int, tile: int):
    """The cotangents of the five inputs from those of the six parts, by the
    backward kernel."""
    lead = inputs[0].ndim - 2
    flat = lambda t: t.reshape(-1, *t.shape[lead:])  # noqa: E731
    operands = (*(_by_chunk(t, lead, chunk) for t in inputs), inverse,
                *map(flat, d_parts[:5]), flat(d_parts[5])[:, :, None, :])
    outs = [(*t.shape[2:], t.dtype) for t in operands[:5]]
    return tuple(d.reshape(t.shape) for d, t in zip(
        _tiled_call(_backward_kernel, operands, outs, tile, platform_of(inputs[0]) != "tpu"), inputs))


def _step(state, parts, dtype):
    """One chunk of the recurrence: ``(S_C, o)``."""
    qg, m, w, uv, kend, dend = parts
    u = uv - _mm("...cd,...dv->...cv", w, state, dtype)
    o = _mm("...cd,...dv->...cv", qg, state, dtype) + _mm("...ab,...bv->...av", m, u, dtype)
    new = dend[..., None] * state + _mm("...cd,...cv->...dv", kend, u, dtype)
    return new, o


def _chunk_first(tree, lead: int):
    return jax.tree.map(lambda t: jnp.moveaxis(t, lead, 0), tree)


def _recur(parts, dtype, chunk_step=_step):
    """``(o by chunk, final state, state at the start of each chunk)``;
    ``chunk_step`` is one chunk of the recurrence (another operator's, whose
    parts hold ``q``'s width first and ``v``'s fourth, as these do)."""
    lead = parts[0].ndim - 3
    shape = parts[0].shape[:lead] + (parts[0].shape[-1], parts[3].shape[-1])

    def step(state, chunk_parts):
        new, o = chunk_step(state, chunk_parts, dtype)
        return new, (o, state)

    final, (o, starts) = jax.lax.scan(step, jnp.zeros(shape, jnp.float32), _chunk_first(parts, lead))
    return jnp.moveaxis(o, 0, lead), final, starts


def _recur_transposed(parts, starts, d_o, d_final, dtype, chunk_step=_step):
    """Cotangents of ``parts``: the recurrence backwards, a chunk at a time,
    each chunk's step recomputed from the state it started with."""
    lead = parts[0].ndim - 3

    def back(d_state, xs):
        chunk_parts, start, d_out = xs
        _, pull = jax.vjp(lambda s, p: chunk_step(s, p, dtype), start, chunk_parts)
        return pull((d_state, d_out))

    _, d_parts = jax.lax.scan(
        back, d_final, (_chunk_first(parts, lead), starts, jnp.moveaxis(d_o, lead, 0)), reverse=True)
    return jax.tree.map(lambda t: jnp.moveaxis(t, 0, lead), d_parts)


def _forward_all(q, k, v, g, beta, chunk, tile):
    """``((o, final state), state at the start of each chunk)``, every leading
    axis at once."""
    with jax.named_scope("ht.kda.prepare"):
        parts = _prepare_kernel(q, k, v, g, beta, chunk, tile) if tile else _prepare(q, k, v, g, beta, chunk)
    with jax.named_scope("ht.kda.recur"):
        o, final, starts = _recur(parts, q.dtype)
    return (o.reshape(v.shape).astype(v.dtype), final), starts


def _backward_all(chunk, tile, inputs, starts, d_o, d_final):
    """The five inputs' cotangents: the chunks' parts again, the recurrence
    backwards from the kept states, and back through the chunk-local part
    (the backward kernel, which makes the parts once more in VMEM around the
    forward kernel's ``T``, or ``jax.vjp`` of ``_prepare``)."""
    with jax.named_scope("ht.kda.prepare"):
        if tile:
            parts, inverse = _prepare_kernel(*inputs, chunk, tile, keep_inverse=True)
        else:
            parts, pull = jax.vjp(functools.partial(_prepare, chunk=chunk), *inputs)
    with jax.named_scope("ht.kda.recur"):
        d_o = d_o.astype(jnp.float32).reshape(parts[3].shape)
        d_parts = _recur_transposed(parts, starts, d_o, d_final.astype(jnp.float32), inputs[0].dtype)
    with jax.named_scope("ht.kda.prepare"):
        if not tile:
            return pull(d_parts)
        width = max(inputs[0].shape[-1], inputs[2].shape[-1])
        return _pull_kernel(inputs, inverse, d_parts, chunk, _backward_tile(tile, chunk, width))


def _forward(q, k, v, g, beta, chunk, tile):
    # ``_prepare``'s arrays are several times the inputs' size: with more than one
    # leading axis (batch, heads) the first is walked one entry at a time.  The
    # kernels' parts are not (the whole step's temporaries are the same 5.78 GiB
    # either way, compiled for a v5e), and one scan over all sequences' heads is
    # shorter than one a sequence (57.9 against 68.6 ms, two sequences)
    if q.ndim < 4 or tile:
        return _forward_all(q, k, v, g, beta, chunk, tile)
    return jax.lax.map(lambda t: _forward_all(*t, chunk, tile), (q, k, v, g, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunk_kda(q, k, v, g, beta, chunk, tile):
    return _forward(q, k, v, g, beta, chunk, tile)[0]


def _fwd(q, k, v, g, beta, chunk, tile):
    out, starts = _forward(q, k, v, g, beta, chunk, tile)
    return out, (q, k, v, g, beta, starts)


def _bwd(chunk, tile, res, cotangents):
    *inputs, starts = res
    if inputs[0].ndim < 4 or tile:
        return _backward_all(chunk, tile, inputs, starts, *cotangents)
    return jax.lax.map(lambda t: _backward_all(chunk, tile, t[0], *t[1:]), (inputs, starts, *cotangents))


_chunk_kda.defvjp(_fwd, _bwd)


def _backward_tile(tile: int, chunk: int, width: int) -> int:
    """Chunks a grid step of the backward kernel: the most that ``_ROWS``
    allows and that divides the forward's."""
    most = max(1, _ROWS[1] * 128 // (chunk * width))
    return max(t for t in range(1, most + 1) if tile % t == 0)


def _pallas_gate(q, v, chunk: int) -> int:
    """Chunks a grid step of the forward kernel, 0 for the XLA form of
    ``_prepare``: the kernels on a TPU and, at test scale, under the
    interpreter on a CPU, where the shapes are theirs (heads of whole lane
    tiles; a chunk of whole sub-blocks whose count is a power of two, up to
    128 tokens: the ``(C, C)`` matrices of 256 pass the scoped VMEM; across
    chips as many sequences as divide among them)."""
    platform = platform_of(q)
    *lead, length, dk = q.shape
    width = max(dk, v.shape[-1])
    mesh = _kernel_mesh(q)
    fits = (dk % 128 == 0 and v.shape[-1] % 128 == 0 and chunk % _SUB == 0 and chunk & (chunk - 1) == 0 and chunk <= 128
            and (mesh is None or math.prod(lead) % mesh.size == 0))
    if fits and (platform == "tpu" or (platform == "cpu" and length <= 512)):
        return max(1, min(_ROWS[0] * 128 // (chunk * width), -(-length // chunk)))
    return 0


def chunk_kda(q, k, v, g, beta, *, chunk: int = 64):
    """Kimi Delta Attention over whole sequences, ``chunk`` tokens at a time.

    ``q, k``: ``(..., S, d_k)``; ``v``: ``(..., S, d_v)``; ``g``: ``(..., S,
    d_k)``, the log-decay (``<= 0``) of each key channel; ``beta``: ``(...,
    S)``.  Leading axes (batch, heads) are independent sequences, each from
    a zero state.  ``q`` and ``k`` come normalised and scaled as the model
    wants them.  Returns ``(o (..., S, d_v) in v's dtype, the final state
    (..., d_k, d_v) in float32)``.  A length that is no multiple of
    ``chunk`` (of the kernels' tile of chunks, where they run:
    ``_pallas_gate``) is padded with tokens that write nothing and decay
    nothing.
    """
    length = q.shape[-2]
    tile = _pallas_gate(q, v, chunk)
    path_counts["pallas" if tile else "dense"] += 1
    pad = -length % (chunk * max(tile, 1))
    if pad:
        widths = [(0, 0)] * (q.ndim - 2) + [(0, pad), (0, 0)]
        q, k, v, g = (jnp.pad(t, widths) for t in (q, k, v, g))
        beta = jnp.pad(beta, widths[:-1])
    with jax.named_scope("ht.kda"):
        o, final = _chunk_kda(q, k, v, g, beta, chunk, tile)
    return (o[..., :length, :] if pad else o), final
