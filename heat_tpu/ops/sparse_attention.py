"""Block-sparse causal attention with a selection learned from the data
(InfLLM-v2, the sparse layers of MiniCPM4 and MiniCPM-SALA).

A query token attends the keys of ``topk`` blocks of ``block_size`` keys, the
same blocks for every query head of a key/value group, chosen by how much
attention the group gives the blocks' compressed keys:

    K^c_j     = mean(K[stride j : stride j + kernel])          (compressed keys)
    p_h(i, j) = softmax_j(q_h(i) . K^c_j / sqrt(d))  over the j with
                stride j + kernel - 1 <= i                     (wholly in the past)
    P(i, j)   = sum_h p_h(i, j)                                (over the group)
    score(i, m) = max of P(i, j) over the j whose keys overlap block m

The first ``init_blocks`` blocks and the blocks that hold positions ``i -
window + 1 .. i`` are always kept, blocks after ``i``'s never; the rest of the
``topk`` are the highest scores (ties: the lower block), and where fewer than
``topk`` blocks lie at or before ``i`` all of them are kept.  Token ``i`` then
takes softmax attention over the kept blocks' keys at or before ``i``.  The
selection is a ``stop_gradient``: no gradient reaches ``q`` or ``k`` through
it.  Sequences of ``dense_len`` tokens or fewer take
:func:`~heat_tpu.ops.flash_attention.flash_attention_gqa` (causal, dense).

The selection (``select``, under the scope ``ht.sparse_attention.select``) is
plain XLA, ``_SELECT_ROWS`` query rows at a time, scores in float32 from
operands in ``q``'s dtype.  It returns each token's blocks and, for each tile
of ``_TOKENS`` consecutive tokens, the sorted union of its tokens' blocks and
its size.  The attention (under ``ht.sparse_attention``) is a Pallas kernel
over tiles of ``_TOKENS`` tokens times a group's query heads, rows token-major:
a tile visits the blocks of its union one by one, through the flash kernels'
online-softmax step (``flash_attention._online_update``), and a row masks the
blocks its token did not choose and the keys after it.  So forward and
backward equal per-token selection exactly.  A key/value head's keys and
values are held whole in VMEM (16 MiB at 32,768 bfloat16 tokens of 128), so a
visited block is a dynamic slice there.  The unions' lengths are
scalar-prefetch operands; the unions themselves stay in HBM and each tile
copies its own into SMEM (all of them, 8 MiB at 32,768 tokens, are more than
the 1 MiB of a v5e's SMEM).  The backward is one sweep over the same tiles
(as ``flash_attention``'s fused backward): each visited block's ``P`` and
``dS`` are made once, dQ goes out with its tile, dK and dV stay whole in VMEM
in float32 until the head's last tile.

The forward's output and log-sum-exp and the selection carry the names in
``KEPT``: a block checkpoint that saves them (``nn.models._remat_jit``) runs
neither the selection nor the forward kernel again in the backward.

Off the TPU the kernels run in the interpreter at test scale (up to
``_INTERPRET_MAX`` tokens) and a dense masked form beyond.  On the TPU there is
no second form: heads that are not whole lane tiles, or a length whose keys,
values and their float32 gradients do not fit ``_VMEM``, are a ``ValueError``
(a dense form needs an ``(S, S)`` mask at exactly those lengths).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.devices import platform_of
from .flash_attention import _dense_attention, _finalize, _kernel_mesh, _online_update, _per_shard

__all__ = ["SparseConfig", "sparse_attention", "select"]

_TOKENS = 8  # query tokens a kernel tile (times a group's heads: its rows)
_SELECT_ROWS = 1024  # query rows whose scores against the compressed keys exist at a time
_INTERPRET_MAX = 512  # longest sequence the CPU interpreter takes
_VMEM = 96 * 2**20  # the kernels' VMEM limit; a head's K, V, dK and dV take at most 2/3 of it

# the names of the residuals a block checkpoint keeps: the forward's output and
# log-sum-exp, and the selection
KEPT = ("ht.sparse.out", "ht.sparse.lse", "ht.sparse.select")

# engagement counter, flash attention's contract: which path a call took,
# counted at trace time
path_counts = {"pallas": 0, "dense": 0, "flash": 0}


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """The selection's sizes (MiniCPM4's ``sparse_config`` names): compressed
    keys of ``kernel_size`` positions every ``kernel_stride``, blocks of
    ``block_size``, ``init_blocks`` first blocks and a local ``window_size``
    always kept, ``topk`` blocks a token, dense up to ``dense_len`` tokens."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size % self.kernel_stride or self.block_size % self.kernel_stride:
            raise ValueError("kernel_size and block_size must be multiples of kernel_stride")


def _compressed(k, cfg: SparseConfig):
    """``(..., S, d)`` keys to their ``(S - kernel) // stride + 1`` compressed
    keys, float32."""
    *lead, length, d = k.shape
    stride, span = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    n = (length - cfg.kernel_size) // stride + 1
    parts = k.astype(jnp.float32).reshape(*lead, length // stride, stride, d).sum(axis=-2)
    return sum(parts[..., o:o + n, :] for o in range(span)) / cfg.kernel_size


def _block_scores(q, rows, kc, cfg: SparseConfig, blocks: int):
    """``score(i, m)`` of the query rows at positions ``rows`` (R,): ``q``
    (..., g, R, d), ``kc`` (..., Nc, d) in ``q``'s dtype; ``(..., R, blocks)``
    float32, before the forced and the future blocks are marked."""
    d = q.shape[-1]
    s = jnp.einsum("...grd,...jd->...grj", q, kc, preferred_element_type=jnp.float32) * d ** -0.5
    n = kc.shape[-2]
    past = (jnp.arange(n) * cfg.kernel_stride + cfg.kernel_size - 1)[None, :] <= rows[:, None]
    s = jnp.where(past, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(past, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    group = jnp.sum(p, axis=-3)  # (..., R, Nc)
    # block m is overlapped by the compressed keys j = m per - lead .. + width - 1
    per, lead = cfg.block_size // cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride - 1
    width = per + lead
    right = blocks * per + width - 1 - lead - n
    padded = jnp.pad(group, [(0, 0)] * (group.ndim - 1) + [(lead, max(right, 0))])
    return functools.reduce(jnp.maximum, [
        jax.lax.slice_in_dim(padded, o, o + (blocks - 1) * per + 1, per, axis=-1) for o in range(width)])


def choose(score, rows, cfg: SparseConfig):
    """Each row's blocks ``(..., R, min(topk, blocks))`` int32, -1 where it
    has fewer: the forced blocks, then the highest ``score`` (ties: the lower
    block), never one after the row."""
    blocks = score.shape[-1]
    m = jnp.arange(blocks)[None, :]
    own = (rows // cfg.block_size)[:, None]
    low = (jnp.maximum(rows - cfg.window_size + 1, 0) // cfg.block_size)[:, None]
    forced = (m < cfg.init_blocks) | (m >= low)
    score = jnp.where(m > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
    vals, idx = jax.lax.top_k(score, min(cfg.topk, blocks))
    return jnp.where(vals > -jnp.inf, idx, -1).astype(jnp.int32)


def kept_pairs(sel, rows, block: int):
    """Pairs of a query and a key that the rows at ``rows`` keep: a whole block
    before the row's own, the row's own block up to the row."""
    start = sel * block
    keys = jnp.clip(rows[:, None] - start + 1, 0, block)
    return jnp.sum(jnp.where(sel >= 0, keys, 0))


def select(q, k, cfg: SparseConfig):
    """The selection, ``stop_gradient``: ``q`` (..., H_kv, g, S, d), ``k``
    (..., H_kv, S, d) to ``(sel (..., H_kv, S, topk), union (..., H_kv, S /
    _TOKENS, blocks), counts (..., H_kv, S / _TOKENS), kept pairs)``: each
    token's blocks (-1: none), each tile's blocks in increasing order (the
    first ``counts`` of the row), the pairs of a query and a key kept in all."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    length = q.shape[-2]
    blocks = length // cfg.block_size
    kc = _compressed(k, cfg).astype(q.dtype)
    step = min(_SELECT_ROWS, length)
    if length % step:
        step = length

    def rows_of(start):
        rows = start + jnp.arange(step)
        qr = jax.lax.dynamic_slice_in_dim(q, start, step, axis=-2)
        sel = choose(_block_scores(qr, rows, kc, cfg, blocks), rows, cfg)
        chosen = jnp.any(sel[..., None] == jnp.arange(blocks), axis=-2)  # (..., R, blocks)
        tiles = chosen.reshape(*chosen.shape[:-2], step // _TOKENS, _TOKENS, blocks).any(axis=-2)
        m = jnp.arange(blocks)
        union = jnp.sort(jnp.where(tiles, m, blocks + m), axis=-1).astype(jnp.int32)
        return sel, union, jnp.sum(tiles, axis=-1, dtype=jnp.int32), kept_pairs(sel, rows, cfg.block_size)

    sel, union, counts, kept = jax.lax.map(rows_of, jnp.arange(0, length, step))
    back = lambda t: jnp.moveaxis(t, 0, -3).reshape(*t.shape[1:-2], -1, t.shape[-1])  # noqa: E731
    return back(sel), back(union), back(counts[..., None])[..., 0], jnp.sum(kept)


# ---------------------------------------------------------------------- #
# the kernels: a tile of _TOKENS tokens x g heads, rows token-major
# ---------------------------------------------------------------------- #
def _tile_masks(sel_ref, tile, rows: int, block: int, g: int):
    """``(the rows' blocks (rows, topk), the rows' positions, the keys' offsets
    in a block)``: a token's row of ``sel`` for each of its ``g`` heads."""
    sel = sel_ref[0]
    tokens, k = sel.shape
    sel_rows = jnp.broadcast_to(sel[:, None, :], (tokens, g, k)).reshape(rows, k)
    qpos = tile * tokens + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0) // g
    kofs = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    return sel_rows, qpos, kofs


def _scores(q, kb, u, sel_rows, qpos, kofs, scale: float, block: int):
    """A row's scores against block ``u``, ``-inf`` where its token did not
    choose the block or the key lies after it."""
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    hit = jnp.max(jnp.where(sel_rows == u, 1.0, 0.0), axis=1, keepdims=True) > 0
    return jnp.where(hit & (u * block + kofs <= qpos), s, -jnp.inf)


def _load(b, tile, union_hbm, ids, sems, whole=()):
    """Copy the tile's union into SMEM and, at a head's first tile, ``whole``
    (pairs of an HBM head and its VMEM scratch)."""
    copies = [pltpu.make_async_copy(union_hbm.at[b, tile], ids, sems.at[0])]

    @pl.when(tile == 0)
    def _():
        for n, (src, dst) in enumerate(whole, 1):
            pltpu.make_async_copy(src.at[b], dst, sems.at[n]).start()
        for n, (src, dst) in enumerate(whole, 1):
            pltpu.make_async_copy(src.at[b], dst, sems.at[n]).wait()

    copies[0].start()
    copies[0].wait()


def _fwd_kernel(counts_ref, q_ref, sel_ref, k_hbm, v_hbm, union_hbm, o_ref, lse_ref,
                k_scr, v_scr, ids, m_scr, l_scr, acc_scr, sems, *, scale, block, g, guarded):
    b, tile = pl.program_id(0), pl.program_id(1)
    _load(b, tile, union_hbm, ids, sems, ((k_hbm, k_scr), (v_hbm, v_scr)))
    rows = q_ref.shape[1]
    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    sel_rows, qpos, kofs = _tile_masks(sel_ref, tile, rows, block, g)
    q = q_ref[0]

    def visit(j, carry):
        u = ids[0, j]
        s = _scores(q, k_scr[u], u, sel_rows, qpos, kofs, scale, block)
        # the block holding key 0 comes first and every row keeps it
        _online_update(s, v_scr.at[pl.ds(u, 1)], m_scr, l_scr, acc_scr, guarded=guarded)
        return carry

    jax.lax.fori_loop(0, counts_ref[b * pl.num_programs(1) + tile], visit, 0)
    _finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _bwd_kernel(counts_ref, q_ref, sel_ref, do_ref, o_ref, lse_ref, k_hbm, v_hbm, union_hbm,
                dq_ref, dk_hbm, dv_hbm, k_scr, v_scr, dk_scr, dv_scr, ids, dq_scr, sems,
                *, scale, block, g):
    b, tile = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    _load(b, tile, union_hbm, ids, sems, ((k_hbm, k_scr), (v_hbm, v_scr)))

    @pl.when(tile == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    rows = q_ref.shape[1]
    sel_rows, qpos, kofs = _tile_masks(sel_ref, tile, rows, block, g)
    q, do = q_ref[0], do_ref[0]
    dd = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32), axis=1, keepdims=True)
    lse = lse_ref[0, 0][:, None]
    dq_scr[:] = jnp.zeros_like(dq_scr)

    def visit(j, carry):
        u = ids[0, j]
        kb, vb = k_scr[u], v_scr[u]
        p = jnp.exp(_scores(q, kb, u, sel_rows, qpos, kofs, scale, block) - lse)
        dv_scr[u] += jax.lax.dot_general(  # Pᵀ · dO  (block, dv)
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - dd)  # unscaled: the scale goes on the sums
        dk_scr[u] += jax.lax.dot_general(  # dSᵀ · Q  (block, d)
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dq_scr[:] += jax.lax.dot_general(  # dS · K  (rows, d)
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, counts_ref[b * pl.num_programs(1) + tile], visit, 0)
    dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)

    @pl.when(tile == last)
    def _():
        dk_scr[:] = dk_scr[:] * scale
        out = [pltpu.make_async_copy(dk_scr, dk_hbm.at[b], sems.at[1]),
               pltpu.make_async_copy(dv_scr, dv_hbm.at[b], sems.at[2])]
        for c in out:
            c.start()
        for c in out:
            c.wait()


def _specs(rows, width):
    return pl.BlockSpec((1, rows, width), lambda b, i, *_: (b, i, 0))


def _row_spec(rows):
    return pl.BlockSpec((1, 1, rows), lambda b, i, *_: (b, 0, i))


@functools.partial(jax.jit, static_argnames=("scale", "block", "g", "guarded", "interpret"))
def _fwd_call(q, k, v, sel, union, counts, *, scale, block, g, guarded, interpret):
    """``(out (BH, S g, dv), lse (BH, 1, S g))`` of token-major rows ``q``
    (BH, S g, d); ``k`` and ``v`` (BH, blocks, block, width)."""
    bh, n_rows, d = q.shape
    _, blocks, _, dv = v.shape
    tokens = n_rows // g // union.shape[1]
    rows, topk = tokens * g, sel.shape[-1]
    any_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block=block, g=g, guarded=guarded),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, union.shape[1]),
            in_specs=[_specs(rows, d), _specs(tokens, topk), any_spec, any_spec, any_spec],
            out_specs=[_specs(rows, dv), _row_spec(rows)],
            scratch_shapes=[
                pltpu.VMEM((blocks, block, d), k.dtype), pltpu.VMEM((blocks, block, dv), v.dtype),
                pltpu.SMEM((1, blocks), jnp.int32),
                pltpu.VMEM((rows, 1), jnp.float32), pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, dv), jnp.float32), pltpu.SemaphoreType.DMA((3,))]),
        out_shape=[jax.ShapeDtypeStruct((bh, n_rows, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, n_rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(counts.reshape(-1), q, sel, k, v, union[:, :, None])


@functools.partial(jax.jit, static_argnames=("scale", "block", "g", "interpret"))
def _bwd_call(q, k, v, sel, union, counts, out, lse, do, *, scale, block, g, interpret):
    """``(dq, dk, dv)``, ``dk`` and ``dv`` float32."""
    bh, n_rows, d = q.shape
    _, blocks, _, dv = v.shape
    tokens = n_rows // g // union.shape[1]
    rows, topk = tokens * g, sel.shape[-1]
    any_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, block=block, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, union.shape[1]),
            in_specs=[_specs(rows, d), _specs(tokens, topk), _specs(rows, dv), _specs(rows, dv),
                      _row_spec(rows), any_spec, any_spec, any_spec],
            out_specs=[_specs(rows, d), any_spec, any_spec],
            scratch_shapes=[
                pltpu.VMEM((blocks, block, d), k.dtype), pltpu.VMEM((blocks, block, dv), v.dtype),
                pltpu.VMEM((blocks, block, d), jnp.float32), pltpu.VMEM((blocks, block, dv), jnp.float32),
                pltpu.SMEM((1, blocks), jnp.int32), pltpu.VMEM((rows, d), jnp.float32),
                pltpu.SemaphoreType.DMA((3,))]),
        out_shape=[jax.ShapeDtypeStruct((bh, n_rows, d), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32), jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(counts.reshape(-1), q, sel, do, out, lse, k, v, union[:, :, None])


def _call(fn, *operands, **kw):
    """``fn`` over the leading (batch x key/value heads) axis, a shard a chip
    where the program spans several."""
    call = functools.partial(fn, **kw)
    mesh = _kernel_mesh(operands[0])
    return (call if mesh is None else _per_shard(call, mesh, len(operands)))(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _sparse(q, k, v, sel, union, counts, block, g, guarded, interpret):
    return _sparse_fwd(q, k, v, sel, union, counts, block, g, guarded, interpret)[0]


def _sparse_fwd(q, k, v, sel, union, counts, block, g, guarded, interpret):
    scale = q.shape[-1] ** -0.5
    out, lse = _call(_fwd_call, q, k, v, sel, union, counts, scale=scale, block=block, g=g,
                     guarded=guarded, interpret=interpret)
    out, lse = checkpoint_name(out, KEPT[0]), checkpoint_name(lse, KEPT[1])
    return out, (q, k, v, sel, union, counts, out, lse)


def _sparse_bwd(block, g, guarded, interpret, res, do):
    q, k, v, sel, union, counts, out, lse = res
    dq, dk, dv = _call(_bwd_call, q, k, v, sel, union, counts, out, lse, do,
                       scale=q.shape[-1] ** -0.5, block=block, g=g, interpret=interpret)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None, None


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def _dense_selected(q, k, v, sel, block: int):
    """The same attention in ``jax.numpy``: each row's chosen blocks as a
    mask over the whole sequence (``_dense_attention``)."""
    length = k.shape[-2]
    chosen = jnp.any(sel[..., None] == jnp.arange(length // block), axis=-2)  # (..., H_kv, S, blocks)
    keep = jnp.repeat(chosen, block, axis=-1) & jnp.tril(jnp.ones((length, length), bool))
    bias = jnp.where(keep, 0.0, -jnp.inf)[..., None, :, :]  # over the group's heads
    return _dense_attention(q, k[..., None, :, :], v[..., None, :, :], False, q.shape[-1] ** -0.5,
                            length, bias=bias)


def _use_kernels(q, v, length: int) -> bool:
    """The kernels on the TPU, where they must take the shapes, and in the
    CPU interpreter up to ``_INTERPRET_MAX`` tokens; else the dense form."""
    platform = platform_of(q)
    if platform != "tpu":
        return platform == "cpu" and length <= _INTERPRET_MAX
    d, dv = q.shape[-1], v.shape[-1]
    if d % 128 or dv % 128:
        raise ValueError(f"sparse attention on the TPU takes heads of whole lane tiles (multiples of 128), "
                         f"not {d} and {dv}")
    held = length * (d + dv) * (q.dtype.itemsize + 4)
    if 3 * held > 2 * _VMEM:
        raise ValueError(f"a key/value head of {length} tokens ({held / 2**20:.0f} MiB of keys, values and their "
                         f"float32 gradients) is more than the kernels' VMEM holds ({2 * _VMEM // 3 / 2**20:.0f} MiB)")
    return True


def sparse_attention(q, k, v, cfg: SparseConfig = SparseConfig()):
    """Causal grouped-query attention over the blocks each token selects.

    ``q``: ``(B, H_q, S, d)``; ``k``: ``(B, H_kv, S, d)``; ``v``: ``(B, H_kv,
    S, d_v)``, ``H_q`` a multiple of ``H_kv``.  Returns ``(out (B, H_q, S,
    d_v) in q's dtype, stats)``: ``stats`` holds ``kept_pairs`` (int32, the
    pairs of a query token and a key its selection keeps, summed over the
    key/value heads), ``causal_pairs`` (the same of the causal triangle) and
    ``selection`` (``(B, H_kv, S, topk)`` int32, -1 where a token has fewer
    blocks; absent on the dense path).  Past ``dense_len`` the length must
    be a multiple of ``block_size``.
    """
    b, hq, length, d = q.shape
    hk = k.shape[1]
    g = hq // hk
    triangle = length * (length + 1) // 2 * b * hk
    if length <= cfg.dense_len:
        from .flash_attention import flash_attention_gqa

        path_counts["flash"] += 1
        out = flash_attention_gqa(q, k, v, causal=True)
        return out, {"kept_pairs": jnp.int32(triangle), "causal_pairs": jnp.int32(triangle)}
    if length % cfg.block_size:
        raise ValueError(f"a sparse sequence ({length}) must be a multiple of block_size ({cfg.block_size})")
    grouped = q.reshape(b, hk, g, length, d)
    with jax.named_scope("ht.sparse_attention.select"):
        sel, union, counts, kept = (checkpoint_name(t, KEPT[2]) for t in select(grouped, k, cfg))
    stats = {"kept_pairs": kept, "causal_pairs": jnp.int32(triangle), "selection": sel}
    with jax.named_scope("ht.sparse_attention"):
        if not _use_kernels(q, v, length):
            path_counts["dense"] += 1
            return _dense_selected(grouped, k, v, sel, cfg.block_size).reshape(b, hq, length, -1), stats
        path_counts["pallas"] += 1
        block, dv = cfg.block_size, v.shape[-1]
        # token-major rows: a token's g heads one after the other
        rows = jnp.swapaxes(grouped, 2, 3).reshape(b * hk, length * g, d)
        by_block = lambda t: t.reshape(b * hk, length // block, block, t.shape[-1])  # noqa: E731
        flat = lambda t: t.reshape(b * hk, *t.shape[2:])  # noqa: E731
        out = _sparse(rows, by_block(k), by_block(v), flat(sel), flat(union), flat(counts),
                      block, g, cfg.init_blocks == 0, platform_of(q) != "tpu")
        out = jnp.swapaxes(out.reshape(b, hk, length, g, dv), 2, 3).reshape(b, hq, length, dv)
    return out, stats
