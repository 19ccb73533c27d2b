"""Pallas TPU flash attention for the per-chip (local) attention block.

The framework's attention surface (``nn.MultiheadAttention``,
``parallel.ring_attention``) reduces every shape to dense softmax attention
over a LOCAL block — either the whole sequence on one chip, or one ring
step's (S/p, S/p) tile.  XLA's lowering of the dense form materializes the
(Sq, Sk) score matrix in HBM: at S=8k and f32 that is 256 MiB *per
batch×head*, all of it read back for the softmax and again for the PV GEMM.

This kernel is the classic flash restructure (SURVEY §2.7: Pallas where
XLA's fusion is insufficient — a multi-pass softmax over a materialized
matrix is exactly that case): one grid sweep tiles Q into (blk_q, d) blocks
and streams K/V (blk_k, d) blocks through VMEM, maintaining the online
softmax statistics (m, l) and the output accumulator in VMEM scratch that
persists across the innermost grid dimension.  The score matrix never
exists anywhere; HBM traffic is one read of Q/K/V and one write of O.

Numerics match ``_dense_attention`` (same online-softmax recurrence the
ring uses), including fully-masked rows (0, not NaN) and the top-left
aligned causal convention (torch ``is_causal``).

Dispatch (``_pallas_gate``): the Pallas kernel on TPU, its interpreter on
CPU at test scale, the dense jnp form where the gate says the kernel does
not apply (other platforms, interpreter past test scale, blocks past the
VMEM budget).  A kernel the gate selected either runs or raises — there is
no fallback from a failed kernel to the dense form.

The per-row logsumexp travels between the forward and the backward sweeps
as a ``(B, 1, S)`` array blocked ``(1, 1, blk)``: Mosaic requires the last
two block dims to be multiples of (8, 128) or the full extent, which a
``(B, S)`` array blocked ``(1, blk)`` violates as soon as B > 1.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.devices import get_default_mesh, platform_of

__all__ = ["flash_attention", "flash_attention_block", "flash_attention_gqa"]

# Block shape of the positions-carrying kernels (the ring's building block).
# Blocks are always rounded to a 128 multiple (Mosaic lane alignment).
_BLK_Q = 512
_BLK_K = 512
# Block sides of the static-offset kernels (``_block_shape``): sequences pad
# to a multiple of ``_BLK``, and run ``_BLK_WIDE`` blocks where that pads no
# further.
_BLK = 512
_BLK_WIDE = 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m

# engagement counter, same contract as ring_attention.path_counts: tests and
# chip_smoke.py assert which implementation a call took (counted per call,
# at trace time under an outer jit); "kept" counts the differentiated calls
# whose residuals carry the names in ``KEPT``; "bwd_fused" and
# "bwd_two_sweeps" which backward a static-offset call's gradient took
# (``_fused_bwd_fits``)
path_counts = {"pallas": 0, "dense": 0, "kept": 0, "bwd_fused": 0,
               "bwd_two_sweeps": 0}

# VMEM of the fused backward (``_flash_bwd_fused_kernel``), which holds a
# head's dQ, dK and dV whole in float32: the limit its ``CompilerParams``
# give Mosaic, and what ``_fused_bwd_bytes`` may take of it.  A v5e has 128
# MiB; compiled for one, Trinity-Mini's layer (48 MiB of the three) fits in
# 64 and not in 56
_FUSED_VMEM = 96 * 2**20
_FUSED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_FUSED_VMEM)

# the names of the flash forward's residuals: a checkpoint whose policy saves
# them (``nn/models._remat_jit``) keeps ``out`` and ``lse`` to the backward,
# and the recomputation no longer runs the forward kernel
KEPT = ("ht.flash.out", "ht.flash.lse")


def _dense_attention(q, k, v, causal: bool, scale: float, s_valid: int,
                     bias=None, return_probs: bool = False,
                     window: Optional[int] = None):
    """THE dense softmax path — every non-flash attention route in the
    framework composes into this one function so masked-row semantics can
    never diverge.  ``s_valid`` masks trailing pad *keys* (positions >=
    s_valid never attend); ``bias`` is an optional additive score bias
    (broadcastable to (..., Sq, Sk)) carrying user masks — torch-style
    bool masks should be pre-converted to 0/-inf.  ``window`` keeps of the
    keys a query may see only the nearest ``window`` (query ``i`` sees key
    ``j`` where ``i - j < window``; causal only).

    Fully-masked rows emit 0, and do so DIFFERENTIABLY: the all--inf row is
    sanitized to zeros *before* the softmax (an after-the-fact ``where``
    would leak NaN through the backward pass — 0·NaN = NaN in the vjp)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    Sq, Sk = s.shape[-2], s.shape[-1]
    if bias is not None:
        s = s + bias
    mask = None
    if s_valid < Sk:
        mask = jnp.zeros((Sq, Sk), bool) | (jnp.arange(Sk)[None, :] < s_valid)
    if causal:
        cm = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        if window is not None:
            cm = cm & (jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :] < window)
        mask = cm if mask is None else (mask & cm)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    alive = jnp.isfinite(s).any(axis=-1, keepdims=True)
    s = jnp.where(alive, s, 0.0)  # sanitize BEFORE softmax (NaN-free vjp)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(alive, p, 0.0)
    out = jnp.einsum("...qk,...kd->...qd", p, v)
    return (out, p) if return_probs else out


def _online_update(s, v_ref, m_scr, l_scr, acc_scr, *, guarded: bool):
    """One step of the online-softmax recurrence against the VMEM scratch —
    shared by the static-offset and positions-carrying forward kernels so
    the numerics cannot diverge.  GEMM operands stay in the storage dtype
    (bf16 rides the MXU's native input type); accumulation is f32.

    ``guarded`` (static) keeps a row that has met no live key yet (m = -inf)
    free of NaN: the positions-carrying kernels can meet one, and so can a
    static-offset sweep under a window (``_block_starves``).  Without a
    window every row of a static-offset sweep sees key 0 in its first block,
    so m is finite from then on, ``exp(-inf - m)`` is an exact 0 and the
    guards would change no bit."""
    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    if guarded:
        # fully-masked-so-far rows keep m=-inf; exp against a safe 0 stays 0
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    else:
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
    l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=-1)
    # p is cast to v's storage dtype for the PV GEMM (bf16 probabilities
    # against bf16 values — the standard TPU flash layout); f32 accum
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    acc_scr[:] = acc_scr[:] * corr[:, None] + pv
    m_scr[:, 0] = m_new


def _finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    out = acc_scr[:] / jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    # logsumexp per row, for the backward recompute and the cross-block
    # merge.  Zero-mass (fully-masked) rows emit -1e30, NOT log(1e-30):
    # a ~-69 sentinel would act as real probability mass in the ring's
    # logaddexp merge and crush rows whose true logsumexp is below ~-62;
    # exp(s - (-1e30)) still recomputes p = 0 (s is -inf there), and
    # exp(-1e30 - lse') underflows to an exact 0 merge weight
    lse = jnp.where(
        jnp.isfinite(m_scr[:, 0]), m_scr[:, 0], 0.0
    ) + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30))
    lse_ref[0, 0] = jnp.where(l_scr[:, 0] > 0.0, lse, -1e30)


def _block_kind(q_lo, k_lo, blk_q: int, blk_k: int, s_valid: int,
                causal: bool, window: Optional[int] = None):
    """``(live, interior)`` of the score block whose first query row is
    ``q_lo`` and whose first key is ``k_lo`` — decided by position alone, so
    the kernels (traced grid offsets) and the tests (ints) call this one
    function.  *Dead* (not live): every key is pad (``>= s_valid``), under
    ``causal`` in the future of every query row or, under a ``window``, at
    least ``window`` positions in the past of every query row — both GEMMs
    are skipped (the ~2x flop saving that makes causal flash worth it).
    *Interior*: no mask of the block can be false — every key is valid and,
    under ``causal``, at or before every query row and, under a ``window``,
    within it of every query row — so its body runs with no iota, compare
    or select.  *Edge* (live, not interior): the diagonal, the window's
    lower edge or the padding crosses it."""
    live = k_lo < s_valid
    interior = k_lo + blk_k <= s_valid
    if causal:
        live = live & (k_lo <= q_lo + blk_q - 1)
        interior = interior & (k_lo + blk_k - 1 <= q_lo)
    if window is not None:
        live = live & (q_lo - (k_lo + blk_k - 1) < window)
        interior = interior & (q_lo + blk_q - 1 - k_lo < window)
    return live, interior


def _block_starves(q_lo, k_lo, blk_q: int, blk_k: int, s_valid: int,
                   window: int):
    """Whether a row of the block can leave it without having met a key,
    under a ``window``: the block's last row sees nothing at or before the
    block's last key (or the last valid one).  A row's keys are contiguous
    and a sweep meets them in order, so such a row met none in the blocks
    before either, its running maximum is still ``-inf``, and the online
    update needs its guard here and in no other block."""
    return ((q_lo + blk_q - (k_lo + blk_k) >= window)
            | (q_lo + blk_q - s_valid >= window))


def _first_live_k(iq, blk_q: int, blk_k: int, window: int):
    """Index of the first K/V block that a ``window`` lets Q block ``iq``
    see: the one holding key ``q_lo - window + 1``."""
    return jnp.maximum(iq * blk_q - window + 1, 0) // blk_k


def _last_live_q(ik, blk_q: int, blk_k: int, nq: int, window: int):
    """Index of the last Q block that sees K/V block ``ik`` under a
    ``window``: the one holding row ``k_lo + blk_k - 1 + window - 1``."""
    return jnp.minimum((ik * blk_k + blk_k + window - 2) // blk_q, nq - 1)


def _window_steps(n_fixed: int, blk_fixed: int, blk_swept: int,
                  window: int) -> int:
    """Length of a windowed sweep: the most blocks of ``blk_swept`` positions
    that the ``blk_fixed + window - 1`` positions which one of the
    ``n_fixed`` fixed blocks can reach span (``window / blk + 1`` for square
    blocks that divide the window)."""
    return max((i * blk_fixed + blk_fixed - 1) // blk_swept
               - max(i * blk_fixed - window + 1, 0) // blk_swept + 1
               for i in range(n_fixed))


def _block_census(Sp: int, s_valid: int, blk_q: int, blk_k: int,
                  causal: bool, window: Optional[int] = None) -> dict:
    """How many of one head's forward grid steps are interior, edge and
    dead: ``Sp/blk_q x Sp/blk_k`` of them, under a ``window`` the
    ``_window_steps`` K/V blocks from each Q block's first live one.  Shapes
    alone decide it, so this stands in for a run-time counter of the
    mechanism."""
    census = {"interior": 0, "edge": 0, "dead": 0}
    nk = Sp // blk_k
    steps = nk if window is None else _window_steps(Sp // blk_q, blk_q, blk_k,
                                                    window)
    for q_lo in range(0, Sp, blk_q):
        first = 0 if window is None else max(q_lo - window + 1, 0) // blk_k
        for ik in range(first, first + steps):
            live, interior = _block_kind(q_lo, ik * blk_k, blk_q, blk_k,
                                         s_valid, causal, window)
            census["interior" if interior else "edge" if live else "dead"] += 1
    return census


def _on_live_blocks(step, kind, masked: bool, starves=None):
    """Run ``step(with_mask)`` of a static-offset kernel on this grid step's
    block: without the mask arithmetic on an interior block, with it on an
    edge block, not at all on a dead one.  ``masked`` False (static: not
    causal and no pad key) means no block has a mask.  ``starves`` (the
    forward sweep under a window: ``_block_starves``) runs an edge block
    that can starve a row as ``step(True, guarded=True)``."""
    live, interior = kind
    if not masked:
        step(False)
        return
    pl.when(interior)(lambda: step(False))
    edge = live & jnp.logical_not(interior)
    if starves is None:
        pl.when(edge)(lambda: step(True))
        return
    pl.when(edge & jnp.logical_not(starves))(lambda: step(True))
    pl.when(edge & starves)(lambda: step(True, guarded=True))


def _scale_folds(scale: float) -> bool:
    """Whether ``scale`` may ride on the ``(blk, d)`` operand of the score
    product instead of on the ``(blk_q, blk_k)`` scores: only where that
    changes no bit, i.e. ``scale`` is a power of two (``d**-0.5`` for
    d = 16, 64, 256), which every float dtype multiplies exactly."""
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _split_scale(scale: float):
    """``(on the operand, on the scores)``: where ``scale`` goes, the other
    ``None``."""
    return (scale, None) if _scale_folds(scale) else (None, scale)


def _score_operand(ref, scr, scale):
    """Stage the sweep's fixed score operand in ``scr``, times ``scale`` if
    it carries one: once a sweep instead of once a score."""
    x = ref[0]
    scr[:] = x if scale is None else (x * scale).astype(scr.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  qs_scr, *, scale: float, causal: bool, s_valid: int,
                  blk_q: int, blk_k: int, nk: int, masked: bool,
                  window: Optional[int] = None):
    """Forward sweep of one Q block over its K/V blocks: all ``nk`` of them
    or, under a ``window``, the ``nk`` from its first live one on."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    on_operand, on_scores = _split_scale(scale)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        _score_operand(q_ref, qs_scr, on_operand)

    q_lo = iq * blk_q
    if window is None:
        k_lo = ik * blk_k
    else:
        k_lo = (_first_live_k(iq, blk_q, blk_k, window) + ik) * blk_k

    def step(with_mask: bool, guarded: bool = False):
        # s: (blk_q, blk_k) f32 — in VMEM only
        s = _masked_scores(
            qs_scr[:], k_ref[0], scale=on_scores, causal=causal,
            masked=with_mask, s_valid=s_valid, q_lo=q_lo, k_lo=k_lo,
            blk_q=blk_q, blk_k=blk_k, window=window,
        )
        # without a window block 0 comes first and holds key 0, which no row
        # masks: m is finite from the first update on, so no guard.  Under a
        # window the sweep starts at the block its lower edge crosses, whose
        # last rows see no key of it (row q_lo + r sees keys > q_lo + r -
        # window): their m stays -inf and exp(-inf - -inf) is NaN, so the
        # blocks that ``_block_starves`` names, and only those, are guarded
        _online_update(s, v_ref, m_scr, l_scr, acc_scr, guarded=guarded)

    _on_live_blocks(
        step, _block_kind(q_lo, k_lo, blk_q, blk_k, s_valid, causal, window),
        masked,
        None if window is None else _block_starves(q_lo, k_lo, blk_q, blk_k,
                                                   s_valid, window))

    @pl.when(ik == nk - 1)
    def _():
        _finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _masked_scores(q, k, *, scale, causal, masked, s_valid,
                   q_lo, k_lo, blk_q, blk_k, window=None):
    """THE score+mask computation — forward and backward share this one
    definition, so the masking convention can never silently diverge
    between the saved lse and the backward recompute.  ``scale`` None: an
    operand already carries it (``_score_operand``)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if scale is not None:
        s = s * scale
    if masked:
        kv_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = kv_pos < s_valid
        if causal:
            q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            mask = mask & (q_pos >= kv_pos)
            if window is not None:
                mask = mask & (q_pos - kv_pos < window)
        s = jnp.where(mask, s, -jnp.inf)
    return s


def _recompute_p(q, k, lse_row, **kw):
    """Backward-side recompute: p_ij = exp(s_ij - lse_i).  The forward's
    lse is finite on every row (-1e30 on a pad row that a window left
    without a key), so a masked score (-inf) recomputes to an exact 0 with
    no guard."""
    s = _masked_scores(q, k, **kw)
    return jnp.exp(s - lse_row[:, None])


# --------------------------------------------------------------------- #
# positions-carrying block kernels (the ring-attention building block)
#
# The ring rotates K/V blocks between chips, so a block's global key
# positions are DYNAMIC (they depend on lax.axis_index and the ring step)
# — the static q_lo/k_lo offsets of the local kernel above cannot express
# the mask.  These variants take explicit per-row/per-key position vectors
# (q_pos as a (blk,1) column, k_pos as a (1,blk) row — 2-D so Mosaic never
# sees a 1-D iota/relayout) and return (out, lse): normalized block output
# plus the row logsumexp, which is exactly what the cross-block
# merge needs (out = Σ_b out_b · exp(lse_b − lse), lse = logaddexp_b).
# --------------------------------------------------------------------- #


def _masked_scores_pos(q, k, qpos_col, kpos_row, *, scale, causal, masked,
                       s_valid):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if masked:
        mask = jnp.broadcast_to(kpos_row < s_valid, s.shape)
        if causal:
            mask = mask & (qpos_col >= kpos_row)
        s = jnp.where(mask, s, -jnp.inf)
    return s


def _recompute_p_pos(q, k, lse_row, **kw):
    s = _masked_scores_pos(q, k, **kw)
    p = jnp.exp(s - lse_row[:, None])
    return jnp.where(jnp.isfinite(s), p, 0.0)


def _block_live(kpos_row, qpos_col, causal: bool, s_valid: int):
    """Dynamic analogue of the static k_lo/q_lo skip: a tile whose every key
    is pad (>= s_valid) or — under causal — strictly in the future of every
    query row here contributes nothing; skip both GEMMs."""
    live = jnp.min(kpos_row) < s_valid
    if causal:
        live = live & (jnp.min(kpos_row) <= jnp.max(qpos_col))
    return live


def _flash_pos_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr,
                      *, scale: float, causal: bool, s_valid: int,
                      nk: int, masked: bool):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qpos = qpos_ref[...]  # (blk_q, 1) i32
    kpos = kpos_ref[...]  # (1, blk_k) i32
    live = _block_live(kpos, qpos, causal, s_valid) if masked else jnp.bool_(True)

    @pl.when(live)
    def _():
        s = _masked_scores_pos(
            q_ref[0], k_ref[0], qpos, kpos,
            scale=scale, causal=causal, masked=masked, s_valid=s_valid,
        )
        _online_update(s, v_ref, m_scr, l_scr, acc_scr, guarded=True)

    @pl.when(ik == nk - 1)
    def _():
        _finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _flash_pos_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                             qpos_ref, kpos_ref, dq_ref, dq_scr,
                             *, scale, causal, s_valid, nk, masked):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    qpos = qpos_ref[...]
    kpos = kpos_ref[...]
    live = _block_live(kpos, qpos, causal, s_valid) if masked else jnp.bool_(True)

    @pl.when(live)
    def _():
        p = _recompute_p_pos(
            q_ref[0], k_ref[0], lse_ref[0, 0], qpos_col=qpos, kpos_row=kpos,
            scale=scale, causal=causal, masked=masked, s_valid=s_valid,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0, 0][:, None]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_pos_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                              qpos_ref, kpos_ref, dk_ref, dv_ref,
                              dk_scr, dv_scr,
                              *, scale, causal, s_valid, nq, masked):
    iq = pl.program_id(2)  # sweeping Q blocks; K/V block fixed per middle idx

    @pl.when(iq == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qpos = qpos_ref[...]
    kpos = kpos_ref[...]
    live = _block_live(kpos, qpos, causal, s_valid) if masked else jnp.bool_(True)

    @pl.when(live)
    def _():
        p = _recompute_p_pos(
            q_ref[0], k_ref[0], lse_ref[0, 0], qpos_col=qpos, kpos_row=kpos,
            scale=scale, causal=causal, masked=masked, s_valid=s_valid,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0, 0][:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         dq_ref, dq_scr, qs_scr,
                         *, scale, causal, s_valid, blk_q, blk_k, nk, masked,
                         window=None):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    on_operand, on_scores = _split_scale(scale)

    @pl.when(ik == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        _score_operand(q_ref, qs_scr, on_operand)

    q_lo, k_lo = iq * blk_q, ik * blk_k
    if window is not None:  # the sweep starts at the Q block's first live block
        k_lo = (_first_live_k(iq, blk_q, blk_k, window) + ik) * blk_k

    def step(with_mask: bool):
        p = _recompute_p(
            qs_scr[:], k_ref[0], lse_ref[0, 0], scale=on_scores,
            causal=causal, masked=with_mask, s_valid=s_valid, q_lo=q_lo,
            k_lo=k_lo, blk_q=blk_q, blk_k=blk_k, window=window,
        )
        dp = jax.lax.dot_general(  # dOᵢ · Vⱼᵀ  (blk_q, blk_k)
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dS without its scale: that is applied once, to the accumulator
        ds = p * (dp - dd_ref[0, 0][:, None])
        dq_scr[:] += jax.lax.dot_general(  # dSᵢⱼ · Kⱼ
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live_blocks(
        step, _block_kind(q_lo, k_lo, blk_q, blk_k, s_valid, causal, window),
        masked)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, ks_scr,
                          *, scale, causal, s_valid, blk_q, blk_k, nq, masked,
                          nq_inner: int = 0, window=None, n_q_blocks: int = 0):
    """dk/dv accumulation sweep.  ``nq`` is the TOTAL innermost sweep length
    (init at 0, write at nq-1); ``nq_inner`` (default: nq) is the number of
    Q blocks PER head — under GQA the sweep interleaves the g query heads of
    this K/V head's group, so the block offset is the sweep index modulo
    nq_inner while the accumulator runs through all g·nq_inner steps.  Under
    a ``window`` a head's ``nq_inner`` steps start at the K/V block's first
    live Q block and may run past the last of the ``n_q_blocks`` there are."""
    ik = pl.program_id(1)  # fixed K/V block
    raw = pl.program_id(2)  # sweeping Q blocks (x group heads under GQA)
    iq = raw % (nq_inner or nq)
    if window is not None:
        iq = iq + _first_live_q(ik, blk_q, blk_k, causal)
    on_operand, on_scores = _split_scale(scale)

    @pl.when(raw == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        _score_operand(k_ref, ks_scr, on_operand)

    q_lo, k_lo = iq * blk_q, ik * blk_k

    def step(with_mask: bool):
        p = _recompute_p(
            q_ref[0], ks_scr[:], lse_ref[0, 0], scale=on_scores,
            causal=causal, masked=with_mask, s_valid=s_valid, q_lo=q_lo,
            k_lo=k_lo, blk_q=blk_q, blk_k=blk_k, window=window,
        )
        dv_scr[:] += jax.lax.dot_general(  # Pᵀ · dOᵢ  (blk_k, d)
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0, 0][:, None])  # unscaled, as in the dq sweep
        dk_scr[:] += jax.lax.dot_general(  # dSᵀ · Qᵢ  (blk_k, d)
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    kind = _block_kind(q_lo, k_lo, blk_q, blk_k, s_valid, causal, window)
    if window is not None:
        kind = tuple(flag & (iq < n_q_blocks) for flag in kind)
    _on_live_blocks(step, kind, masked)

    @pl.when(raw == nq - 1)
    def _():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                            dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                            ks_scr, *, scale, causal, s_valid, blk, g, nk,
                            q_steps, masked, window=None, n_q_blocks: int = 0):
    """Both backward sweeps as one: for each of a K/V head's ``g`` query
    heads, K/V block ``ik`` fixed and the Q blocks streamed as in
    ``_flash_bwd_dkv_kernel``, each live block's ``P`` and ``dS`` made once
    for all three products.  ``dq_scr`` holds the query head's whole dQ,
    ``dk_scr`` and ``dv_scr`` the K/V head's whole dK and dV, in float32:
    dK/dV take their terms in the dk/dv sweep's order (query head, then Q
    block) and go out after the group's last head; a Q block's dQ rows go
    out at the step that adds their last term, into the output block the
    index map names there."""
    h = pl.program_id(1)  # the query head of the group
    ik = pl.program_id(2)  # fixed K/V block
    i = pl.program_id(3)  # sweeping Q blocks
    iq = i if window is None else _first_live_q(ik, blk, blk, causal) + i
    on_operand, on_scores = _split_scale(scale)

    @pl.when((h == 0) & (ik == 0) & (i == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((ik == 0) & (i == 0))
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(i == 0)
    def _():
        _score_operand(k_ref, ks_scr, on_operand)

    q_lo, k_lo = iq * blk, ik * blk
    rows = pl.ds(pl.multiple_of(q_lo, blk), blk)
    keys = pl.ds(pl.multiple_of(k_lo, blk), blk)

    def step(with_mask: bool):
        p = _recompute_p(
            q_ref[0], ks_scr[:], lse_ref[0, 0], scale=on_scores,
            causal=causal, masked=with_mask, s_valid=s_valid, q_lo=q_lo,
            k_lo=k_lo, blk_q=blk, blk_k=blk, window=window,
        )
        dv_scr[keys, :] += jax.lax.dot_general(  # Pᵀ · dOᵢ  (blk, dv)
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(  # dOᵢ · Vⱼᵀ  (blk, blk)
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0, 0][:, None])  # unscaled: the scale goes on the sums
        dk_scr[keys, :] += jax.lax.dot_general(  # dSᵀ · Qᵢ  (blk, d)
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_scr[rows, :] += jax.lax.dot_general(  # dS · Kⱼ  (blk, d)
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    kind = _block_kind(q_lo, k_lo, blk, blk, s_valid, causal, window)
    if window is not None:
        kind = tuple(flag & (iq < n_q_blocks) for flag in kind)
    _on_live_blocks(step, kind, masked)

    # Q block iq's last term: under causal the diagonal's (a row sees no key
    # after its own, a window or not), else the last K/V block's
    @pl.when(iq == ik if causal else ik == nk - 1)
    def _():
        dq_ref[0] = (dq_scr[rows, :] * scale).astype(dq_ref.dtype)

    @pl.when((h == g - 1) & (i == q_steps - 1))
    def _():
        dk_ref[0] = (dk_scr[keys, :] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[keys, :].astype(dv_ref.dtype)


def _row_dot(a, b):
    """Σ_d aᵢ ⊙ bᵢ in f32 as a ``(B, 1, S)`` row carrier."""
    return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32),
                   axis=-1)[:, None, :]


def _kernel_mesh(q):
    """Mesh to ``shard_map`` a Mosaic kernel on ``q`` over, or None to call
    it directly.  Mosaic kernels cannot be auto-partitioned: inside a jit
    over more than one device jax refuses to lower them unless every mesh
    axis is manual.  So on the TPU a call that is not already inside a
    ``shard_map`` runs the kernel per shard of the leading (batch·heads)
    axis — over ``q``'s own mesh when it is concrete, over the default mesh
    when it is a tracer (same convention as ``platform_of``).  The CPU
    interpreter has no such limit and is called directly."""
    if platform_of(q) != "tpu":
        return None
    if isinstance(q, jax.core.Tracer):
        if jax.sharding.get_abstract_mesh().manual_axes:
            return None
        mesh = get_default_mesh()
    else:
        mesh = getattr(getattr(q, "sharding", None), "mesh", None)
    return mesh if mesh is not None and mesh.size > 1 else None


def _per_shard(call, mesh, n_batched: int):
    """``call`` run per shard of its first ``n_batched`` operands' leading
    axis, split over every axis of ``mesh``; later operands are replicated
    and every output is sharded on its leading axis like the inputs."""
    rows = P(mesh.axis_names)

    def wrapped(*ops):
        specs = tuple(rows if i < n_batched else P() for i in range(len(ops)))
        return jax.shard_map(call, mesh=mesh, in_specs=specs, out_specs=rows,
                             check_vma=False)(*ops)

    return wrapped


def _run_flash_padded(flat_ops, S: int, blk: int, call):
    """THE kernel-dispatch tail shared by the flash entry points: pad the
    sequence axis of the flattened (B, S, d) operands to a block multiple
    and — when the kernel runs per mesh shard (``_kernel_mesh``) — the
    leading axis to a multiple of the mesh size, run ``call`` (a kernel
    failure propagates), count the path, and slice the pad back off."""
    B, Bk = flat_ops[0].shape[0], flat_ops[1].shape[0]
    Sp = _round_up(S, blk)
    mesh = _kernel_mesh(flat_ops[0])
    if mesh is not None:
        call = _per_shard(call, mesh, len(flat_ops))
    Bkp = _round_up(Bk, mesh.size) if mesh is not None else Bk

    def padded(t):
        # Q carries B // Bk rows per K/V row (> 1 under GQA): pad in step
        rows = t.shape[0] // Bk * Bkp
        if rows == t.shape[0] and Sp == S:
            return t
        return jnp.pad(t, ((0, rows - t.shape[0]), (0, Sp - S), (0, 0)))

    out = call(*(padded(t) for t in flat_ops))
    path_counts["pallas"] += 1
    return out if out.shape[:2] == (B, S) else out[:B, :S]


def _block_shape(S: int, d: int, itemsize: int):
    """``(blk_q, blk_k)`` of the static-offset kernels for a sequence of
    ``S`` rows of width ``d``: one block up to ``_BLK`` rows; beyond, square
    blocks of ``_BLK_WIDE`` where ``S`` padded to ``_BLK`` is a multiple of
    it and a row of a block is at most 512 bytes (bfloat16 to d = 256,
    float32 to d = 128: what Mosaic fits into a v5e's scoped VMEM; float32
    at d = 256 it refuses), else of ``_BLK``.  A step's lane reductions, row
    statistics and pipeline turn cost the same whatever the block's width,
    so the wide block halves them per score: at (128, 8192, 64) causal
    bfloat16 on a v5e the forward takes 35.6 ms at 512 x 512, 38.1 at
    1024 x 512, 28.5 at 256 x 1024, 21.7 at 512 x 1024, 22.4 at 512 x 2048
    and 19.8 at 1024 x 1024, the two backward sweeps 35.8 + 27.5,
    30.1 + 24.4, 41.6 + 29.7, 29.6 + 25.0, 30.5 + 25.2 and 28.1 + 22.5
    (PERF.md, PR 31; 1024 x 1024 also won at S = 1024, 4096 and 32768, at
    d = 128 and without ``causal``).  Idempotent under the padding it
    causes, so the kernels recover it from the padded length."""
    r = _round_up(S, 128)
    if r <= _BLK:
        return r, r
    wide = (_round_up(S, _BLK) % _BLK_WIDE == 0
            and itemsize * _round_up(d, 128) <= 512)
    blk = _BLK_WIDE if wide else _BLK
    return blk, blk


def _pallas_gate(q, S: int, d: int, dv: Optional[int] = None):
    """THE kernel-dispatch gate, shared by every flash entry point so the
    platform policy and VMEM budget cannot drift between them.  The
    platform is that of ``q``'s devices (``platform_of``).  CPU runs the
    interpreter (slow): test scale only, like the kmeans kernels'
    16384-row gate.  The VMEM estimate covers Q/K/V/O blocks + scores +
    accumulator in f32; shapes past it take the dense form; ``d`` is the
    wider of the key's and the value's width (``dv``).  Returns
    ``(use_pallas, blk, platform)``, ``blk`` the multiple to pad ``S`` to."""
    platform = platform_of(q)
    use_pallas = platform == "tpu" or (platform == "cpu" and S <= 512)
    d = max(d, dv or d)
    blk_q, blk_k = _block_shape(S, d, q.dtype.itemsize)
    if use_pallas:
        vmem = 4 * (3 * blk_q * d + 2 * blk_k * d + blk_q * blk_k + 2 * blk_q)
        use_pallas = vmem <= 12 * 2**20
    return use_pallas, max(blk_q, blk_k), platform


def _fused_bwd_bytes(Sp: int, d: int, dv: int, blk: int,
                     itemsize: int) -> int:
    """VMEM of the fused backward at a padded length ``Sp`` and blocks of
    ``blk``: a head's dQ, dK and dV (float32) and the staged K operand, the
    blocks in and out double-buffered, and four ``(blk, blk)`` float32
    score temporaries."""
    resident = 4 * Sp * (2 * d + dv) + itemsize * blk * d
    blocks = 2 * itemsize * blk * (5 * d + 3 * dv)
    rows = 2 * 2 * 4 * 8 * blk  # lse and D, a row padded to 8 sublanes
    return resident + blocks + rows + 4 * 4 * blk * blk


def _fused_bwd_fits(Sp: int, d: int, dv: int, itemsize: int) -> bool:
    """THE backward's path: the fused kernel where ``_fused_bwd_bytes`` fits
    ``_FUSED_VMEM``, else the two sweeps (at d = 128 past S = 49,152).
    Shapes alone decide it."""
    blk, _ = _block_shape(Sp, max(d, dv), itemsize)
    return _fused_bwd_bytes(Sp, d, dv, blk, itemsize) <= _FUSED_VMEM


def _blocks_rect(Sq: int, Sk: int):
    blk_q = min(_BLK_Q, _round_up(Sq, 128))
    blk_k = min(_BLK_K, _round_up(Sk, 128))
    return blk_q, blk_k, pl.cdiv(Sq, blk_q), pl.cdiv(Sk, blk_k)


# --------------------------------------------------------------------- #
# positions-carrying block primitive: pallas_call plumbing + custom VJP
# --------------------------------------------------------------------- #


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "s_valid", "masked",
                              "interpret")
)
def _flash_pos_fwd_impl(q, k, v, qpos, kpos, causal: bool, scale: float,
                        s_valid: int, masked: bool, interpret: bool):
    B, Sq, d = q.shape
    Sk = k.shape[1]
    blk_q, blk_k, nq, nk = _blocks_rect(Sq, Sk)
    kernel = functools.partial(
        _flash_pos_kernel, scale=scale, causal=causal, s_valid=s_valid,
        nk=nk, masked=masked,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((blk_q, 1), lambda b, iq, ik: (iq, 0)),
            pl.BlockSpec((1, blk_k), lambda b, iq, ik: (0, ik)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, blk_q), lambda b, iq, ik: (b, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, d), q.dtype),
            jax.ShapeDtypeStruct((B, 1, Sq), jnp.float32),  # logsumexp
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, qpos, kpos)
    return out, lse[:, 0, :]


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "s_valid", "masked",
                              "interpret")
)
def _flash_pos_bwd_impl(q, k, v, qpos, kpos, out, lse, do, glse,
                        causal: bool, scale: float, s_valid: int,
                        masked: bool, interpret: bool):
    B, Sq, d = q.shape
    Sk = k.shape[1]
    blk_q, blk_k, nq, nk = _blocks_rect(Sq, Sk)
    # D_i = Σ_d dOᵢ ⊙ Oᵢ − g_lseᵢ: the lse cotangent folds into the same
    # row term (∂lse/∂s = p, so ds += p·g ≡ ds = p·(dp − (dd − g)))
    dd = _row_dot(do, out) - glse.astype(jnp.float32)[:, None, :]
    lse = lse[:, None, :]

    qspec = pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, blk_q), lambda b, i, j: (b, 0, i))
    qpspec = pl.BlockSpec((blk_q, 1), lambda b, i, j: (i, 0))
    kpspec = pl.BlockSpec((1, blk_k), lambda b, i, j: (0, j))
    dq = pl.pallas_call(
        functools.partial(
            _flash_pos_bwd_dq_kernel, scale=scale, causal=causal,
            s_valid=s_valid, nk=nk, masked=masked,
        ),
        grid=(B, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec,
                  qpspec, kpspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, dd, qpos, kpos)

    # dk/dv sweep: K/V block fixed per middle grid index, Q blocks stream
    qspec2 = pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0))
    rowspec2 = pl.BlockSpec((1, 1, blk_q), lambda b, j, i: (b, 0, i))
    qpspec2 = pl.BlockSpec((blk_q, 1), lambda b, j, i: (i, 0))
    kpspec2 = pl.BlockSpec((1, blk_k), lambda b, j, i: (0, j))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_pos_bwd_dkv_kernel, scale=scale, causal=causal,
            s_valid=s_valid, nq=nq, masked=masked,
        ),
        grid=(B, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2,
                  qpspec2, kpspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sk, d), k.dtype),
            jax.ShapeDtypeStruct((B, Sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, dd, qpos, kpos)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_pos(q, k, v, qpos, kpos, causal: bool, scale: float, s_valid: int,
               masked: bool, interpret: bool):
    return _flash_pos_fwd_impl(q, k, v, qpos, kpos, causal, scale, s_valid,
                               masked, interpret)


def _flash_pos_fwd_rule(q, k, v, qpos, kpos, causal, scale, s_valid, masked,
                        interpret):
    out, lse = _flash_pos_fwd_impl(q, k, v, qpos, kpos, causal, scale,
                                   s_valid, masked, interpret)
    return (out, lse), (q, k, v, qpos, kpos, out, lse)


def _flash_pos_bwd_rule(causal, scale, s_valid, masked, interpret, res, ct):
    q, k, v, qpos, kpos, out, lse = res
    do, glse = ct
    dq, dk, dv = _flash_pos_bwd_impl(q, k, v, qpos, kpos, out, lse, do, glse,
                                     causal, scale, s_valid, masked,
                                     interpret)
    import numpy as _np

    f0 = lambda x: _np.zeros(x.shape, jax.dtypes.float0)  # int positions
    return dq, dk, dv, f0(qpos), f0(kpos)


_flash_pos.defvjp(_flash_pos_fwd_rule, _flash_pos_bwd_rule)


def _dense_block_pos(q, k, v, q_pos, k_pos, causal: bool, scale: float,
                     s_valid: int, masked: bool):
    """jnp reference for the positions block (``impl='dense'``): same masking
    convention and the same finite-lse sentinel for fully-masked rows
    (log(1e-30) ≈ −69 with a zero output row), so the cross-block merge
    treats kernel and reference results identically.  Differentiable via
    plain autodiff (the −inf rows are sanitized before the softmax)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if masked:
        mask = jnp.broadcast_to(k_pos[None, :] < s_valid, s.shape)
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)
    out = out / jnp.maximum(l, 1e-30)[..., None].astype(out.dtype)
    # zero-mass rows emit the -1e30 no-mass sentinel (see _finalize)
    lse = jnp.where(l > 0.0, safe_m + jnp.log(jnp.maximum(l, 1e-30)), -1e30)
    return out.astype(q.dtype), lse


def flash_attention_block(q, k, v, q_pos, k_pos, *, causal: bool,
                          scale: float, s_valid: int, impl: str):
    """One attention block with explicit global positions → ``(out, lse)``.

    ``q``: ``(..., blk_q, d)``; ``k, v``: ``(..., blk_k, d)`` (rectangular
    blocks allowed — cross-attention callers); ``q_pos``/``k_pos``: int32
    ``(blk_q,)``/``(blk_k,)`` GLOBAL positions of the rows/keys.  Keys at
    positions ``>= s_valid`` are pad and never attend; under ``causal`` a
    query at position i attends keys at positions ``<= i``.  Returns the
    normalized block output (q's dtype) and the per-row logsumexp (f32,
    finite even for fully-masked rows — their output row is 0).  ``impl``:
    ``'pallas'`` (TPU kernel), ``'interpret'`` (kernel under the CPU
    interpreter, test scale), ``'dense'`` (the jnp block).  This is ring
    attention's per-step building block; blocks over disjoint key sets
    merge exactly via ``lse = logaddexp(lse_a, lse_b)``,
    ``out = Σ out_b·exp(lse_b − lse)``.
    """
    blk_q, d = q.shape[-2:]
    blk_k = k.shape[-2]
    # positions at/above the pad sentinel (2**30) must never attend, even
    # under the "no pad keys" s_valid of 2**31-1 — cap the comparison point
    s_valid = min(int(s_valid), 2**30)
    masked = bool(causal) or bool(s_valid < 2**30)
    if impl == "dense":
        path_counts["dense"] += 1
        return _dense_block_pos(q, k, v, q_pos, k_pos, causal, scale,
                                s_valid, masked)
    path_counts["pallas"] += 1
    lead = q.shape[:-2]
    B = 1
    for a in lead:
        B *= int(a)
    # pad each side to a multiple of the kernel TILE the grid will use, not
    # just the 128 lane quantum: a 640-row block would otherwise tile at
    # 512 and the second tile would read out-of-bounds rows whose garbage
    # positions the mask cannot reliably kill
    q_p = _round_up(blk_q, min(_BLK_Q, _round_up(blk_q, 128)))
    k_p = _round_up(blk_k, min(_BLK_K, _round_up(blk_k, 128)))
    qf = q.reshape((B, blk_q, d))
    kf = k.reshape((B, blk_k, d))
    vf = v.reshape((B, blk_k, d))
    qpos = q_pos.astype(jnp.int32)
    kpos = k_pos.astype(jnp.int32)
    if q_p != blk_q:
        qf = jnp.pad(qf, ((0, 0), (0, q_p - blk_q), (0, 0)))
        qpos = jnp.pad(qpos, (0, q_p - blk_q), constant_values=2**30)
    if k_p != blk_k:
        # pad keys get a beyond-any-sequence sentinel so the mask kills them
        kf = jnp.pad(kf, ((0, 0), (0, k_p - blk_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, k_p - blk_k), (0, 0)))
        kpos = jnp.pad(kpos, (0, k_p - blk_k), constant_values=2**30)
        masked = True

    def call(a, b, c, qp, kp):
        return _flash_pos(a, b, c, qp, kp, causal, scale, s_valid, masked,
                          impl == "interpret")

    mesh = _kernel_mesh(q)
    if mesh is not None:
        Bp = _round_up(B, mesh.size)
        qf, kf, vf = (jnp.pad(t, ((0, Bp - B), (0, 0), (0, 0)))
                      for t in (qf, kf, vf))
        call = _per_shard(call, mesh, 3)
    out, lse = call(qf, kf, vf, qpos.reshape(q_p, 1), kpos.reshape(1, k_p))
    if out.shape[:2] != (B, blk_q):
        out = out[:B, :blk_q]
        lse = lse[:B, :blk_q]
    return out.reshape(q.shape), lse.reshape(q.shape[:-1])


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Softmax attention over a local block, flash-fused on TPU.

    ``q, k``: identical shapes ``(..., S, d)`` (leading batch/head axes
    collapse internally); ``v``: ``(..., S, d_v)``, of the same width or of
    another (latent attention: 192-wide keys, 128-wide values).  Returns
    ``(..., S, d_v)`` in ``q``'s dtype.  The causal mask is top-left aligned
    (torch ``is_causal``).  Accumulation is f32 regardless of input dtype
    (bf16 inputs stay bf16 through the GEMM operands — the MXU's native
    layout).  With ``window`` (causal only) query ``i`` sees the keys ``j``
    with ``0 <= i - j < window``, and the kernels' sweeps visit only the
    blocks that holds.
    """
    if k.shape != q.shape or v.shape[:-1] != q.shape[:-1]:
        raise ValueError(
            f"flash_attention requires q and k of one shape and v of their "
            f"leading axes, got {q.shape}, {k.shape}, {v.shape}"
        )
    S, d = q.shape[-2:]
    dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    scale = float(scale)
    window = _checked_window(window, causal, S)

    use_pallas, blk, platform = _pallas_gate(q, S, d, dv)
    if not use_pallas:
        path_counts["dense"] += 1
        return _dense_attention(q, k, v, causal, scale, S, window=window)

    lead = q.shape[:-2]
    B = 1
    for a in lead:
        B *= int(a)
    out = _run_flash_padded(
        (q.reshape((B, S, d)), k.reshape((B, S, d)), v.reshape((B, S, dv))),
        S, blk,
        lambda a, b, c: _flash(a, b, c, causal, scale, S, platform == "cpu",
                               *_window_args(window)),
    )
    return out.reshape(v.shape)


# --------------------------------------------------------------------- #
# the static-offset kernels' pallas_call plumbing, grouped-query (GQA/MQA)
#
# K/V carry H_kv heads serving H_q = g·H_kv query heads.  Only the BlockSpec
# index maps know: each flattened (batch·head) query row b reads K/V row
# (b // hq)·hk + (b % hq) // g, so the g-fold K/V repeat that ``jnp.repeat``
# would materialize in HBM never exists.  The dk/dv sweep runs the g query
# heads of a K/V head's group through one accumulator (grid
# (B·hk, nk, g·nq), block offset = sweep index mod nq).  The fused backward,
# which takes the two sweeps' place wherever its VMEM budget holds, keeps a
# K/V head's dK/dV in VMEM through its g query heads (grid (B·hk, g, nk,
# nq)).  Equal heads are the case hq == hk (``_flash``): the row maps are
# then the identity.
#
# The streamed side's block index is clamped to the sweep's nearest live
# block (``_last_live_k``/``_first_live_q``): consecutive dead steps then
# name the block already in VMEM and the pipeline copies nothing for them.
#
# Under a ``window`` a sweep has ``_window_steps`` steps, not one for every
# block of the streamed side: it starts at the fixed block's first live
# block (``_first_live_k``/``_first_live_q``) and the clamp holds it at the
# last (``_last_live_k``/``_last_live_q``).  ``window=None`` builds the
# sweeps over every block, as they were before there was a window.
# --------------------------------------------------------------------- #


def _gqa_kv_row(b, hq: int, hk: int):
    g = hq // hk
    return (b // hq) * hk + (b % hq) // g


def _gqa_q_row(b, h, hq: int, hk: int):
    """Query row of the ``h``-th head in K/V row ``b``'s group."""
    return (b // hk) * hq + (b % hk) * (hq // hk) + h


def _last_live_k(iq, blk_q: int, blk_k: int, s_valid: int, causal: bool):
    """Index of the last live K/V block of Q block ``iq`` (``_block_kind``:
    live blocks of a row are 0 .. this)."""
    last = (s_valid - 1) // blk_k
    if causal:
        last = jnp.minimum(last, (iq * blk_q + blk_q - 1) // blk_k)
    return last


def _first_live_q(ik, blk_q: int, blk_k: int, causal: bool):
    """Index of the first live Q block of K/V block ``ik`` (live blocks of a
    column are this .. the end)."""
    return (ik * blk_k) // blk_q if causal else 0


def _streamed_k(blk_q: int, blk_k: int, s_valid: int, causal: bool,
                window: Optional[int]):
    """``(iq, step) -> K/V block`` of the sweeps that fix a Q block: the
    step's own block, from the first live one on under a ``window``, held at
    the last live one."""
    last_k = functools.partial(_last_live_k, blk_q=blk_q, blk_k=blk_k,
                               s_valid=s_valid, causal=causal)
    if window is None:
        return lambda iq, ik: jnp.minimum(ik, last_k(iq))
    return lambda iq, ik: jnp.minimum(
        _first_live_k(iq, blk_q, blk_k, window) + ik, last_k(iq))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "s_valid", "hq", "hk", "interpret",
                     "window"),
)
def _flash_gqa_fwd_impl(q, k, v, causal: bool, scale: float, s_valid: int,
                        hq: int, hk: int, interpret: bool,
                        window: Optional[int] = None):
    BHq, Sp, d = q.shape
    dv = v.shape[-1]
    blk_q, blk_k = _block_shape(Sp, max(d, dv), q.dtype.itemsize)
    nq, nk = Sp // blk_q, Sp // blk_k
    if window is not None:  # the K/V blocks one Q block can reach
        nk = _window_steps(nq, blk_q, blk_k, window)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, s_valid=s_valid,
        blk_q=blk_q, blk_k=blk_k, nk=nk,
        masked=causal or (Sp != s_valid), window=window,
    )
    kvrow = functools.partial(_gqa_kv_row, hq=hq, hk=hk)
    kblk = _streamed_k(blk_q, blk_k, s_valid, causal, window)

    def kspec(width):
        return pl.BlockSpec(
            (1, blk_k, width),
            lambda b, iq, ik: (kvrow(b), kblk(iq, ik), 0))

    return pl.pallas_call(
        kernel,
        grid=(BHq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, iq, ik: (b, iq, 0)),
            kspec(d), kspec(dv),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, dv), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, blk_q), lambda b, iq, ik: (b, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHq, Sp, dv), q.dtype),
            jax.ShapeDtypeStruct((BHq, 1, Sp), jnp.float32),  # logsumexp
        ],
        scratch_shapes=[
            # (blk_q, 1) not (blk_q,): TPU scratch wants >=2-D tiles
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
            pltpu.VMEM((blk_q, d), q.dtype),  # the score product's Q operand
        ],
        interpret=interpret,
    )(q, k, v)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "s_valid", "hq", "hk", "interpret",
                     "window", "fused"),
)
def _flash_gqa_bwd_impl(q, k, v, out, lse, do, causal: bool, scale: float,
                        s_valid: int, hq: int, hk: int, interpret: bool,
                        window: Optional[int] = None, fused: bool = False):
    """``(dq, dk, dv)``: the fused kernel (``_flash_bwd_fused_kernel``) where
    ``fused``, else the dq sweep and the dk/dv sweep."""
    BHq, Sp, d = q.shape
    BHk, dv = k.shape[0], v.shape[-1]
    g = hq // hk
    blk_q, blk_k = _block_shape(Sp, max(d, dv), q.dtype.itemsize)
    nq, nk = Sp // blk_q, Sp // blk_k
    # the streamed side's steps of the two sweeps: every block, or what a
    # window lets one fixed block reach
    k_steps, q_steps = nk, nq
    if window is not None:
        k_steps = _window_steps(nq, blk_q, blk_k, window)
        q_steps = _window_steps(nk, blk_k, blk_q, window)
    masked = causal or (Sp != s_valid)
    # D_i = Σ_d dOᵢ ⊙ Oᵢ — one cheap fused elementwise pass, fine in XLA;
    # (B, 1, Sp) like lse (see module docstring)
    dd = _row_dot(do, out)
    kvrow = functools.partial(_gqa_kv_row, hq=hq, hk=hk)
    kblk = _streamed_k(blk_q, blk_k, s_valid, causal, window)
    first_q = functools.partial(_first_live_q, blk_q=blk_q, blk_k=blk_k,
                                causal=causal)

    def qblk(j, i):
        if window is None:
            return jnp.maximum(i % nq, first_q(j))
        return jnp.minimum(first_q(j) + i % q_steps,
                           _last_live_q(j, blk_q, blk_k, nq, window))

    if fused:
        return _bwd_fused_call(
            q, k, v, do, lse, dd, qblk, causal=causal, scale=scale,
            s_valid=s_valid, hq=hq, hk=hk, blk=blk_q, nk=nk, q_steps=q_steps,
            masked=masked, window=window, interpret=interpret)

    # dq sweep: Q block fixed per middle grid index, K/V blocks stream
    # q and k are ``d`` wide, v and the output's cotangent ``dv``
    def qspec(width):
        return pl.BlockSpec((1, blk_q, width), lambda b, i, j: (b, i, 0))

    def kspec(width):
        return pl.BlockSpec(
            (1, blk_k, width),
            lambda b, i, j: (kvrow(b), kblk(i, j), 0))

    rowspec = pl.BlockSpec((1, 1, blk_q), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal, s_valid=s_valid,
            blk_q=blk_q, blk_k=blk_k, nk=k_steps, masked=masked,
            window=window,
        ),
        grid=(BHq, nq, k_steps),
        in_specs=[qspec(d), kspec(d), kspec(dv), qspec(dv), rowspec, rowspec],
        out_specs=qspec(d),
        out_shape=jax.ShapeDtypeStruct((BHq, Sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),
            pltpu.VMEM((blk_q, d), q.dtype),  # the score product's Q operand
        ],
        interpret=interpret,
    )(q, k, v, do, lse, dd)

    # dk/dv sweep: K/V block fixed per middle grid index; one K/V head
    # accumulates its whole group — the innermost grid interleaves the g
    # query heads x nq blocks through ONE scratch
    def qrow(b, i):
        return (b // hk) * hq + (b % hk) * g + i // q_steps

    def qspec2(width):
        return pl.BlockSpec((1, blk_q, width),
                            lambda b, j, i: (qrow(b, i), qblk(j, i), 0))

    def kspec2(width):
        return pl.BlockSpec((1, blk_k, width), lambda b, j, i: (b, j, 0))

    rowspec2 = pl.BlockSpec((1, 1, blk_q),
                            lambda b, j, i: (qrow(b, i), 0, qblk(j, i)))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            s_valid=s_valid, blk_q=blk_q, blk_k=blk_k, nq=g * q_steps,
            nq_inner=q_steps, masked=masked, window=window, n_q_blocks=nq,
        ),
        grid=(BHk, nk, g * q_steps),
        in_specs=[qspec2(d), kspec2(d), kspec2(dv), qspec2(dv), rowspec2, rowspec2],
        out_specs=[kspec2(d), kspec2(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((BHk, Sp, d), k.dtype),
            jax.ShapeDtypeStruct((BHk, Sp, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, dv), jnp.float32),
            pltpu.VMEM((blk_k, d), k.dtype),  # the score product's K operand
        ],
        interpret=interpret,
    )(q, k, v, do, lse, dd)
    return dq, dk, dv


def _bwd_fused_call(q, k, v, do, lse, dd, qblk, *, causal, scale, s_valid,
                    hq, hk, blk, nk, q_steps, masked, window, interpret):
    """The fused backward's ``pallas_call``: grid ``(B·H_kv, g, nk,
    q_steps)``, the Q side's blocks as the dk/dv sweep names them
    (``qblk``)."""
    BHq, Sp, d = q.shape
    BHk, dv = k.shape[0], v.shape[-1]
    g = hq // hk
    qrow = functools.partial(_gqa_q_row, hq=hq, hk=hk)

    def qspec(width):
        return pl.BlockSpec((1, blk, width),
                            lambda b, h, j, i: (qrow(b, h), qblk(j, i), 0))

    def kspec(width):
        return pl.BlockSpec((1, blk, width), lambda b, h, j, i: (b, j, 0))

    # dK/dV go out after the group's last head: block j then, block 0
    # (not yet written) before
    def dkspec(width):
        return pl.BlockSpec((1, blk, width),
                            lambda b, h, j, i: (b, jnp.where(h == g - 1, j, 0), 0))

    # the Q block whose dQ this K/V block completes: the diagonal under
    # causal, else each in turn in the last K/V block's sweep
    def dq_blk(j, i):
        return j if causal else jnp.where(j == nk - 1, i, 0)

    rowspec = pl.BlockSpec((1, 1, blk),
                           lambda b, h, j, i: (qrow(b, h), 0, qblk(j, i)))
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel, scale=scale, causal=causal,
            s_valid=s_valid, blk=blk, g=g, nk=nk, q_steps=q_steps,
            masked=masked, window=window, n_q_blocks=Sp // blk,
        ),
        grid=(BHk, g, nk, q_steps),
        in_specs=[qspec(d), kspec(d), kspec(dv), qspec(dv), rowspec, rowspec],
        out_specs=[
            pl.BlockSpec((1, blk, d),
                         lambda b, h, j, i: (qrow(b, h), dq_blk(j, i), 0)),
            dkspec(d), dkspec(dv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHq, Sp, d), q.dtype),
            jax.ShapeDtypeStruct((BHk, Sp, d), k.dtype),
            jax.ShapeDtypeStruct((BHk, Sp, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((Sp, d), jnp.float32),  # the query head's dQ
            pltpu.VMEM((Sp, d), jnp.float32),  # the K/V head's dK
            pltpu.VMEM((Sp, dv), jnp.float32),  # and dV
            pltpu.VMEM((blk, d), k.dtype),  # the score product's K operand
        ],
        compiler_params=_FUSED_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, dd)


# custom_vjp: jax.grad runs the Pallas backward kernels (the fused sweep, or
# the dq sweep + the dk/dv sweep) instead of failing out of pallas_call's
# missing autodiff rule — training keeps the flash memory profile
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_gqa(q, k, v, causal: bool, scale: float, s_valid: int,
               hq: int, hk: int, interpret: bool,
               window: Optional[int] = None):
    out, _ = _flash_gqa_fwd_impl(q, k, v, causal, scale, s_valid, hq, hk,
                                 interpret, window)
    return out


def _flash_gqa_fwd_rule(q, k, v, causal, scale, s_valid, hq, hk, interpret,
                        window):
    out, lse = _flash_gqa_fwd_impl(q, k, v, causal, scale, s_valid, hq, hk,
                                   interpret, window)
    out, lse = checkpoint_name(out, KEPT[0]), checkpoint_name(lse, KEPT[1])
    path_counts["kept"] += 1
    return out, (q, k, v, out, lse)


def _flash_gqa_bwd_rule(causal, scale, s_valid, hq, hk, interpret, window,
                        res, do):
    q, k, v, out, lse = res
    _, Sp, d = q.shape
    fused = _fused_bwd_fits(Sp, d, v.shape[-1], q.dtype.itemsize)
    path_counts["bwd_fused" if fused else "bwd_two_sweeps"] += 1
    return _flash_gqa_bwd_impl(q, k, v, out, lse, do, causal, scale, s_valid,
                               hq, hk, interpret, window, fused=fused)


_flash_gqa.defvjp(_flash_gqa_fwd_rule, _flash_gqa_bwd_rule)


def _flash(q, k, v, causal: bool, scale: float, s_valid: int,
           interpret: bool, window: Optional[int] = None):
    """Equal heads: every query row reads its own K/V row."""
    return _flash_gqa(q, k, v, causal, scale, s_valid, 1, 1, interpret,
                      *_window_args(window))


def _window_args(window):
    """The window as the trailing argument of the kernels' entry, or none: a
    call without a window is the call it was before there was one."""
    return () if window is None else (window,)


def _checked_window(window, causal: bool, S: int):
    """``window`` as the kernels take it: ``None`` where it masks nothing (no
    window, or one that holds the whole sequence)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(
            f"window ({window}) must be at least 1 and needs causal=True")
    return None if window >= S else int(window)


def flash_attention_gqa(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Grouped-query attention, flash-fused on TPU without repeating K/V.

    ``q``: ``(..., H_q, S, d)``; ``k``: ``(..., H_kv, S, d)``; ``v``:
    ``(..., H_kv, S, d_v)`` with ``H_q % H_kv == 0`` and identical leading
    axes.  Each query head
    attends its group's shared K/V head straight from the kernel's index
    map — the ``H_q/H_kv``-fold K/V broadcast that ``jnp.repeat`` would
    write to HBM never materializes, forward or backward.  Returns
    ``(..., H_q, S, d_v)`` in q's dtype; same causal, ``window`` and
    masked-row semantics as :func:`flash_attention`.  Dispatch follows ``_pallas_gate`` exactly
    like :func:`flash_attention` (TPU kernel; CPU interpreter at test
    scale; dense path over a repeated K/V everywhere else, incl. past the
    VMEM gate).
    """
    if q.ndim < 3 or k.shape[:-1] != v.shape[:-1] or q.shape[:-3] != k.shape[:-3] \
            or q.shape[-2:] != k.shape[-2:]:
        raise ValueError(
            f"flash_attention_gqa requires (..., H_q, S, d) q, (..., H_kv, S, d) k "
            f"and (..., H_kv, S, d_v) v, got {q.shape}, {k.shape}, {v.shape}"
        )
    hq, hk = q.shape[-3], k.shape[-3]
    if hq % hk:
        raise ValueError(
            f"query heads ({hq}) must be a multiple of key/value heads ({hk})"
        )
    S, d = q.shape[-2:]
    dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    scale = float(scale)
    if hq == hk:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window)
    window = _checked_window(window, causal, S)

    use_pallas, blk, platform = _pallas_gate(q, S, d, dv)
    if not use_pallas:
        path_counts["dense"] += 1
        g = hq // hk
        return _dense_attention(
            q, jnp.repeat(k, g, axis=-3), jnp.repeat(v, g, axis=-3),
            causal, scale, S, window=window,
        )

    lead = q.shape[:-3]
    B = 1
    for a in lead:
        B *= int(a)
    out = _run_flash_padded(
        (q.reshape((B * hq, S, d)), k.reshape((B * hk, S, d)),
         v.reshape((B * hk, S, dv))),
        S, blk,
        lambda a, b, c: _flash_gqa(a, b, c, causal, scale, S, hq, hk,
                                   platform == "cpu", *_window_args(window)),
    )
    return out.reshape(q.shape[:-1] + (dv,))
