"""Lightning attention: linear attention with a constant decay a head
(MiniMax-01, arXiv:2501.08313; the ``lightning-attn`` layers of MiniCPM-SALA).

A head keeps a state ``S`` of ``d_k x d_v``, zero at the start of a sequence:

    S_t = lambda S_{t-1} + k_t^T v_t,      o_t = q_t S_t

with ``lambda = exp(g)``, ``g < 0`` the head's log-decay.  Inside a chunk of
``C`` tokens (positions ``i = 1 .. C``) with start state ``S_0``:

    o_i = lambda^i q_i S_0 + sum_{j <= i} lambda^(i - j) (q_i . k_j) v_j
    S_C = lambda^C S_0 + sum_j lambda^(C - j) k_j^T v_j

The parts that need no state, ``(q lambda^i, the decayed triangle's output,
k lambda^(C - j), v, lambda^C)``, are made for all chunks at once
(``_chunk_parts``); the state runs through the chunks in the sequential scan
of :mod:`heat_tpu.ops.kda` (``kda._recur``, with this operator's ``_step``),
two small products a chunk.  Every exponent is ``<= 0``.  The backward pass
is a custom rule as ``chunk_kda``'s: the forward keeps the inputs and the
state at the start of each chunk, the backward makes the parts again, runs
the recurrence's transpose (``kda._recur_transposed``) and pulls the result
back through the chunk-local part.  Products take their operands in ``q``'s
dtype and add up in float32; the decays and the state are float32.

The chunk-local part runs as two Pallas kernels over tiles of whole chunks of
one head (``kda._tiled_call``: the same grid, a program of its own) for heads
of whole lane tiles on a TPU and, at test scale, under the interpreter on a
CPU; the same function mapped over the chunks by XLA elsewhere.  The forward
kernel writes the parts, the backward kernel traces ``jax.vjp`` of the same
function into its body.  The decay is a constant of the layer: it gets no
gradient.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.devices import platform_of
from .flash_attention import _kernel_mesh
from .kda import _by_chunk, _dot, _iota, _mm, _recur, _recur_transposed, _tiled_call

__all__ = ["lightning_attention"]

_ROWS = (512, 256)  # tokens of a head a grid step of the kernels, (forward, backward)

# engagement counter, flash attention's contract: which implementation the
# chunk-local part of a call took, counted at trace time
path_counts = {"pallas": 0, "dense": 0}


def _chunk_parts(q, k, v, g):
    """One chunk: ``q, k (C, d_k)``, ``v (C, d_v)``, ``g (1, C)`` the
    log-decay of each token (the head's, everywhere) to ``(q lambda^i, the
    triangle's output (C, d_v) float32, k lambda^(C - j), lambda^C (1, d_k)
    float32)``."""
    size, dk = k.shape
    dtype = q.dtype
    # products of operands the caller brought in float32 stay float32
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    log = jnp.max(g.astype(jnp.float32), axis=1, keepdims=True)  # (1, 1)
    pos = _iota((size, 1), 0).astype(jnp.float32)
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    decay = jnp.where(row >= col, jnp.exp((row - col).astype(jnp.float32) * log), 0.0)
    scores = _dot(q, k, ((1,), (1,)), precision) * decay
    within = _dot(scores.astype(dtype), v, precision=precision)
    qg = (q.astype(jnp.float32) * jnp.exp((pos + 1.0) * log)).astype(dtype)
    kend = (k.astype(jnp.float32) * jnp.exp((size - 1.0 - pos) * log)).astype(dtype)
    return qg, within, kend, jnp.broadcast_to(jnp.exp(size * log), (1, dk))


def _step(state, parts, dtype):
    """One chunk of the recurrence: ``(S_C, o)``."""
    qg, within, kend, v, dend = parts
    o = _mm("...cd,...dv->...cv", qg, state, dtype) + within
    new = dend[..., None] * state + _mm("...cd,...cv->...dv", kend, v, dtype)
    return new, o


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, *out_refs):
    """A tile of whole chunks of one head: the four inputs in, the parts
    without ``v`` out."""
    for ref, part in zip(out_refs, jax.vmap(_chunk_parts)(q_ref[0], k_ref[0], v_ref[0], g_ref[0])):
        ref[0] = part


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, dqg_ref, dwithin_ref, dkend_ref, dq_ref, dk_ref, dv_ref):
    """The inputs and the three parts' cotangents in, ``q``'s, ``k``'s and
    ``v``'s (through the triangle) out: ``jax.vjp`` of the tile's forward."""
    g = g_ref[0]
    _, pull = jax.vjp(lambda q, k, v: jax.vmap(_chunk_parts)(q, k, v, g)[:3], q_ref[0], k_ref[0], v_ref[0])
    for ref, d in zip((dq_ref, dk_ref, dv_ref), pull((dqg_ref[0], dwithin_ref[0], dkend_ref[0]))):
        ref[0] = d


def _parts(q, k, v, g, chunk: int, tile: int):
    """The parts of every chunk, a chunk axis before the token axis."""
    lead = q.shape[:-2]
    dk, dv, dtype = q.shape[-1], v.shape[-1], q.dtype
    chunks = [_by_chunk(t, len(lead), chunk) for t in (q, k, v, g)]
    if tile:
        outs = [(chunk, dk, dtype), (chunk, dv, jnp.dtype(jnp.float32)), (chunk, dk, dtype),
                (1, dk, jnp.dtype(jnp.float32))]
        found = _tiled_call(_forward_kernel, chunks, outs, tile, platform_of(q) != "tpu")
    else:
        found = jax.vmap(jax.vmap(_chunk_parts))(*chunks)
    qg, within, kend, dend = (t.reshape(*lead, *t.shape[1:]) for t in found)
    return qg, within, kend, chunks[2].reshape(*lead, *chunks[2].shape[1:]), dend.reshape(*lead, -1, dk)


def _pull(q, k, v, g, d_parts, chunk: int, tile: int):
    """``(dq, dk, dv)`` from the cotangents of ``q lambda^i``, the triangle's
    output, ``k lambda^(C - j)`` and ``v``."""
    lead = q.shape[:-2]
    chunks = [_by_chunk(t, len(lead), chunk) for t in (q, k, v, g)]
    flat = lambda t: t.reshape(-1, *t.shape[len(lead):])  # noqa: E731
    d_qg, d_within, d_kend, d_v = (flat(t) for t in d_parts[:4])
    if tile:
        outs = [(*t.shape[2:], t.dtype) for t in chunks[:3]]
        backward_tile = max(1, min(tile, _ROWS[1] // chunk))
        found = _tiled_call(_backward_kernel, chunks + [d_qg, d_within, d_kend], outs, backward_tile,
                            platform_of(q) != "tpu")
    else:
        _, pull = jax.vjp(lambda a, b, c: jax.vmap(jax.vmap(_chunk_parts))(a, b, c, chunks[3])[:3], *chunks[:3])
        found = pull((d_qg, d_within.astype(jnp.float32), d_kend))
    dq, dk, dv = (d.reshape(t.shape) for d, t in zip(found, (q, k, v)))
    return dq, dk, (dv.astype(jnp.float32) + d_v.reshape(v.shape)).astype(v.dtype)


def _forward(q, k, v, g, chunk, tile):
    parts = _parts(q, k, v, g, chunk, tile)
    o, _, starts = _recur(parts, q.dtype, _step)
    return o.reshape(v.shape).astype(v.dtype), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _lightning(q, k, v, g, chunk, tile):
    return _forward(q, k, v, g, chunk, tile)[0]


def _fwd(q, k, v, g, chunk, tile):
    out, starts = _forward(q, k, v, g, chunk, tile)
    return out, (q, k, v, g, starts)


def _bwd(chunk, tile, res, d_o):
    q, k, v, g, starts = res
    parts = _parts(q, k, v, g, chunk, tile)
    d_o = d_o.astype(jnp.float32).reshape(parts[1].shape)
    final = jnp.zeros(starts.shape[1:], jnp.float32)
    d_parts = _recur_transposed(parts, starts, d_o, final, q.dtype, _step)
    return (*_pull(q, k, v, g, d_parts, chunk, tile), jnp.zeros_like(g))


_lightning.defvjp(_fwd, _bwd)


def _pallas_gate(q, v, chunk: int) -> int:
    """Chunks a grid step of the forward kernel, 0 for XLA: the kernels on a
    TPU and, up to 512 tokens, under the interpreter on a CPU, for heads of
    whole lane tiles and chunks of whole sublane tiles up to 256 tokens."""
    platform = platform_of(q)
    *lead, length, dk = q.shape
    mesh = _kernel_mesh(q)
    fits = (dk % 128 == 0 and v.shape[-1] % 128 == 0 and chunk % 16 == 0 and chunk <= 256
            and (mesh is None or math.prod(lead) % mesh.size == 0))
    if fits and (platform == "tpu" or (platform == "cpu" and length <= 512)):
        return max(1, min(_ROWS[0] // chunk, -(-length // chunk)))
    return 0


def lightning_attention(q, k, v, log_decay, *, chunk: int = 128):
    """Causal linear attention with a constant decay a head, ``chunk`` tokens
    at a time.

    ``q, k``: ``(B, H, S, d_k)``; ``v``: ``(B, H, S, d_v)``; ``log_decay``:
    ``(H,)``, ``log lambda`` of each head (``< 0``; no gradient reaches it).
    ``q`` and ``k`` come scaled and activated as the model wants them.
    Returns ``o`` ``(B, H, S, d_v)`` in ``v``'s dtype.  A length that is no
    multiple of ``chunk`` (of the kernels' tile of chunks, where they run)
    is padded with tokens that write nothing."""
    length = q.shape[-2]
    tile = _pallas_gate(q, v, chunk)
    path_counts["pallas" if tile else "dense"] += 1
    g = jnp.broadcast_to(log_decay.astype(jnp.float32)[:, None], q.shape[:-1])
    pad = -length % (chunk * max(tile, 1))
    if pad:
        widths = [(0, 0)] * (q.ndim - 2) + [(0, pad), (0, 0)]
        q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
        g = jnp.pad(g, widths[:-1], mode="edge")
    with jax.named_scope("ht.lightning"):
        o = _lightning(q, k, v, g, chunk, tile)
    return o[..., :length, :] if pad else o
