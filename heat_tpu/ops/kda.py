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
the state at the start of each chunk, the backward recomputes ``_prepare``,
runs the recurrence's transpose as a reverse scan over the chunks (the
cotangent of ``S`` its carry) and pulls the result back through
``_prepare``.  With a batch axis before the heads, forward and backward walk
it one sequence at a time (``_prepare`` makes a dozen arrays of ``q``'s size,
and one sequence's heads are enough for a step of the recurrence).  Matrix
products take their operands in ``q``'s dtype (bfloat16 on the chip) and add
up in float32; the decays, the solve and the state are float32.

On a v5e (my chip runs, PR 32; 32 heads of 128, 8,192 tokens, chunk 64, one
sequence): ``_prepare`` 17.4 ms (the cumulative sum 2.2, the two matrices 8.2,
the solve 5.0), the recurrence 3.4, their transposes 39.5 and 7.8: the
chunk-parallel part, not the scan, is where a kernel would pay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["chunk_kda"]

_SUB = 16


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


def _step(state, parts, dtype):
    """One chunk of the recurrence: ``(S_C, o)``."""
    qg, m, w, uv, kend, dend = parts
    u = uv - _mm("...cd,...dv->...cv", w, state, dtype)
    o = _mm("...cd,...dv->...cv", qg, state, dtype) + _mm("...ab,...bv->...av", m, u, dtype)
    new = dend[..., None] * state + _mm("...cd,...cv->...dv", kend, u, dtype)
    return new, o


def _chunk_first(tree, lead: int):
    return jax.tree.map(lambda t: jnp.moveaxis(t, lead, 0), tree)


def _recur(parts, dtype):
    """``(o by chunk, final state, state at the start of each chunk)``."""
    lead = parts[0].ndim - 3
    shape = parts[0].shape[:lead] + (parts[0].shape[-1], parts[3].shape[-1])

    def step(state, chunk_parts):
        new, o = _step(state, chunk_parts, dtype)
        return new, (o, state)

    final, (o, starts) = jax.lax.scan(step, jnp.zeros(shape, jnp.float32), _chunk_first(parts, lead))
    return jnp.moveaxis(o, 0, lead), final, starts


def _recur_transposed(parts, starts, d_o, d_final, dtype):
    """Cotangents of ``parts``: the recurrence backwards, a chunk at a time,
    each chunk's step recomputed from the state it started with."""
    lead = parts[0].ndim - 3

    def back(d_state, xs):
        chunk_parts, start, d_out = xs
        _, pull = jax.vjp(lambda s, p: _step(s, p, dtype), start, chunk_parts)
        return pull((d_state, d_out))

    _, d_parts = jax.lax.scan(
        back, d_final, (_chunk_first(parts, lead), starts, jnp.moveaxis(d_o, lead, 0)), reverse=True)
    return jax.tree.map(lambda t: jnp.moveaxis(t, 0, lead), d_parts)


def _forward_all(q, k, v, g, beta, chunk):
    """``((o, final state), state at the start of each chunk)``, every leading
    axis at once."""
    o, final, starts = _recur(_prepare(q, k, v, g, beta, chunk), q.dtype)
    return (o.reshape(v.shape).astype(v.dtype), final), starts


def _backward_all(chunk, inputs, starts, d_o, d_final):
    """The five inputs' cotangents: ``_prepare`` again, the recurrence
    backwards from the kept states, and back through ``_prepare``."""
    parts, pull = jax.vjp(functools.partial(_prepare, chunk=chunk), *inputs)
    d_o = d_o.astype(jnp.float32).reshape(parts[3].shape)
    return pull(_recur_transposed(parts, starts, d_o, d_final.astype(jnp.float32), inputs[0].dtype))


def _forward(q, k, v, g, beta, chunk):
    # with more than one leading axis (batch, heads) the first is walked one entry at
    # a time: the arrays above are several times the inputs' size, and one
    # sequence's heads fill a step of the recurrence (3.4 of a sequence's 21 ms)
    if q.ndim < 4:
        return _forward_all(q, k, v, g, beta, chunk)
    return jax.lax.map(lambda t: _forward_all(*t, chunk), (q, k, v, g, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunk_kda(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk)[0]


def _fwd(q, k, v, g, beta, chunk):
    out, starts = _forward(q, k, v, g, beta, chunk)
    return out, (q, k, v, g, beta, starts)


def _bwd(chunk, res, cotangents):
    *inputs, starts = res
    if inputs[0].ndim < 4:
        return _backward_all(chunk, inputs, starts, *cotangents)
    return jax.lax.map(lambda t: _backward_all(chunk, t[0], *t[1:]), (inputs, starts, *cotangents))


_chunk_kda.defvjp(_fwd, _bwd)


def chunk_kda(q, k, v, g, beta, *, chunk: int = 64):
    """Kimi Delta Attention over whole sequences, ``chunk`` tokens at a time.

    ``q, k``: ``(..., S, d_k)``; ``v``: ``(..., S, d_v)``; ``g``: ``(..., S,
    d_k)``, the log-decay (``<= 0``) of each key channel; ``beta``: ``(...,
    S)``.  Leading axes (batch, heads) are independent sequences, each from
    a zero state.  ``q`` and ``k`` come normalised and scaled as the model
    wants them.  Returns ``(o (..., S, d_v) in v's dtype, the final state
    (..., d_k, d_v) in float32)``.  A length that is no multiple of
    ``chunk`` is padded with tokens that write nothing and decay nothing.
    """
    length = q.shape[-2]
    pad = -length % chunk
    if pad:
        widths = [(0, 0)] * (q.ndim - 2) + [(0, pad), (0, 0)]
        q, k, v, g = (jnp.pad(t, widths) for t in (q, k, v, g))
        beta = jnp.pad(beta, widths[:-1])
    with jax.named_scope("ht.kda"):
        o, final = _chunk_kda(q, k, v, g, beta, chunk)
    return (o[..., :length, :] if pad else o), final
