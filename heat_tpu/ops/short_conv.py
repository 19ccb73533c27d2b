"""Gated short convolution: the sequence operator of convolution/attention
hybrid language models (a depthwise causal convolution of a few taps between
two element-wise gates).

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

import jax
import jax.numpy as jnp

__all__ = ["gated_short_conv"]


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
