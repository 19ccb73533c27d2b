"""Lloyd's k-means and Sculley's mini-batch k-means, written plainly.

Reference of the ``blobs_d32_k64`` configuration (jobs ``kmeans_fit`` and
``lloyd_eager``).  Follows Lloyd (1982) and Sculley, "Web-scale k-means
clustering" (WWW 2010), algorithm 1 with the per-batch form of the update: a
centre moves towards the mean of the batch rows assigned to it by
``eta = n_batch / n_seen``.  As upstream HeAT does, an empty cluster keeps its
centre.  Everything is float32 with ``highest`` matmul precision: on a TPU a
float32 product otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _assign(x, centers):
    d2 = (
        jnp.sum(x * x, axis=1, keepdims=True)
        + jnp.sum(centers * centers, axis=1)[None, :]
        - 2.0 * (x @ centers.T)
    )
    return jnp.argmin(d2, axis=1), jnp.maximum(jnp.min(d2, axis=1), 0.0)


def _batch_stats(x, centers):
    label, d2 = _assign(x, centers)
    onehot = (label[:, None] == jnp.arange(centers.shape[0])[None, :]).astype(x.dtype)
    return onehot.T @ x, jnp.sum(onehot, axis=0), d2


@functools.partial(jax.jit, static_argnames="iters")
def lloyd(x, centers, iters: int):
    """``iters`` full Lloyd iterations, then ``(centres, inertia)``."""
    with jax.default_matmul_precision("highest"):
        for _ in range(iters):
            sums, counts, _ = _batch_stats(x, centers)
            centers = jnp.where(
                counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers)
        _, d2 = _assign(x, centers)
        return centers, jnp.sum(d2)


@functools.partial(jax.jit, static_argnames="batch")
def minibatch(x, centers, offsets, batch: int):
    """One mini-batch step per offset, each on ``x[o:o+batch]``:
    ``(centres, rows seen per centre)``."""
    with jax.default_matmul_precision("highest"):
        seen = jnp.zeros((centers.shape[0],), x.dtype)
        for i in range(offsets.shape[0]):
            xb = lax.dynamic_slice_in_dim(x, offsets[i], batch, axis=0)
            sums, counts, _ = _batch_stats(xb, centers)
            seen = seen + counts
            eta = counts / jnp.maximum(seen, 1.0)
            mean = sums / jnp.maximum(counts, 1.0)[:, None]
            centers = centers + eta[:, None] * (mean - centers)
        return centers, seen
