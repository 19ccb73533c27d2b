"""KMeans (reference: ``heat/cluster/kmeans.py``; BASELINE workload, SURVEY §3.4).

M-step = segment-sum over the sharded sample axis; XLA emits the two small
Allreduces (sums, counts) the reference issues by hand.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core._cache import comm_cached
from ._kcluster import _KCluster

__all__ = ["KMeans"]


class KMeans(_KCluster):
    """K-Means clustering with the reference's API.

    Parameters mirror ``heat.cluster.KMeans``: n_clusters, init
    ('kmeans++' | 'random' | array), max_iter, tol, random_state.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, object] = "kmeans++",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        super().__init__(
            metric=lambda x, y: None, n_clusters=n_clusters, init=init,
            max_iter=max_iter, tol=tol, random_state=random_state,
        )

    @staticmethod
    def _blocked_stats(jx, k, label_fn):
        """(k, d) cluster sums + (k,) counts over transposed fixed-size blocks.

        ``label_fn(xb, start, blk) -> (blk,) labels`` supplies the assignment
        for each ``(d, blk)`` block; an out-of-range label (e.g. the sentinel
        ``k`` for pad rows) contributes nothing.  The transposed view is a
        FREE bitcast of the {0,1} at-rest layout (see ``_KCluster._assign``),
        so X is never relayout-copied (a (blk, d) slice layout lane-pads
        d→128: 4× HBM for d=32, measured OOM on v5e).  The clamped tail block
        overlaps the previous one; overlapped rows get weight 0, so every row
        counts once.
        """
        n, d = jx.shape
        blk = min(_KCluster._ASSIGN_BLOCK, n)
        xt = jx.T

        def stats_at(start, w):
            xb = jax.lax.dynamic_slice_in_dim(xt, start, blk, axis=1)  # (d, blk)
            lb = label_fn(xb, start, blk)
            onehot = (jnp.arange(k)[:, None] == lb[None, :]).astype(jx.dtype) * w[None, :]
            bs = jnp.einsum("kb,db->kd", onehot, xb)  # MXU GEMM, no relayout
            return bs, jnp.sum(onehot, axis=1)

        if n <= blk:
            return stats_at(jnp.asarray(0), jnp.ones((blk,), jx.dtype))

        nblocks = -(-n // blk)

        def body(i, carry):
            s, c = carry
            start = jnp.minimum(i * blk, n - blk)
            w = (jnp.arange(blk) + start >= i * blk).astype(jx.dtype)
            bs, bc = stats_at(start, w)
            return s + bs, c + bc

        return jax.lax.fori_loop(
            0, nblocks, body,
            (jnp.zeros((k, d), jx.dtype), jnp.zeros((k,), jx.dtype)),
        )

    @staticmethod
    def _centers_from_stats(sums, counts, centers):
        safe = jnp.maximum(counts, 1.0)
        new = sums / safe[:, None]
        # empty clusters keep their previous center (reference behavior)
        return jnp.where(counts[:, None] > 0, new, centers)

    @staticmethod
    def _update(jx, labels, centers):
        k = centers.shape[0]
        n = jx.shape[0]
        if n <= _KCluster._ASSIGN_BLOCK:
            onehot = (labels[:, None] == jnp.arange(k)[None, :]).astype(jx.dtype)
            sums, counts = onehot.T @ jx, jnp.sum(onehot, axis=0)
        else:
            sums, counts = KMeans._blocked_stats(
                jx, k,
                lambda xb, start, blk: jax.lax.dynamic_slice(labels, (start,), (blk,)),
            )
        return KMeans._centers_from_stats(sums, counts, centers)

    @classmethod
    def _em_step(cls, jx, centers):
        """Fused Lloyd iteration: ONE pass over X per iteration — each block
        is read once, assigned, and immediately folded into the (k, d)/(k,)
        statistics.  Halves HBM traffic vs assign-then-update."""
        k = centers.shape[0]
        n = jx.shape[0]
        if n <= _KCluster._ASSIGN_BLOCK:
            labels, _ = cls._assign(jx, centers)
            return cls._update(jx, labels, centers)
        cc = jnp.sum(centers * centers, axis=1)[:, None]

        def assign_block(xb, start, blk):
            xx = jnp.sum(xb * xb, axis=0)[None, :]
            d2 = cc + xx - 2.0 * (centers @ xb)  # (k, blk)
            return jnp.argmin(d2, axis=0)

        sums, counts = cls._blocked_stats(jx, k, assign_block)
        return cls._centers_from_stats(sums, counts, centers)

    # ------------------------------------------------------------------ #
    # shard_map fit path (multi-chip native; SURVEY §3.4): each shard runs
    # the blocked E+M over its LOCAL rows and the two small (k,d)/(k,)
    # Allreduces the reference issues per iteration become explicit psums —
    # X never crosses chips, only the statistics do.
    # ------------------------------------------------------------------ #
    _supports_sharded_fit = True

    @staticmethod
    def _local_em_stats(jxl, centers, base, n):
        """Blocked (k, d) sums + (k,) counts over one shard's LOCAL rows
        ``jxl`` (c, d); ``base`` is this shard's global row offset, rows with
        ``base + i >= n`` are pad and get the sentinel label ``k`` (zero
        onehot row — see ``_blocked_stats``)."""
        k = centers.shape[0]
        cc = jnp.sum(centers * centers, axis=1)[:, None]

        def label_fn(xb, start, blk):
            xx = jnp.sum(xb * xb, axis=0)[None, :]
            d2 = cc + xx - 2.0 * (centers @ xb)
            lb = jnp.argmin(d2, axis=0)
            gidx = base + start + jnp.arange(blk)
            return jnp.where(gidx < n, lb, k)  # pad rows → sentinel

        return KMeans._blocked_stats(jxl, k, label_fn)

    @classmethod
    def _fit_program_sharded(cls, comm):
        """Whole Lloyd iteration as one shard_map'd XLA program over the
        PHYSICAL row-sharded array: per-shard blocked E+M, psum of the
        (k,d)/(k,) statistics, while_loop to convergence, final per-shard
        assignment via ``_assign``.  ``n`` (the logical row count) is a
        traced operand, so all row counts sharing a padded shape share one
        compile.  Cached on the comm instance (``comm_cached``) so the
        program — which pins mesh + XLA executable — dies with the comm."""
        return _fit_sharded_program(comm, cls, _KCluster._ASSIGN_BLOCK)


@comm_cached
def _fit_sharded_program(comm, cls, assign_block):
    axis = comm.axis

    def shard_fn(phys_blk, centers0, n, max_iter, tol):
        c = phys_blk.shape[0]
        base = jax.lax.axis_index(axis) * c

        def em(centers):
            s, cnt = cls._local_em_stats(phys_blk, centers, base, n)
            s = jax.lax.psum(s, axis)  # the reference's two Allreduces
            cnt = jax.lax.psum(cnt, axis)
            return cls._centers_from_stats(s, cnt, centers)

        def cond(state):
            _, it, shift = state
            return jnp.logical_and(it < max_iter, shift > tol)

        def body(state):
            centers, it, _ = state
            with jax.named_scope("ht.kmeans.em"):
                new = em(centers)
            return new, it + 1, jnp.max(jnp.abs(new - centers))

        centers, n_iter, _ = jax.lax.while_loop(
            cond, body,
            (centers0, jnp.asarray(0), jnp.asarray(jnp.inf, centers0.dtype)),
        )
        # final local assignment on the converged centers — _assign
        # handles the small and blocked cases; pad rows are masked below
        with jax.named_scope("ht.kmeans.assign"):
            labels, d2min = cls._assign(phys_blk, centers)
        w = (base + jnp.arange(c) < n).astype(d2min.dtype)
        inertia = jax.lax.psum(jnp.sum(d2min * w), axis)
        return centers, labels, inertia, n_iter

    from jax.sharding import PartitionSpec as P

    mapped = comm.shard_map(
        shard_fn,
        in_splits=((2, 0), P(), P(), P(), P()),
        out_splits=(P(), (1, 0), P(), P()),
    )
    return jax.jit(mapped)
