"""Shared k-clustering skeleton (reference: ``heat/cluster/_kcluster.py``).

Init strategies and the E/M fit loop shell.  The per-iteration compute
(distances → assignment → masked aggregation) is one jitted XLA program; the
reference's two Allreduces per iteration (SURVEY §3.4) are implicit in the
sharded segment-sum.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import factories, types
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray


class _KCluster(ClusteringMixin, BaseEstimator):
    """Base class for KMeans/KMedians/KMedoids."""

    def __init__(self, metric: Callable, n_clusters: int, init, max_iter: int, tol: float, random_state: Optional[int]):
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state
        self._metric = metric

        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

    @property
    def cluster_centers_(self) -> DNDarray:
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    @property
    def inertia_(self) -> float:
        return self._inertia

    @property
    def n_iter_(self) -> int:
        return self._n_iter

    @property
    def functional_value_(self) -> float:
        return self._inertia

    # ------------------------------------------------------------------ #
    def _initialize_cluster_centers(self, x: DNDarray, oversampling: float = 1.0, iter_multiplier: float = 1.0):
        """Center init: 'random', 'kmeans++' (distributed D² sampling), or
        a user-provided (k, d) DNDarray/array."""
        k = self.n_clusters
        jx = x._jarray
        n, d = x.shape
        key = jax.random.key(self.random_state if self.random_state is not None else 0)

        if isinstance(self.init, DNDarray) or isinstance(self.init, (np.ndarray, jnp.ndarray)):
            centers = self.init._jarray if isinstance(self.init, DNDarray) else jnp.asarray(self.init)
            if centers.shape != (k, d):
                raise ValueError(f"initial centers must have shape {(k, d)}, got {centers.shape}")
            self._cluster_centers = factories.array(centers, device=x.device, comm=x.comm)
            return

        if self.init == "random":
            idx = jax.random.choice(key, n, (k,), replace=False)
            centers = jx[idx]
        elif self.init in ("kmeans++", "probability_based"):
            # greedy D² sampling: draw several candidates ∝ D², keep the one
            # minimizing the resulting potential (the reference's Allreduce of
            # the D² mass is XLA's implicit psum over the sharded sample axis)
            n_trials = 2 + int(np.ceil(np.log2(max(k, 2))))

            def body(i, state):
                centers, d2, key = state
                key, sub = jax.random.split(key)
                probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
                cand_idx = jax.random.choice(sub, n, (n_trials,), p=probs)
                cand = jx[cand_idx]  # (t, d)
                cd2 = jnp.sum((jx[:, None, :] - cand[None, :, :]) ** 2, axis=-1)  # (n, t)
                pots = jnp.sum(jnp.minimum(d2[:, None], cd2), axis=0)  # (t,)
                best = jnp.argmin(pots)
                nxt = cand[best]
                d2 = jnp.minimum(d2, cd2[:, best])
                return centers.at[i].set(nxt), d2, key

            key, sub = jax.random.split(key)
            first = jx[jax.random.randint(sub, (), 0, n)]
            centers0 = jnp.zeros((k, d), jx.dtype).at[0].set(first)
            d2_0 = jnp.sum((jx - first[None, :]) ** 2, axis=-1)
            centers, _, _ = jax.lax.fori_loop(1, k, body, (centers0, d2_0, key))
        elif self.init == "batchparallel":
            centers = jx[jax.random.choice(key, n, (k,), replace=False)]
        else:
            raise ValueError(f"Unknown init strategy {self.init!r}")
        centers = x.comm.shard(centers, None)
        self._cluster_centers = DNDarray(
            centers, (k, d), x.dtype, None, x.device, x.comm, True
        )

    # rows per E-step block: bounds the materialized (block, k) distance
    # tile so the fit scales to BASELINE's 1e8-row config without an n×k
    # buffer ever existing in HBM (the X matrix itself is the footprint)
    _ASSIGN_BLOCK = 1 << 20

    @staticmethod
    def _assign(jx, centers):
        """E-step: squared distances + argmin, fused on the MXU.

        For large n the rows are processed in fixed-size blocks read with
        ``dynamic_slice`` inside a ``fori_loop`` — X stays in its at-rest
        layout and only one (block, k) distance tile plus one (block, d) row
        tile exist at a time.  (A reshape/``lax.map`` formulation materializes
        a full lane-padded copy of X as an HLO temp — a 4× blowup for d=32
        that OOMs HBM at 2²⁵ rows; measured on v5e.)
        """
        cc = jnp.sum(centers * centers, axis=1)[None, :]

        def block_assign(xb):
            xx = jnp.sum(xb * xb, axis=1, keepdims=True)
            d2 = xx + cc - 2.0 * (xb @ centers.T)
            return jnp.argmin(d2, axis=1), jnp.min(jnp.maximum(d2, 0.0), axis=1)

        n = jx.shape[0]
        blk = _KCluster._ASSIGN_BLOCK
        if n <= blk:
            return block_assign(jx)
        # TRANSPOSED block loop: X at rest is {0,1}-laid-out (n, d), which IS
        # (d, n) row-major — jx.T is a free bitcast, and (d, blk) tiles have
        # their minor dim = blk, so nothing ever lane-pads (a (blk, d) slice
        # layout pads d→128 lanes: 4× HBM for d=32, measured OOM on v5e)
        xt = jx.T
        nblocks = -(-n // blk)

        def body(i, carry):
            labels, d2min = carry
            start = jnp.minimum(i * blk, n - blk)  # tail block overlaps; writes agree
            xb = jax.lax.dynamic_slice_in_dim(xt, start, blk, axis=1)  # (d, blk)
            xx = jnp.sum(xb * xb, axis=0)[None, :]
            d2 = cc.T + xx - 2.0 * (centers @ xb)  # (k, blk)
            lb = jnp.argmin(d2, axis=0)
            db = jnp.min(jnp.maximum(d2, 0.0), axis=0)
            labels = jax.lax.dynamic_update_slice(labels, lb, (start,))
            d2min = jax.lax.dynamic_update_slice(d2min, db, (start,))
            return labels, d2min

        labels0 = jnp.zeros((n,), dtype=jnp.int32)
        d2min0 = jnp.zeros((n,), dtype=jx.dtype)
        return jax.lax.fori_loop(0, nblocks, body, (labels0, d2min0))

    @staticmethod
    def _update(jx, labels, centers):
        raise NotImplementedError()

    @classmethod
    def _em_step(cls, jx, centers):
        """One Lloyd iteration: new centers from current ones.  Default =
        assign then update (two passes over X); subclasses may fuse."""
        labels, _ = cls._assign(jx, centers)
        return cls._update(jx, labels, centers)

    @classmethod
    def _fit_program(cls):
        """The WHOLE Lloyd iteration as one compiled XLA program
        (lax.while_loop, SURVEY §3.4) — a single device dispatch per fit,
        no per-iteration host round-trips.  Cached per class so repeated
        fits (and new instances) skip retracing."""
        cache = cls.__dict__.get("_FIT_PROGRAM")
        if cache is None:
            cache = {}
            cls._FIT_PROGRAM = cache
        # the E/M block size is baked into the trace — key the cache on it
        prog = cache.get(_KCluster._ASSIGN_BLOCK)
        if prog is None:

            @jax.jit
            def prog(jx, centers0, max_iter, tol):
                def cond(state):
                    _, it, shift = state
                    return jnp.logical_and(it < max_iter, shift > tol)

                def body(state):
                    centers, it, _ = state
                    with jax.named_scope("ht.kmeans.em"):
                        new = cls._em_step(jx, centers)
                    return new, it + 1, jnp.max(jnp.abs(new - centers))

                centers, n_iter, _ = jax.lax.while_loop(
                    cond, body, (centers0, jnp.asarray(0), jnp.asarray(jnp.inf, centers0.dtype))
                )
                with jax.named_scope("ht.kmeans.assign"):
                    labels, d2 = cls._assign(jx, centers)
                return centers, labels, jnp.sum(d2), n_iter

            cache[_KCluster._ASSIGN_BLOCK] = prog
        return prog

    def fit(self, x: DNDarray):
        """Lloyd iteration — one fused sharded XLA program per fit.

        Row-split inputs on a multi-device mesh take the shard_map path
        (per-shard blocked E+M + psum of the (k,d)/(k,) statistics — X never
        crosses chips); otherwise the global GSPMD program runs.
        """
        from ..core.sanitation import sanitize_in

        sanitize_in(x)
        self._initialize_cluster_centers(x)
        centers0 = self._cluster_centers._jarray
        n = x.shape[0]
        use_sharded = (
            getattr(self, "_supports_sharded_fit", False)
            and x.split == 0
            and x.comm.is_distributed()
        )
        if use_sharded:
            prog = self._fit_program_sharded(x.comm)
            centers, labels_phys, inertia, n_iter = prog(
                x._masked(0),  # pads must be zero, not dead garbage
                centers0,
                jnp.asarray(n),
                jnp.asarray(self.max_iter),
                jnp.asarray(self.tol, centers0.dtype),
            )
            n_iter = int(n_iter)
            self._cluster_centers = DNDarray(
                x.comm.shard(centers, None), tuple(centers.shape), x.dtype, None,
                x.device, x.comm, True,
            )
            self._labels = DNDarray(
                labels_phys, (n,), types.canonical_heat_type(labels_phys.dtype),
                0, x.device, x.comm, True,
            )
            self._inertia = float(inertia)
            self._n_iter = n_iter
            return self

        jx = x._jarray
        centers, labels, inertia, n_iter = self._fit_program()(
            jx, centers0, jnp.asarray(self.max_iter), jnp.asarray(self.tol, centers0.dtype)
        )
        n_iter = int(n_iter)

        self._cluster_centers = DNDarray(
            x.comm.shard(centers, None), tuple(centers.shape), x.dtype, None, x.device, x.comm, True
        )
        lab = x.comm.shard(labels, x.split)
        self._labels = DNDarray(
            lab, tuple(lab.shape), types.canonical_heat_type(lab.dtype), x.split, x.device, x.comm, True
        )
        self._inertia = float(inertia)
        self._n_iter = n_iter
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Nearest-center assignment for new data."""
        from ..core.sanitation import sanitize_in

        sanitize_in(x)
        labels, _ = self._assign(x._jarray, self._cluster_centers._jarray)
        lab = x.comm.shard(labels, x.split)
        return DNDarray(
            lab, tuple(lab.shape), types.canonical_heat_type(lab.dtype), x.split, x.device, x.comm, True
        )
