"""Jax-side job executors for the elastic scheduler (the serving tier).

``scheduler.py`` is deliberately stdlib-only — it knows nothing about jax,
arrays or collectives.  This module is its runtime half: executors for the
heterogeneous job kinds the ROADMAP's serving scenario names — **KMeans
fits**, **matmul / triangular-solve requests** and **NN forward batches**
— each built deterministically from the job's JSON payload, so every rank
of an SPMD world reconstructs the identical computation and stages the
identical collectives (scheduling divergence would be a desync; see
design.md "Serving & scheduling").

Micro-batching contract: :func:`batch_key` groups jobs by *program
signature* (kind + structural payload fields, data/seed fields excluded),
so same-shape requests from different tenants share one dispatch —
``nn_forward`` batches genuinely stack into a single forward pass, and the
per-job kinds reuse the PR 1 sharding-keyed program cache (the second
identical-shape matmul request compiles NOTHING).

Deadline contract: the scheduler arms ``health.deadline`` (the contextvar
``comm.deadline`` also arms) around every dispatch, so the collective
staging points and the guarded blocking waits inside these executors trip
``CollectiveTimeoutError`` at the offending job when the world wedges.

All jax/heat imports are lazy (inside :func:`make_executor`): importing
this module costs nothing, and ``heat_tpu.parallel`` stays importable in
processes that never execute a job.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List

from . import scheduler as _scheduler

__all__ = ["KINDS", "batch_key", "make_executor"]

# exception type names that mean the distributed MACHINERY failed (a dead
# peer's connection reset, a torn-down client) rather than the job itself —
# name-matched because the concrete classes live in jaxlib and vary by
# version
_WORLD_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError")


def _raise_world_broken(e: BaseException):
    """Convert an XLA/transport runtime error into
    :class:`scheduler.WorldBroken` so the scheduler requeues the batch
    instead of terminally failing jobs whose only crime was running while
    a peer died (the raise-fast vs hang race under the supervisor's
    teardown)."""
    for klass in type(e).__mro__:
        if klass.__name__ in _WORLD_ERROR_TYPES:
            raise _scheduler.WorldBroken(
                f"distributed runtime failed under dispatch: {e}"
            ) from e

KINDS = ("matmul", "solve", "kmeans", "nn_forward")

# payload fields that parameterize the DATA, not the compiled program —
# excluded from the batch signature so same-shape jobs share one dispatch
_DATA_FIELDS = ("seed",)


def batch_key(job) -> str:
    """Program-signature batch key: jobs whose payloads differ only in
    data fields (``seed``) are compatible — one shared SPMD dispatch."""
    sig = {k: v for k, v in job.payload.items() if k not in _DATA_FIELDS}
    return f"{job.kind}|{json.dumps(sig, sort_keys=True)}"


def make_executor(comm=None) -> Callable[[List[Any]], List[Any]]:
    """Build the ``executor(jobs) -> results`` callable for
    :class:`heat_tpu.parallel.scheduler.Scheduler`.

    Every result is ``{"digest": float, ...}`` — a host-materialized
    scalar summary, so a DONE job is attested by a value that actually
    crossed the device→host boundary (a wedged collective can therefore
    never produce a phantom DONE record)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import heat_tpu as ht

    if comm is None:
        comm = ht.communication.get_comm()

    # nn_forward model cache: one Linear stack per feature width, params
    # derived from a fixed key — identical on every rank by construction
    _models: Dict[int, tuple] = {}

    def _model(features: int):
        got = _models.get(features)
        if got is None:
            model = ht.nn.Sequential(ht.nn.Linear(features, 4), ht.nn.ReLU())
            params = model.init(jax.random.key(0))
            got = _models[features] = (model, params)
        return got

    def _fetch_sum(x) -> float:
        """Host digest of a DNDarray reduction (the one sanctioned
        device→host sync per job — collective, guarded, fault-retried)."""
        return float(np.asarray(comm.host_fetch(x.sum()._jarray)))

    # ------------------------------------------------------------------ #
    def _matmul(job) -> dict:
        n = int(job.payload.get("n", 16))
        scale = 1.0 + int(job.payload.get("seed", 0)) % 7
        a = ht.reshape(ht.arange(n * n, dtype=ht.float32, split=0), (n, n))
        a = a * (scale / n)
        c = a @ ht.transpose(a)
        return {"digest": _fetch_sum(c), "n": n}

    def _solve(job) -> dict:
        n = int(job.payload.get("n", 8))
        # well-conditioned lower-triangular system, deterministic entries
        ln = ht.reshape(ht.arange(n * n, dtype=ht.float32, split=0), (n, n))
        a = ht.tril(ln * (1.0 / (n * n))) + ht.eye(n, dtype=ht.float32, split=0) * 2.0
        b = ht.reshape(ht.arange(n, dtype=ht.float32, split=0), (n, 1))
        x = ht.linalg.solve_triangular(a, b, lower=True)
        return {"digest": _fetch_sum(x), "n": n}

    def _kmeans(job) -> dict:
        n = int(job.payload.get("n", 32))
        k = int(job.payload.get("k", 2))
        # payload-seeded, so every rank draws the IDENTICAL stream — the
        # per-rank-divergence class HT105 guards against cannot occur
        rng = np.random.default_rng(int(job.payload.get("seed", 0)))  # heatlint: disable=HT105 payload-seeded, rank-identical
        pts = rng.standard_normal((n, 2)).astype(np.float32)
        pts[: n // 2] += 8.0  # two separable blobs: the fit converges fast
        x = ht.array(pts, split=0)
        km = ht.cluster.KMeans(n_clusters=k, init="random", max_iter=5,
                               random_state=0)
        km.fit(x)
        return {"digest": _fetch_sum(km.cluster_centers_), "k": k}

    def _nn_forward_batch(jobs) -> List[dict]:
        """The genuinely stacked kind: all jobs' inputs concatenate into
        ONE forward pass (the shared SPMD dispatch), results split back
        per job."""
        features = int(jobs[0].payload.get("features", 8))
        model, params = _model(features)
        xs, sizes = [], []
        for job in jobs:
            b = int(job.payload.get("batch", 4))
            rng = np.random.default_rng(int(job.payload.get("seed", 0)))  # heatlint: disable=HT105 payload-seeded, rank-identical
            xs.append(rng.standard_normal((b, features)).astype(np.float32))
            sizes.append(b)
        out = model.apply(params, jnp.asarray(np.concatenate(xs, axis=0)))
        host = np.asarray(comm.host_fetch(out))
        results, off = [], 0
        for b in sizes:
            results.append({"digest": float(host[off: off + b].sum()), "batch": b})
            off += b
        return results

    _single = {"matmul": _matmul, "solve": _solve, "kmeans": _kmeans}

    # federation admission feedback (ISSUE 17): with HEAT_TPU_FED_PEAKS
    # set to a history path and the memledger armed, every executed batch
    # is bracketed in a memledger.peak_window and its incremental peak is
    # recorded per kind — the persisted history federation.
    # AdmissionPredictor sheds mem_infeasible jobs against at the edge.
    _predictor = None
    _peaks_path = os.environ.get("HEAT_TPU_FED_PEAKS")
    if _peaks_path:
        from ..utils import memledger as _memledger

        if _memledger.enabled():
            from . import federation as _federation

            _predictor = _federation.AdmissionPredictor(_peaks_path)

    def _run(jobs: List[Any]) -> List[Any]:
        kind = jobs[0].kind
        if kind == "nn_forward":
            return _nn_forward_batch(jobs)
        fn = _single.get(kind)
        if fn is None:
            raise ValueError(f"unknown job kind {kind!r} (serve {KINDS})")
        # same-signature jobs re-enter the SAME cached programs (PR 1
        # sharding-keyed cache): the batch shares compiled dispatches
        # even though each job's data digest is computed separately
        return [fn(job) for job in jobs]

    def execute(jobs: List[Any]) -> List[Any]:
        try:
            if _predictor is not None:
                from ..utils import memledger as _memledger

                with _memledger.peak_window() as w:
                    results = _run(jobs)
                # per-JOB footprint: the batch's incremental peak split
                # evenly — conservative enough for admission (the window
                # maximum already over-counts concurrent neighbors)
                delta = max(0, int(w["peak"]) - int(w["base"]))
                if delta > 0:
                    _predictor.observe(jobs[0].kind,
                                       (delta + len(jobs) - 1) // len(jobs))
                return results
            return _run(jobs)
        except Exception as e:
            _raise_world_broken(e)  # transport death -> WorldBroken
            raise

    return execute
