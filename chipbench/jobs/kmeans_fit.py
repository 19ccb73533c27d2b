"""Job ``kmeans_fit``: one ``ht.cluster.KMeans(...).fit(X)`` of a fixed number
of Lloyd iterations on the resident blobs.

Traffic keys: ``iterations`` (``max_iter``), ``tol`` (negative: the loop's
test is ``shift > tol``, so every iteration runs).  Configuration keys:
``rows``, ``features``, ``clusters``, ``dtype``, ``blob_spread``,
``check_rows``.  The initial centres are one seeded draw from each blob
(``data.blob_draws``), the same in every job: with rows of X chosen at random,
some blobs start with two centres and some with none, and PR 22 measured the
system's five iterations 0.45 of the largest coordinate away from the float32
reference's, because a float32 product at default precision runs in bfloat16
passes and moves the points on a boundary inside a blob.  An iteration costs
the same whatever the values.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

import heat_tpu as ht
from chipbench.harness import data
from chipbench.references import lloyd as reference
from chipbench.references import rel_err

# Share of the largest coordinate.  With one initial centre in every blob no
# label depends on the last bits of a distance, and what is left is the
# rounding of the sums: PR 22 measured 2.3e-5 and 2.7e-5 on the chip.  Sums
# kept in bfloat16 would be off by 2**-9 of each coordinate, above this.
CENTER_TOL = 1e-3
# ``inertia_`` is the sum of the distances as the fit computes them, float32
# products at default precision, which on a TPU are bfloat16 passes: each
# centre's rounding biases its cluster's distances, and PR 22 measured 2.4e-4
# to 2.7e-3 against the float32 reference.  Distances from bfloat16 data
# would be off by more than this.
INERTIA_TOL = 1e-2


def setup(config: dict, traffic: dict, seed: int, comm):
    n, d, k = config["rows"], config["features"], config["clusters"]
    x, centers = data.blobs(comm.mesh, comm.axis, seed, n, d, k, config["blob_spread"],
                            jnp.dtype(config["dtype"]))
    return types.SimpleNamespace(
        X=ht.array(x, split=0, comm=comm),
        init=ht.array(data.blob_draws(centers, seed).astype(x.dtype), comm=comm),
        k=k, iterations=traffic["iterations"], tol=traffic["tol"],
        check_rows=min(config["check_rows"], n),
    )


def _fit(s, x):
    km = ht.cluster.KMeans(n_clusters=s.k, init=s.init, max_iter=s.iterations, tol=s.tol)
    with jax.profiler.TraceAnnotation("ht.cluster.KMeans.fit"):
        km.fit(x)
    if km.n_iter_ != s.iterations:
        raise RuntimeError(f"the fit ran {km.n_iter_} iterations, not {s.iterations}")
    return km


def job(s):
    km = _fit(s, s.X)
    return km.cluster_centers_, km.labels_


def check(s, out) -> tuple:
    """The system and the plain reference on the same first ``check_rows``
    rows from the same centres; the full-size result only has to be finite."""
    sample = s.X[: s.check_rows]
    km = _fit(s, sample)
    want, want_inertia = reference.lloyd(sample._jarray, s.init._jarray, s.iterations)
    facts = {
        "centers_err": rel_err(km.cluster_centers_._jarray, want),
        "inertia_err": abs(km.inertia_ - float(want_inertia)) / float(want_inertia),
        "full_size_finite": bool(jnp.isfinite(out[0]._jarray).all()),
    }
    ok = (facts["centers_err"] < CENTER_TOL and facts["inertia_err"] < INERTIA_TOL
          and facts["full_size_finite"])
    return ok, facts


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Every iteration and the final assignment read X once and need the
    ``rows x clusters`` distances (one GEMM); an iteration also adds each row
    into its centre.  The labels are written once."""
    n, d, k = config["rows"], config["features"], config["clusters"]
    passes = traffic["iterations"] + 1
    itemsize = jnp.dtype(config["dtype"]).itemsize
    return {
        "flop": passes * 2 * n * d * k + traffic["iterations"] * n * d,
        "bytes": passes * n * d * itemsize + n * 4,
        "derived": {"iterations_per_job": traffic["iterations"]},
    }
