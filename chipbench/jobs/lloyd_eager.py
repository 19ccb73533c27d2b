"""Job ``lloyd_eager``: mini-batch k-means (Sculley 2010) written the way
upstream HeAT writes its estimators and users write their own loops, in
public eager ``ht.*`` calls only, on slices of the resident blobs.

Traffic keys: ``batch_rows``, ``steps``, ``check_steps``.  The offsets are
``steps`` multiples of ``batch_rows`` drawn from the seed, the same list in
every job; every job starts from the same centres (one seeded draw from each
blob, as in ``jobs/kmeans_fit.py`` and for its reason) and zero counts.  Centres
and counts are ``split=None``, X and its slices ``split=0``: the sums come
out of ``ht.matmul(onehot.T, xb)`` split along the features, so they are
resplit to ``None`` (no data moves on one chip) before they meet the centres.
"""

from __future__ import annotations

import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from chipbench.harness import data
from chipbench.references import lloyd as reference
from chipbench.references import rel_err

# as in jobs/kmeans_fit.py; a batch gives a centre 256 rows, not 16384, so
# the rounding of the sums shows more: PR 22 measured 0.7e-4 to 1.0e-4 on the chip
CENTER_TOL = 1e-3

_span = jax.profiler.TraceAnnotation


def setup(config: dict, traffic: dict, seed: int, comm):
    n, d, k = config["rows"], config["features"], config["clusters"]
    batch = traffic["batch_rows"]
    x, centers = data.blobs(comm.mesh, comm.axis, seed, n, d, k, config["blob_spread"],
                            jnp.dtype(config["dtype"]))
    offsets = np.random.default_rng(seed).choice(n // batch, traffic["steps"], replace=False) * batch
    return types.SimpleNamespace(
        X=ht.array(x, split=0, comm=comm),
        C0=ht.array(data.blob_draws(centers, seed).astype(x.dtype), comm=comm),
        arange=ht.arange(k, comm=comm),
        k=k, batch=batch, offsets=[int(o) for o in offsets],
        check_steps=min(traffic["check_steps"], traffic["steps"]), comm=comm,
    )


def _step(s, C, counts, o: int):
    with _span("ht.getitem"):
        xb = s.X[o:o + s.batch]
    with _span("ht.spatial.cdist"):
        dist = ht.spatial.cdist(xb, C, quadratic_expansion=True)
    with _span("ht.argmin"):
        label = ht.argmin(dist, axis=1)
    with _span("ht.eq"):
        hit = label[:, None] == s.arange
    with _span("ht.astype"):
        onehot = hit.astype(ht.float32)
    with _span("ht.sum"):
        n_b = onehot.sum(axis=0)
    with _span("ht.matmul"):
        sums = ht.matmul(onehot.T, xb)
    with _span("ht.resplit"):
        sums = ht.resplit(sums, None)
    with _span("ht.iadd"):
        counts += n_b
    with _span("ht.update"):
        eta = n_b / ht.maximum(counts, 1.0)
        mean = sums / ht.maximum(n_b, 1.0)[:, None]
        C = C + eta[:, None] * (mean - C)
    return C, counts


def _run(s, offsets) -> tuple:
    C, counts = s.C0, ht.zeros((s.k,), dtype=ht.float32, comm=s.comm)
    for o in offsets:
        C, counts = _step(s, C, counts, o)
    return C, counts


def job(s):
    return _run(s, s.offsets)


def check(s, out) -> tuple:
    """The first ``check_steps`` steps against the plain reference; no step
    may warn of mismatched splits, and centres and counts stay unsplit."""
    offsets = s.offsets[: s.check_steps]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        C, counts = _run(s, offsets)
    want, want_seen = reference.minibatch(
        s.X._jarray, s.C0._jarray, jnp.asarray(offsets), s.batch)
    facts = {
        "centers_err": rel_err(C._jarray, want),
        "rows_seen": float(counts._jarray.sum()),
        "rows_seen_wanted": float(want_seen.sum()),
        "split_warnings": sum("mismatched splits" in str(w.message) for w in caught),
        "splits": [C.split, counts.split],
        "full_job_finite": bool(jnp.isfinite(out[0]._jarray).all()),
    }
    ok = (facts["centers_err"] < CENTER_TOL and facts["rows_seen"] == facts["rows_seen_wanted"]
          and not facts["split_warnings"] and facts["splits"] == [None, None]
          and facts["full_job_finite"])
    return ok, facts


def work(config: dict, traffic: dict, chips: int) -> dict:
    """A step reads its batch, forms ``batch x clusters`` distances (one
    GEMM) and the per-centre sums (a second); the rest is of lower order."""
    d, k = config["features"], config["clusters"]
    b, steps = traffic["batch_rows"], traffic["steps"]
    itemsize = jnp.dtype(config["dtype"]).itemsize
    return {
        "flop": steps * 2 * (2 * b * d * k),
        "bytes": steps * b * d * itemsize,
        "derived": {"steps_per_job": steps},
    }
