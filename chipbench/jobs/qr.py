"""Job ``qr``: one ``ht.linalg.qr(A)`` of a resident tall-skinny float32
matrix split along the rows (TSQR).

Traffic key: ``mode`` (``"reduced"`` forms Q, ``"r"`` does not).
Configuration keys: ``rows``, ``cols``, ``dtype``.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

import heat_tpu as ht
from chipbench.harness import data
from chipbench.references import dense as reference

# chip_smoke measured 3.6e-6 on one chip and 9.8e-7 on four; a factor from a
# bfloat16 Gram matrix lies near 1e-2.
GRAM_TOL = 1e-3


def setup(config: dict, traffic: dict, seed: int, comm):
    a = data.dense(comm.mesh, comm.axis, seed, config["rows"], config["cols"], 1.0,
                   jnp.dtype(config["dtype"]))
    return types.SimpleNamespace(A=ht.array(a, split=0, comm=comm), mode=traffic["mode"])


def job(s):
    with jax.profiler.TraceAnnotation("ht.linalg.qr"):
        qr = ht.linalg.qr(s.A, mode=s.mode)
    return (qr.R,) if s.mode == "r" else (qr.Q, qr.R)


def check(s, out) -> tuple:
    r = out[-1]._jarray
    facts = {
        "gram_rel_err": float(reference.gram_gap(s.A._jarray, r)),
        "r_upper_triangular": bool((jnp.tril(r, -1) == 0).all()),
    }
    ok = facts["gram_rel_err"] < GRAM_TOL and facts["r_upper_triangular"]
    if s.mode != "r":
        facts["q_orthogonality_gap"] = float(reference.orthogonality_gap(out[0]._jarray))
        ok = ok and facts["q_orthogonality_gap"] < GRAM_TOL * r.shape[0] ** 0.5
    return ok, facts


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Householder's count for the factor, ``2 m n^2 - 2 n^3 / 3``, and as
    much again where Q is formed; A is read once and Q written once."""
    m, n = config["rows"], config["cols"]
    itemsize = jnp.dtype(config["dtype"]).itemsize
    with_q = traffic["mode"] != "r"
    factor = 2 * m * n * n - 2 * n ** 3 // 3
    return {
        "flop": factor * (2 if with_q else 1),
        "bytes": (m * n * (2 if with_q else 1) + n * n) * itemsize,
        "derived": {},
    }
