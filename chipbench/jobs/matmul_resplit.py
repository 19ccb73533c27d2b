"""Job ``matmul_resplit``: ``c = ht.matmul(a, b)`` of two resident square
operands, both split along the rows, then ``ht.resplit(c, 1)``.

Traffic keys: ``matmul`` and ``resplit`` (either can be switched off: with
``matmul`` off the resplit moves ``a``), ``resplit_to``.  Configuration keys:
``n``, ``dtype``.  ``b`` is scaled by ``n ** -0.5`` so that the product's
entries are of order one.  Each of the two calls ends in
``block_until_ready`` inside its own span, which is what ``matmul_ms`` and
``resplit_ms`` read; the operands are never rebuilt.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from chipbench.harness import data
from chipbench.references import dense as reference
from chipbench.references import rel_err

# bfloat16 keeps 8 significant bits: the result rounds once after a float32
# accumulation, so it lies within 2**-8 of the largest entry (chip_smoke
# measured 2.9e-3).  A product accumulated in bfloat16 would miss this widely.
ENTRY_TOL = 2.0 ** -7
PROBE = 256


def setup(config: dict, traffic: dict, seed: int, comm):
    n, dtype = config["n"], jnp.dtype(config["dtype"])
    a = data.dense(comm.mesh, comm.axis, 2 * seed, n, n, 1.0, dtype)
    b = data.dense(comm.mesh, comm.axis, 2 * seed + 1, n, n, n ** -0.5, dtype)
    return types.SimpleNamespace(
        a=ht.array(a, split=0, comm=comm), b=ht.array(b, split=0, comm=comm),
        matmul=traffic["matmul"], resplit=traffic["resplit"],
        resplit_to=traffic["resplit_to"], seed=seed,
    )


def job(s):
    out = [s.a]
    if s.matmul:
        with jax.profiler.TraceAnnotation("ht.matmul"):
            out = [jax.block_until_ready(ht.matmul(s.a, s.b))]
    if s.resplit:
        with jax.profiler.TraceAnnotation("ht.resplit"):
            out.append(jax.block_until_ready(ht.resplit(out[0], s.resplit_to)))
    return tuple(out)


def check(s, out) -> tuple:
    """A corner and seeded scattered entries of the product against the
    float32 product of the same rows and columns; the resplit result equal
    to its source bit for bit, with the split asked for."""
    facts, ok = {}, True
    n = s.a.shape[0]
    probe = min(PROBE, n)
    if s.matmul:
        scattered = np.sort(np.random.default_rng(s.seed).choice(n, probe, replace=False))
        for name, idx in (("corner", np.arange(probe)), ("scattered", scattered)):
            want = reference.product_block(s.a._jarray[idx], s.b._jarray[:, idx])
            err = rel_err(out[0]._jarray[idx][:, idx], want)
            facts[f"{name}_rel_err"] = err
            ok = ok and err < ENTRY_TOL
    if s.resplit:
        facts["resplit_exact"] = bool(jnp.array_equal(out[-1]._jarray, out[0]._jarray))
        facts["resplit_split"] = out[-1].split
        ok = ok and facts["resplit_exact"] and out[-1].split == s.resplit_to
    return ok, facts


def work(config: dict, traffic: dict, chips: int) -> dict:
    """The product needs ``2 n^3`` operations and touches its three matrices
    once.  The resplit reads and writes the matrix once, and nothing where
    one chip holds it whole."""
    n, itemsize = config["n"], jnp.dtype(config["dtype"]).itemsize
    flop = 2 * n ** 3 if traffic["matmul"] else 0
    nbytes = 3 * n * n * itemsize if traffic["matmul"] else 0
    if traffic["resplit"] and chips > 1:
        nbytes += 2 * n * n * itemsize
    return {"flop": flop, "bytes": nbytes, "derived": {}}
