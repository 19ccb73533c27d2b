"""Pairwise distances (reference: ``heat/spatial/distance.py``).

The reference's both-split case is a ring algorithm: the X block stays put,
Y blocks circulate via Isend/Irecv (SURVEY §2.4).  Here a call of ``cdist``,
``rbf`` or ``manhattan`` is ONE sharded program (GSPMD chooses the data
movement — typically an all-gather of the smaller operand over ICI): the
whole expression is a module-level compute function, compiled once per
operand signature into the dispatch layer's program cache and launched once
a call (``core._operations._program_op``), its result already on the
canonical sharding.  Operands that cannot key a program — tracers under the
caller's own ``jax.jit``, padded (ragged) operands, a ragged result — run
the same compute function op by op.  The explicit ring is available as
``cdist_ring`` built on ``parallel.ring_map`` for the memory-constrained
regime where only one rotating block may be resident at a time.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..core import types
from ..core._operations import _program_op
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = ["cdist", "cdist_ring", "cdist_small", "manhattan", "rbf"]


def _wrap(jarr, split, proto: DNDarray) -> DNDarray:
    if split is not None and split >= jarr.ndim:
        split = None
    jarr = proto.comm.shard(jarr, split)
    return DNDarray(
        jarr, tuple(jarr.shape), types.canonical_heat_type(jarr.dtype), split, proto.device, proto.comm, True
    )


# ---------------------------------------------------------------------- #
# compute functions: plain jnp on the operands' arrays, module-level so
# that their identity keys the program cache
# ---------------------------------------------------------------------- #
def _sq_euclid(x, y):
    # quadratic expansion: ||x||² + ||y||² − 2 x·yᵀ — one big MXU GEMM
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    yy = jnp.sum(y * y, axis=1, keepdims=True).T
    d2 = xx + yy - 2.0 * (x @ y.T)
    return jnp.maximum(d2, 0.0)


def _sq_direct(x, y):
    # direct form, still batched: (n,1,d)-(1,m,d) — better precision
    return jnp.sum((x[:, None, :] - y[None, :, :]) ** 2, axis=-1)


def _cdist_quadratic(x, y):
    return jnp.sqrt(_sq_euclid(x, y))


def _cdist_direct(x, y):
    return jnp.sqrt(jnp.maximum(_sq_direct(x, y), 0.0))


def _manhattan(x, y):
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def _rbf(x, y, scale, quadratic):
    d2 = _sq_euclid(x, y) if quadratic else _sq_direct(x, y)
    return jnp.exp(-d2 / scale)


def _pairwise(compute, x, y, *scalars, static=()) -> DNDarray:
    """``compute`` over the rows of ``x`` and ``y`` (``x`` again if None) as
    one program; the result is split along the rows of whichever is."""
    sanitize_in(x)
    if y is None:
        y = x
    sanitize_in(y)
    split = 0 if x.split == 0 else (1 if y.split == 0 else None)
    return _program_op(compute, (x, y, *scalars), split, static)


def cdist(x: DNDarray, y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Euclidean distance matrix between rows of ``x`` and ``y``.

    ``quadratic_expansion=True`` uses the GEMM form (MXU-friendly: the
    expansion maps the computation onto the systolic array); the default is
    the direct form, of better precision.  Either is one cached program a
    call; see the module's docstring for when the same expression runs op by
    op instead.
    """
    return _pairwise(_cdist_quadratic if quadratic_expansion else _cdist_direct, x, y)


def cdist_small(x: DNDarray, y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    return cdist(x, y, quadratic_expansion)


def manhattan(x: DNDarray, y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """City-block distance matrix."""
    return _pairwise(_manhattan, x, y)


def rbf(x: DNDarray, y: Optional[DNDarray] = None, sigma: float = 1.0, quadratic_expansion: bool = False) -> DNDarray:
    """Gaussian RBF kernel matrix exp(−d²/(2σ²)).  ``sigma`` reaches the
    program as an argument: a new value compiles nothing."""
    return _pairwise(_rbf, x, y, 2.0 * sigma * sigma, static=(bool(quadratic_expansion),))


def cdist_ring(x: DNDarray, y: Optional[DNDarray] = None) -> DNDarray:
    """Explicit ring cdist (reference's Isend/Irecv algorithm on ppermute).

    Both operands row-split; X blocks stationary, Y blocks rotate. Peak
    memory per chip is one X block + one Y block + one output block —
    the reason the reference uses this form at scale.
    """
    from ..parallel.ring import ring_map

    sanitize_in(x)
    if y is None:
        y = x
    comm = x.comm
    if (
        comm.size == 1
        or x.split != 0
        or y.split != 0
        or x.shape[0] % comm.size
        or y.shape[0] % comm.size
    ):
        return cdist(x, y, quadratic_expansion=True)

    d = ring_map(
        _cdist_ring_step, x._jarray, y._jarray, comm,
        combine="concat", concat_axis=1,
    )
    return _wrap(d, 0, x)


def _cdist_ring_step(x_blk, y_blk, src):
    # module-level (stable identity) so ring_map's comm-cached program is
    # reused across cdist_ring calls instead of recompiling per call
    return _cdist_quadratic(x_blk, y_blk)
