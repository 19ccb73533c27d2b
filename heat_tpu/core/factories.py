"""Array factories (reference: ``heat/core/factories.py``, SURVEY §3.1).

The reference's ``array()`` materializes the full input on every rank, then
keeps only the local chunk.  Here the factory builds ONE global ``jax.Array``
and places it with the ``NamedSharding`` implied by ``split`` — XLA moves the
bytes.  ``is_split`` ingest (each process contributes its local chunk) maps to
assembling along the split axis then sharding; on a single controller it
degenerates to ``split=``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import devices, sanitation, types
from .communication import Communication, sanitize_comm
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis, sanitize_shape

# device-memory-ledger hook (``utils.memledger.enable()`` pokes the module
# in): ``_finalize``/``_filled`` are where every factory's buffer becomes
# live, so they are registration choke points.  Disabled cost: one
# module-global load (telemetry-hook pattern; module bottom re-arms).
_MEMLEDGER = None

__all__ = [
    "arange",
    "array",
    "asarray",
    "empty",
    "empty_like",
    "eye",
    "from_partitioned",
    "full",
    "full_like",
    "linspace",
    "logspace",
    "meshgrid",
    "ones",
    "ones_like",
    "zeros",
    "zeros_like",
]


def _finalize(
    jarr: jax.Array,
    split: Optional[int],
    device,
    comm,
    dtype=None,
) -> DNDarray:
    """Shard a raw jax array and wrap it as a DNDarray."""
    device = devices.sanitize_device(device)
    comm = sanitize_comm(comm)
    split = sanitize_axis(jarr.shape, split)
    if dtype is not None:
        dtype = types.canonical_heat_type(dtype)
        if jarr.dtype != dtype.jax_dtype():
            jarr = jarr.astype(dtype.jax_dtype())
    # derive the metadata dtype from the array the cast actually produced,
    # honoring JAX canonicalization (64→32-bit when x64 is off) like
    # DNDarray.astype does — a requested float64 with x64 off used to leave
    # float64 METADATA on a float32 buffer (runtime sanitizer's first catch)
    dtype = types.canonical_heat_type(jarr.dtype)
    jarr = comm.shard(jarr, split)
    ret = DNDarray(jarr, tuple(jarr.shape), dtype, split, device, comm, True)
    if _MEMLEDGER is not None:
        # ledger choke point: op=None -> the ledger's frame walk names the
        # public factory up-stack (arange/linspace/eye/..., skipping
        # comprehension frames — meshgrid/ix_ call from list comps)
        _MEMLEDGER.register(ret._parray, op=None, site="factory")
    # factory boundary of the runtime sanitizer (HEAT_TPU_CHECKS=1):
    # no-op unless armed, metadata-only when armed
    return sanitation.check(ret, "factory")


def array(
    obj,
    dtype=None,
    copy: Optional[bool] = None,
    ndmin: int = 0,
    order: str = "C",
    split: Optional[int] = None,
    is_split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Create a DNDarray from array-like data — the workhorse factory.

    ``split=k`` shards axis ``k`` over the mesh; ``is_split=k`` declares the
    input to be this process's local chunk along ``k`` (single-controller: the
    chunks of all processes are the whole array, so it behaves as ``split``).
    """
    if split is not None and is_split is not None:
        raise ValueError("split and is_split are mutually exclusive")
    if isinstance(obj, DNDarray):
        jarr = obj._jarray
        comm = comm if comm is not None else obj.comm
        device = device if device is not None else obj.device
        if split is None and is_split is None:
            split = obj.split
    elif isinstance(obj, jax.Array):
        jarr = obj
    else:
        npa = np.asarray(obj)
        if npa.dtype == object:
            raise TypeError("invalid data of type object")
        jarr = jnp.asarray(npa)
    if dtype is not None:
        jarr = jarr.astype(types.canonical_heat_type(dtype).jax_dtype())
    while jarr.ndim < ndmin:
        jarr = jarr[jnp.newaxis]
    eff_split = split if split is not None else is_split
    return _finalize(jarr, eff_split, device, comm, dtype)


def asarray(obj, dtype=None, copy=None, order="C", is_split=None, device=None) -> DNDarray:
    return array(obj, dtype=dtype, copy=copy, order=order, is_split=is_split, device=device)


def _filled(shape, value, dtype, split, device, comm, like=None) -> DNDarray:
    shape = sanitize_shape(shape)
    dtype = types.canonical_heat_type(dtype)
    comm_s = sanitize_comm(comm)
    split_s = sanitize_axis(shape, split)
    jdt = dtype.jax_dtype()
    sharding = comm_s.sharding(len(shape), split_s)
    # jnp.full with out_sharding materializes each shard on its own device —
    # no host round-trip, no full replica (TPU-friendly for huge arrays)
    try:
        jarr = jnp.full(shape, value, dtype=jdt, out_sharding=sharding)
    except (TypeError, ValueError):
        jarr = comm_s.shard(jnp.full(shape, value, dtype=jdt), split_s)
    ret = DNDarray(jarr, shape, dtype, split_s, devices.sanitize_device(device), comm_s, True)
    if _MEMLEDGER is not None:
        _MEMLEDGER.register(ret._parray, op=None, site="factory")
    return ret


def zeros(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    return _filled(shape, 0, dtype, split, device, comm)


def ones(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    return _filled(shape, 1, dtype, split, device, comm)


def empty(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    # XLA has no uninitialized buffers; empty == zeros (documented deviation)
    return _filled(shape, 0, dtype, split, device, comm)


def full(shape, fill_value, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    if dtype is None:
        dtype = types.heat_type_of(fill_value)
        if dtype is types.float64:
            dtype = types.float32
    return _filled(shape, fill_value, dtype, split, device, comm)


def _like(proto, factory, dtype, split, device, comm, **kw):
    if not isinstance(proto, DNDarray):
        proto = array(proto)
    return factory(
        proto.shape,
        dtype=dtype if dtype is not None else proto.dtype,
        split=split if split is not None else proto.split,
        device=device if device is not None else proto.device,
        comm=comm if comm is not None else proto.comm,
        **kw,
    )


def zeros_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return _like(a, zeros, dtype, split, device, comm)


def ones_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return _like(a, ones, dtype, split, device, comm)


def empty_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return _like(a, empty, dtype, split, device, comm)


def full_like(a, fill_value, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    if not isinstance(a, DNDarray):
        a = array(a)
    return full(
        a.shape,
        fill_value,
        dtype=dtype if dtype is not None else a.dtype,
        split=split if split is not None else a.split,
        device=device if device is not None else a.device,
        comm=comm if comm is not None else a.comm,
    )


def arange(*args, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """``arange(stop)`` / ``arange(start, stop[, step])`` — reference-parity."""
    num_args = len(args)
    if num_args == 1:
        start, stop, step = 0, args[0], 1
    elif num_args == 2:
        start, stop, step = args[0], args[1], 1
    elif num_args == 3:
        start, stop, step = args
    else:
        raise TypeError(f"arange takes 1 to 3 positional arguments, got {num_args}")
    if dtype is None:
        all_ints = all(isinstance(a, (int, np.integer)) for a in (start, stop, step))
        dtype = types.int32 if all_ints else types.float32
    dtype = types.canonical_heat_type(dtype)
    jarr = jnp.arange(start, stop, step, dtype=dtype.jax_dtype())
    return _finalize(jarr, split, device, comm, dtype)


def linspace(
    start,
    stop,
    num: int = 50,
    endpoint: bool = True,
    retstep: bool = False,
    dtype=None,
    split=None,
    device=None,
    comm=None,
):
    num = int(num)
    jarr = jnp.linspace(float(start), float(stop), num, endpoint=endpoint, dtype=jnp.float32)
    res = _finalize(jarr, split, device, comm, dtype)
    if retstep:
        step = (float(stop) - float(start)) / max(1, (num - 1 if endpoint else num))
        return res, step
    return res


def logspace(
    start, stop, num=50, endpoint=True, base=10.0, dtype=None, split=None, device=None, comm=None
) -> DNDarray:
    jarr = jnp.logspace(float(start), float(stop), int(num), endpoint=endpoint, base=base, dtype=jnp.float32)
    return _finalize(jarr, split, device, comm, dtype)


def eye(shape, dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    if isinstance(shape, (int, np.integer)):
        n, m = int(shape), int(shape)
    else:
        shape = sanitize_shape(shape)
        n, m = (shape[0], shape[0]) if len(shape) == 1 else shape[:2]
    dtype = types.canonical_heat_type(dtype)
    jarr = jnp.eye(n, m, dtype=dtype.jax_dtype())
    return _finalize(jarr, split, device, comm, dtype)


def meshgrid(*arrays, indexing: str = "xy") -> list:
    """Coordinate matrices from vectors. If any input is split, the result
    follows the reference's convention (first output split=0/second split=1
    under 'xy' is simplified to: all outputs split along the axis the split
    input occupies)."""
    comm = None
    device = None
    for a in arrays:
        if isinstance(a, DNDarray):
            comm, device = a.comm, a.device
            break
    jarrs = [a._jarray if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    outs = jnp.meshgrid(*jarrs, indexing=indexing)
    # position of the first split input among ALL inputs (not just DNDarrays)
    split_in = next(
        (i for i, a in enumerate(arrays) if isinstance(a, DNDarray) and a.split is not None),
        None,
    )
    out_split = None
    if split_in is not None and len(arrays) >= 1:
        # vector i varies along output axis: 'xy' swaps the first two
        ax = split_in
        if indexing == "xy" and split_in in (0, 1) and len(arrays) >= 2:
            ax = 1 - split_in
        out_split = ax
    return [_finalize(o, out_split, device, comm) for o in outs]


def from_partitioned(x, comm=None) -> DNDarray:
    """Ingest an object exposing ``__partitioned__`` (reference parity)."""
    parts = x.__partitioned__
    shape = tuple(parts["shape"])
    tiling = parts.get("partition_tiling", (1,))
    split = None
    for i, t in enumerate(tiling):
        if t > 1:
            split = i
            break
    get = parts.get("get", lambda v: v)
    chunks = []
    for pos in sorted(parts["partitions"]):
        data = get(parts["partitions"][pos]["data"])
        chunks.append(np.asarray(data))
    full_arr = np.concatenate(chunks, axis=split or 0) if len(chunks) > 1 else chunks[0]
    return array(full_arr.reshape(shape), split=split, comm=comm)


def identity(n: int, dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    """n×n identity matrix (numpy ``identity``)."""
    return eye(int(n), dtype=dtype, split=split, device=device, comm=comm)


def geomspace(start, stop, num: int = 50, endpoint: bool = True, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Log-spaced samples between start and stop (inclusive ends)."""
    dt = types.canonical_heat_type(dtype) if dtype is not None else types.float32
    jarr = jnp.geomspace(start, stop, num=num, endpoint=endpoint, dtype=dt.jax_dtype())
    return _finalize(jarr, split, device, comm, dt)


def tri(N: int, M=None, k: int = 0, dtype=types.float32, split=None, device=None, comm=None) -> DNDarray:
    """Lower-triangular ones matrix."""
    dt = types.canonical_heat_type(dtype)
    jarr = jnp.tri(int(N), None if M is None else int(M), k, dtype=dt.jax_dtype())
    return _finalize(jarr, split, device, comm, dt)


def vander(x, N=None, increasing: bool = False) -> DNDarray:
    """Vandermonde matrix of a 1-D input; rows follow the input's split."""
    from .dndarray import DNDarray as _D

    jx = x._jarray if isinstance(x, _D) else jnp.asarray(np.asarray(x))
    jarr = jnp.vander(jx, N=N, increasing=increasing)
    if isinstance(x, _D):
        split = 0 if x.split is not None else None
        jarr = x.comm.shard(jarr, split)
        return _D(jarr, tuple(jarr.shape), types.canonical_heat_type(jarr.dtype), split, x.device, x.comm, True)
    return _finalize(jarr, None, None, None, types.canonical_heat_type(jarr.dtype))


def indices(dimensions, dtype=types.int32, sparse: bool = False):
    """Grid-index arrays (numpy ``indices``); replicated."""
    dt = types.canonical_heat_type(dtype)
    res = jnp.indices(tuple(int(d) for d in dimensions), dtype=dt.jax_dtype(), sparse=sparse)
    if sparse:
        return tuple(_finalize(r, None, None, None, dt) for r in res)
    return _finalize(res, None, None, None, dt)


def ix_(*args):
    """Open-mesh index arrays from 1-D sequences (numpy ``ix_``)."""
    from .dndarray import DNDarray as _D

    js = [a._jarray if isinstance(a, _D) else jnp.asarray(np.asarray(a)) for a in args]
    outs = jnp.ix_(*js)
    return tuple(_finalize(o, None, None, None, types.canonical_heat_type(o.dtype)) for o in outs)


def diag_indices(n: int, ndim: int = 2):
    """Index arrays addressing the main diagonal of an ndim-cube."""
    res = jnp.diag_indices(int(n), int(ndim))
    return tuple(_finalize(r, None, None, None, types.canonical_heat_type(r.dtype)) for r in res)


def diag_indices_from(arr) -> tuple:
    if arr.ndim < 2 or len(set(arr.shape)) != 1:
        raise ValueError("input must be square along every axis")
    return diag_indices(arr.shape[0], arr.ndim)


def tril_indices_from(arr, k: int = 0):
    from .indexing import tril_indices

    if arr.ndim != 2:
        raise ValueError("input must be 2-D")
    return tril_indices(arr.shape[0], k=k, m=arr.shape[1])


def triu_indices_from(arr, k: int = 0):
    from .indexing import triu_indices

    if arr.ndim != 2:
        raise ValueError("input must be 2-D")
    return triu_indices(arr.shape[0], k=k, m=arr.shape[1])


def unravel_index(idx, shape):
    from .dndarray import DNDarray as _D

    ji = idx._jarray if isinstance(idx, _D) else jnp.asarray(np.asarray(idx))
    res = jnp.unravel_index(ji, tuple(int(s) for s in shape))
    if isinstance(idx, _D):
        outs = []
        for r in res:
            r = idx.comm.shard(r, idx.split)
            outs.append(_D(r, tuple(r.shape), types.canonical_heat_type(r.dtype), idx.split, idx.device, idx.comm, True))
        return tuple(outs)
    return tuple(_finalize(r, None, None, None, types.canonical_heat_type(r.dtype)) for r in res)


def ravel_multi_index(multi_index, dims, mode: str = "raise", order: str = "C"):
    from .dndarray import DNDarray as _D

    js = [m._jarray if isinstance(m, _D) else jnp.asarray(np.asarray(m)) for m in multi_index]
    dims_t = tuple(int(d) for d in dims)
    if mode == "raise":
        # numpy contract: out-of-bounds multi-indices are an error; validate
        # eagerly, then index with clip semantics.  ONE sanctioned host_fetch
        # for every axis's (min, max) pair (retried + deadline-guarded, see
        # choose()) instead of 2*ndim naked int() syncs
        checks = [(j, d) for j, d in zip(js, dims_t) if j.size]
        if checks:
            bounds = Communication.host_fetch(
                jnp.stack([jnp.stack([jnp.min(j), jnp.max(j)]) for j, _ in checks])
            )
            for (_j, d), bound in zip(checks, bounds):
                lo, hi = int(bound[0]), int(bound[1])
                if lo < 0 or hi >= d:
                    raise ValueError(f"invalid entry in coordinates array (range [{lo}, {hi}] for dim {d})")
        mode = "clip"
    res = jnp.ravel_multi_index(tuple(js), dims_t, mode=mode, order=order)
    proto = next((m for m in multi_index if isinstance(m, _D)), None)
    if proto is not None:
        r = proto.comm.shard(res, proto.split)
        return _D(r, tuple(r.shape), types.canonical_heat_type(r.dtype), proto.split, proto.device, proto.comm, True)
    return _finalize(res, None, None, None, types.canonical_heat_type(res.dtype))


def _window(fn, M: int) -> DNDarray:
    jarr = fn(int(M))
    return _finalize(jarr, None, None, None, types.canonical_heat_type(jarr.dtype))


def bartlett(M: int) -> DNDarray:
    return _window(jnp.bartlett, M)


def blackman(M: int) -> DNDarray:
    return _window(jnp.blackman, M)


def hamming(M: int) -> DNDarray:
    return _window(jnp.hamming, M)


def hanning(M: int) -> DNDarray:
    return _window(jnp.hanning, M)


def kaiser(M: int, beta: float) -> DNDarray:
    jarr = jnp.kaiser(int(M), beta)
    return _finalize(jarr, None, None, None, types.canonical_heat_type(jarr.dtype))


# the memory ledger may have been env-armed (HEAT_TPU_MEMLEDGER=1) while
# this module was still importing — re-read the flag now (defensive
# module-bottom re-arm, same pattern as _operations/communication)
import sys as _sys  # noqa: E402

_ml = _sys.modules.get("heat_tpu.utils.memledger")
if _ml is not None and _ml.enabled():
    _MEMLEDGER = _ml
del _sys, _ml

__all__ += [
    "bartlett",
    "blackman",
    "diag_indices",
    "diag_indices_from",
    "geomspace",
    "hamming",
    "hanning",
    "identity",
    "indices",
    "ix_",
    "kaiser",
    "ravel_multi_index",
    "tri",
    "tril_indices_from",
    "triu_indices_from",
    "unravel_index",
    "vander",
]
