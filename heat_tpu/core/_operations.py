"""Generalized op dispatch (reference: ``heat/core/_operations.py``, SURVEY §2.1).

The reference's four dispatch helpers do sanitize → local torch call →
explicit collective → wrap.  Here the collective step vanishes: ops run on
globally-shaped sharded ``jax.Array``s and XLA's SPMD partitioner emits any
required communication.  What remains is *metadata propagation* — computing
the result ``split`` under broadcasting and reductions, and reconciling
mismatched splits (an explicit reshard, with the reference's perf warning).

Zero-copy dispatch: each helper's compute tail (op + output-sharding
placement) runs through a sharding-keyed program cache
(``_cache.cached_program``): one jitted executable per ``(op, avals, split)``
signature per comm, with the output sharding compiled in as a
``with_sharding_constraint`` — so a repeated op never re-traces, re-lowers,
or pays an eager post-op ``device_put``.  The in-place dunders additionally
donate their left operand's buffer to the executable (``donate_argnums``),
letting XLA alias input and output storage.  ``_program_op`` gives a library
function the same tail for its whole ``jnp`` expression (``spatial.cdist``:
one launch where op-by-op made eleven).

Program spans: while a profile records (``_cache.recording()``, the
profiler's own answer — no flag here), each helper runs entry to return
under ``ht.dispatch.<kind>`` (stat ``op``) and each cached program's call
under ``ht.dispatch.launch``; with nothing recording an eager op pays two
``is_enabled()`` calls and one frame.  See design.md "Telemetry & metrics".

Observers: every cached program is called in one place, ``_launch``, and
every result passes ``_checked``.  The flight recorder, the memory ledger
and the sanitizer reach them through their slots in ``_hooks``; disarmed,
each costs one attribute load.  What the whole layer costs on the chip is
``dispatch_py_ms_per_job`` in the benchmark's eager cell.
"""

from __future__ import annotations

import contextlib
import contextvars
import warnings
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import _cache, _hooks, sanitation, types
from .dndarray import DNDarray
from .stride_tricks import broadcast_shape, sanitize_axis

__all__ = ["_local_op", "_binary_op", "_reduce_op", "_cum_op", "_program_op"]

# set by the in-place dunders (``__iadd__`` etc. via ``arithmetics._iop``):
# the next _binary_op donates its first operand's buffer to the compiled
# program — numpy's in-place contract realized as XLA buffer aliasing
_DONATE_T1 = contextvars.ContextVar("heat_tpu_donate_t1", default=False)


def _op_name(op) -> str:
    return getattr(op, "__name__", str(op))


def _checked(ret: DNDarray, where: str) -> DNDarray:
    """``ret``, validated by the sanitizer (``HEAT_TPU_CHECKS=1``) when armed:
    the zero-copy paths skip ``DNDarray.__init__``'s enforcement, so the
    sanitizer re-checks here what they assume."""
    chk = _hooks.checks
    return ret if chk is None else chk(ret, where)


def _launch(where: str, op, entry, args: tuple, device, comm, donated=None) -> DNDarray:
    """Call a cached dispatch program and wrap its result: the one place the
    five helpers launch one.  ``entry`` is ``cached_program``'s
    ``(program, shape, heat dtype, split)``; ``where`` names the helper to
    the observers (``dispatch.<kind>``).  While a profile records, the call
    runs under ``ht.dispatch.launch``.  An armed memory ledger sees an
    exception before it re-raises, and ``donated``, the in-place dunders'
    left operand, once the program has really consumed it; an armed flight
    recorder gets the op's name."""
    prog, rshape, rdtype, rsplit = entry
    try:
        res = _cache.launch(prog, *args) if _cache.recording() else prog(*args)
    except Exception as e:
        ml = _hooks.memledger
        if ml is not None:
            ml.note_oom(e, where, None)
        raise
    fr = _hooks.flightrec
    if fr is not None:
        fr.record_dispatch(_op_name(op))
    if donated is not None:
        ml = _hooks.memledger
        # is_deleted() is the runtime's own truth: the plan may have
        # narrowed donation off (shape- or dtype-changing results), and a
        # live buffer is never dropped from the ledger early
        if ml is not None and donated.is_deleted():
            ml.consume(donated)
    return _checked(DNDarray._from_parts(res, rshape, rdtype, rsplit, device, comm), where)


@contextlib.contextmanager
def donate_first_operand():
    """Donate the first operand of the next ``_binary_op`` (in-place dunders)."""
    token = _DONATE_T1.set(True)
    try:
        yield
    finally:
        _DONATE_T1.reset(token)


def _sig(j) -> Tuple:
    """Aval signature of a concrete array: (shape, dtype)."""
    return (j.shape, j.dtype)


def _cacheable(*js) -> bool:
    """True when every array may go through a cached mesh-sharded program:
    concrete (not a tracer — traced dispatch belongs to the surrounding jit)."""
    for j in js:
        if isinstance(j, jax.core.Tracer) or not isinstance(j, jax.Array):
            return False
    return True


def _hashable(obj) -> bool:
    try:
        hash(obj)
    except TypeError:
        return False
    return True


# jnp.add/multiply/... are module-level jnp.ufunc singletons (no
# __qualname__) — stable identities, always cacheable
_UFUNC_TYPES = tuple(t for t in (getattr(jnp, "ufunc", None),) if t is not None)


def _stable_op(op) -> bool:
    """True when ``op``'s identity can key a program cache: a module-level
    function (or jnp.ufunc singleton) whose identity is the same on every
    call.  Per-call lambdas / closures (``lambda a: jnp.clip(a, lo, hi)``)
    get a fresh identity each call — caching them would miss every time,
    churn the LRU, and pin any closure-captured device arrays — so they
    take the eager path."""
    qn = getattr(op, "__qualname__", None)
    if qn is None:
        # partial()s and exotic callables may be per-call too
        return isinstance(op, _UFUNC_TYPES)
    return "<lambda>" not in qn and "<locals>" not in qn


def _reduce_kinds():
    # nan* ops: NaN is the exact masking identity on floats (ignored by the
    # op, and an all-NaN slice still yields NaN as numpy does); on integer
    # dtypes nan-ops degenerate to the plain op, so the base kind applies
    kinds = {}
    for name, kind in (
        ("sum", "zero"), ("nansum", ("nan", "zero")), ("count_nonzero", "zero"),
        ("any", "zero"), ("prod", "one"), ("nanprod", ("nan", "one")), ("all", "one"),
        ("max", "neg"), ("amax", "neg"), ("nanmax", ("nan", "neg")), ("argmax", "neg"),
        ("min", "pos"), ("amin", "pos"), ("nanmin", ("nan", "pos")), ("argmin", "pos"),
    ):
        fn = getattr(jnp, name, None)
        if fn is not None:
            kinds[fn] = kind
    return kinds


_REDUCE_KIND = _reduce_kinds()


def _reduce_identity(op, dtype):
    """Identity fill value for masking the pad region of a ragged array under
    reduction ``op`` (pad-and-mask boundary masking); None = op not maskable."""
    kind = _REDUCE_KIND.get(op)
    if kind is None:
        return None
    dt = jnp.dtype(dtype)
    is_float = jnp.issubdtype(dt, jnp.floating) or jnp.issubdtype(dt, jnp.complexfloating)
    if isinstance(kind, tuple):
        if is_float:
            return jnp.nan
        kind = kind[1]
    if kind == "zero":
        return False if dt == jnp.bool_ else 0
    if kind == "one":
        return True if dt == jnp.bool_ else 1
    if dt == jnp.bool_:
        return False if kind == "neg" else True
    if is_float:
        return -jnp.inf if kind == "neg" else jnp.inf
    info = jnp.iinfo(dt)
    return info.min if kind == "neg" else info.max


def _local_op(op: Callable, x: DNDarray, out: Optional[DNDarray] = None, **kwargs) -> DNDarray:
    """Elementwise op with no communication; split is preserved."""
    if not _cache.recording():
        return _local(op, x, out, kwargs)
    with _cache.TraceAnnotation("ht.dispatch.local", op=_op_name(op)):
        return _local(op, x, out, kwargs)


def _local(op, x, out, kwargs):
    sanitation.sanitize_in(x)
    if x._pad and out is None:
        # ragged fast path: compute on the padded physical array — the pad
        # region produces dead values (masked at reduction boundaries), and
        # the result stays fully sharded with no unpad gather
        phys = op(x._parray, **kwargs)
        if phys.shape == x._parray.shape:
            ret = DNDarray(
                phys,
                x.shape,
                types.canonical_heat_type(phys.dtype),
                x.split,
                x.device,
                x.comm,
                x.balanced,
            )
            return _checked(ret, "dispatch.local.pad")
    comm = x.comm
    j = x._jarray
    if (
        out is None
        and not x._pad
        and _stable_op(op)
        and _cacheable(j)
        and _hashable(kw := tuple(sorted(kwargs.items())))
    ):
        entry = _cache.cached_program(
            comm,
            ("local", op, _sig(j), x.split, kw),
            lambda: _build_local(comm, op, j, x.split, kwargs),
        )
        if entry is not _SLOW:
            return _launch("dispatch.local", op, entry, (j,), x.device, comm)
    result = op(j, **kwargs)
    result = comm.shard(result, x.split if x.split is not None and x.split < result.ndim else None)
    if out is not None:
        sanitation.sanitize_out(out, result.shape, x.split, x.device)
        out._jarray = result.astype(out.dtype.jax_dtype())
        return _checked(out, "dispatch.local.out")
    ret = DNDarray(
        result,
        tuple(result.shape),
        types.canonical_heat_type(result.dtype),
        x.split if x.split is not None and x.split < result.ndim else None,
        x.device,
        x.comm,
        x.balanced,
    )
    return _checked(ret, "dispatch.local.general")


def _compile_tail(comm, compute, want_split, *js):
    """Shared compile tail of the fast paths that take ``compute`` whole
    (_local/_reduce/_cum on one operand, _program on several): resolve the
    result signature of ``compute`` by eval_shape, clamp the split, refuse
    ragged results (``_SLOW`` — pad bookkeeping belongs to the general
    path), and jit (compute + canonical output placement).
    Returns ``(program, result shape, heat dtype, split)`` or ``_SLOW``."""
    aval = jax.eval_shape(compute, *js)
    rshape = tuple(aval.shape)
    rsplit = want_split if want_split is not None and want_split < len(rshape) else None
    if rsplit is not None and comm.size > 1 and rshape[rsplit] % comm.size:
        return _SLOW
    prog = jax.jit(lambda *a: comm.shard(compute(*a), rsplit))
    return prog, rshape, types.canonical_heat_type(aval.dtype), rsplit


def _build_local(comm, op, j, split, kwargs):
    return _compile_tail(comm, lambda a: op(a, **kwargs), split, j)


def _result_split(
    shapes_splits: Tuple[Tuple[Tuple[int, ...], Optional[int]], ...], out_ndim: int
) -> Optional[int]:
    """Result split of a broadcasted op: operand splits aligned to output dims."""
    aligned = []
    for shape, split in shapes_splits:
        if split is None:
            continue
        aligned.append(split + (out_ndim - len(shape)))
    if not aligned:
        return None
    return aligned[0]


def _binary_op(
    op: Callable,
    t1,
    t2,
    out: Optional[DNDarray] = None,
    where=None,
    fn_kwargs: Optional[dict] = None,
) -> DNDarray:
    """Broadcasting binary op with split reconciliation (reference __binary_op)."""
    if not _cache.recording():
        return _binary(op, t1, t2, out, where, fn_kwargs)
    with _cache.TraceAnnotation("ht.dispatch.binary", op=_op_name(op)):
        return _binary(op, t1, t2, out, where, fn_kwargs)


def _binary(op, t1, t2, out, where, fn_kwargs):
    from . import factories

    # ---- planned fast path ------------------------------------------- #
    # ONE dict lookup replaces the whole dispatch prologue: the plan keyed
    # on (op, operand descriptors, donate) pre-resolved broadcasting, split
    # alignment and the result metadata, and holds the compiled executable.
    # Ineligible signatures (pads, mismatched splits, tracers) are
    # negative-cached as _SLOW and take the general path below.
    if out is None and where is None and not fn_kwargs and _stable_op(op):
        d1 = isinstance(t1, DNDarray)
        proto = t1 if d1 else t2 if isinstance(t2, DNDarray) else None
        if proto is not None:
            comm = proto.comm
            k1 = _plan_desc(t1, comm)
            k2 = _plan_desc(t2, comm)
            if k1 is not None and k2 is not None:
                donate = (
                    _DONATE_T1.get()
                    and d1
                    and not (
                        isinstance(t2, DNDarray) and t1._parray is t2._parray
                    )  # one buffer may not be donated and read in one call
                )
                entry = _cache.cached_program(
                    comm,
                    ("binary", op, k1, k2, donate),
                    lambda: _plan_binary(op, t1, t2, donate, comm),
                )
                if entry is not _SLOW:
                    a1 = t1._jarray if d1 else t1
                    a2 = t2._jarray if isinstance(t2, DNDarray) else t2
                    return _launch(
                        "dispatch.binary", op, entry, (a1, a2), proto.device, comm,
                        donated=a1 if donate else None,
                    )

    fn_kwargs = fn_kwargs or {}
    if not isinstance(t1, DNDarray) and not isinstance(t2, DNDarray):
        raise TypeError(f"At least one operand must be a DNDarray, got {type(t1)}, {type(t2)}")

    proto = t1 if isinstance(t1, DNDarray) else t2
    device, comm = proto.device, proto.comm

    def as_operand(t):
        if isinstance(t, DNDarray):
            return t
        if np.isscalar(t) or isinstance(t, (np.ndarray, jax.Array, list, tuple)):
            return factories.array(t, device=device, comm=comm)
        raise TypeError(f"Unsupported operand type {type(t)}")

    # keep Python scalars as weak-typed scalars (jnp promotion handles them);
    # everything else becomes a DNDarray
    t1_scalar = np.isscalar(t1) and not isinstance(t1, (np.generic,))
    t2_scalar = np.isscalar(t2) and not isinstance(t2, (np.generic,))
    a1 = t1 if t1_scalar else as_operand(t1)
    a2 = t2 if t2_scalar else as_operand(t2)

    s1 = a1.split if isinstance(a1, DNDarray) else None
    s2 = a2.split if isinstance(a2, DNDarray) else None
    sh1 = a1.shape if isinstance(a1, DNDarray) else ()
    sh2 = a2.shape if isinstance(a2, DNDarray) else ()
    out_shape = broadcast_shape(sh1, sh2)
    out_ndim = len(out_shape)

    # split reconciliation: both distributed along different output axes →
    # reshard the second operand (comm!), mirroring the reference's warning
    if s1 is not None and s2 is not None:
        al1 = s1 + (out_ndim - len(sh1))
        al2 = s2 + (out_ndim - len(sh2))
        if al1 != al2:
            warnings.warn(
                "Binary operation with mismatched splits triggers a redistribution "
                f"(split {s2} -> {al1 - (out_ndim - len(sh2))}); this is a communication-heavy operation."
            )
            a2 = a2.resplit(al1 - (out_ndim - len(sh2)))
            s2 = a2.split

    res_split = _result_split(
        ((sh1, s1), (sh2, s2)),
        out_ndim,
    )

    # ragged fast path: same shape + same split + same pad → operate on the
    # padded physical arrays directly (pad regions stay dead, no unpad gather)
    if out is None and where is None:
        d1, d2 = isinstance(a1, DNDarray), isinstance(a2, DNDarray)
        p1 = a1._pad if d1 else 0
        p2 = a2._pad if d2 else 0
        if (p1 or p2) and (
            (d1 and d2 and sh1 == sh2 and s1 == s2 and p1 == p2)
            or (d1 and p1 and not d2 and np.isscalar(a2))
            or (d2 and p2 and not d1 and np.isscalar(a1))
        ):
            pj1 = a1._parray if d1 else a1
            pj2 = a2._parray if d2 else a2
            phys = op(pj1, pj2, **fn_kwargs)
            ret = DNDarray(
                phys,
                out_shape,
                types.canonical_heat_type(phys.dtype),
                res_split,
                device,
                comm,
                True,
            )
            return _checked(ret, "dispatch.binary.pad")

    j1 = a1._jarray if isinstance(a1, DNDarray) else a1
    j2 = a2._jarray if isinstance(a2, DNDarray) else a2
    result = op(j1, j2, **fn_kwargs)
    if res_split is not None and res_split >= result.ndim:
        res_split = None
    result = comm.shard(result, res_split)

    if out is not None:
        if where is not None:
            w = where._jarray if isinstance(where, DNDarray) else jnp.asarray(where)
            result = jnp.where(w, result, out._jarray)
            result = comm.shard(result, res_split)
        sanitation.sanitize_out(out, result.shape, res_split, device)
        out._jarray = result.astype(out.dtype.jax_dtype())
        return _checked(out, "dispatch.binary.out")
    if where is not None:
        w = where._jarray if isinstance(where, DNDarray) else jnp.asarray(where)
        result = comm.shard(jnp.where(w, result, jnp.zeros_like(result)), res_split)
    ret = DNDarray(
        result,
        tuple(result.shape),
        types.canonical_heat_type(result.dtype),
        res_split,
        device,
        comm,
        True,
    )
    return _checked(ret, "dispatch.binary.general")


# negative-cache sentinel: this signature must take the general path
# (lookups that find it count under cache_stats()["slow"], not as hits)
_SLOW = _cache.SLOW


def _plan_desc(t, comm):
    """Plan-cache key for one operand, or None when the operand can't key a
    plan (tracer, foreign comm, numpy/list coercions)."""
    if isinstance(t, DNDarray):
        if t.comm is not comm:
            return None
        j = t._parray
        if isinstance(j, jax.core.Tracer) or not isinstance(j, jax.Array):
            return None
        return (t.shape, t.dtype, t.split, t._pad)
    if np.isscalar(t) and not isinstance(t, np.generic):
        # python scalars ride as weak-typed RUNTIME args of the program —
        # promotion matches eager, and the executable is never specialized
        # on the scalar's value
        return type(t)
    return None


def _plan_binary(op, t1, t2, donate, comm):
    """Resolve broadcasting/split metadata for one signature and compile its
    executable — or ``_SLOW`` when the signature needs the general path."""
    d1, d2 = isinstance(t1, DNDarray), isinstance(t2, DNDarray)
    if (d1 and t1._pad) or (d2 and t2._pad):
        return _SLOW  # ragged operands: the pad fast path owns these
    j1 = t1._jarray if d1 else t1
    j2 = t2._jarray if d2 else t2
    if not _cacheable(*(j for j, d in ((j1, d1), (j2, d2)) if d)):
        return _SLOW
    sh1 = t1.shape if d1 else ()
    sh2 = t2.shape if d2 else ()
    s1 = t1.split if d1 else None
    s2 = t2.split if d2 else None
    out_shape = broadcast_shape(sh1, sh2)
    out_ndim = len(out_shape)
    if (
        s1 is not None
        and s2 is not None
        and s1 + (out_ndim - len(sh1)) != s2 + (out_ndim - len(sh2))
    ):
        return _SLOW  # mismatched splits: per-call reshard + warning
    res_split = _result_split(((sh1, s1), (sh2, s2)), out_ndim)
    donate = donate and d1 and out_shape == sh1
    plan = _build_binary(comm, op, j1, j2, res_split, donate, {})
    rshape, rsplit = plan[1], plan[3]
    if rsplit is not None and comm.size > 1 and rshape[rsplit] % comm.size:
        return _SLOW  # ragged result: pad bookkeeping belongs to __init__
    return plan


def _build_binary(comm, op, j1, j2, res_split, donate, fn_kwargs):
    """Compile the (op + output placement) tail of ``_binary_op`` for one
    signature pair; ``donate`` aliases the first operand's buffer into the
    output (the in-place dunders' zero-copy path)."""
    aval = jax.eval_shape(lambda a, b: op(a, b, **fn_kwargs), j1, j2)
    rsplit = res_split if res_split is not None and res_split < len(aval.shape) else None
    # donate only when the result provably replaces the operand's buffer
    # (same shape AND dtype): a shape/dtype-changing result could never
    # alias, and XLA would warn 'donated buffers were not usable' on every
    # such signature — donation is aliasing, not a hint
    donate = (
        donate
        and tuple(aval.shape) == tuple(j1.shape)
        and aval.dtype == j1.dtype
    )
    prog = jax.jit(
        lambda a, b: comm.shard(op(a, b, **fn_kwargs), rsplit),
        donate_argnums=(0,) if donate else (),
    )
    return prog, tuple(aval.shape), types.canonical_heat_type(aval.dtype), rsplit


def _reduce_op(
    op: Callable,
    x: DNDarray,
    axis: Union[int, Tuple[int, ...], None] = None,
    keepdims: bool = False,
    out: Optional[DNDarray] = None,
    dtype=None,
    **kwargs,
) -> DNDarray:
    """Reduction with split bookkeeping (reference __reduce_op).

    Reducing over the split axis (or all axes) yields a replicated result —
    the implicit ``Allreduce``; other axes keep the (shifted) split.
    """
    if not _cache.recording():
        return _reduce(op, x, axis, keepdims, out, dtype, kwargs)
    with _cache.TraceAnnotation("ht.dispatch.reduce", op=_op_name(op)):
        return _reduce(op, x, axis, keepdims, out, dtype, kwargs)


def _reduce(op, x, axis, keepdims, out, dtype, kwargs):
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)

    split = x.split
    if split is None or axis is None:
        new_split = None
        reduces_split = axis is None and split is not None
    else:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        reduces_split = split in axes
        if reduces_split:
            new_split = None
        elif keepdims:
            new_split = split
        else:
            new_split = split - sum(1 for a in axes if a < split)

    # ragged fast path: reduce the padded physical array with the pad region
    # replaced by the op's identity element (pad-and-mask boundary masking)
    fill = _reduce_identity(op, x._parray.dtype) if x._pad else None
    if fill is not None and axis is None and op in (jnp.argmax, jnp.argmin):
        # flat arg-reductions index PHYSICAL coordinates when an interior axis
        # is padded — the flat index would be wrong; take the logical path
        fill = None
    if x._pad and out is None and fill is not None:
        ok_split = reduces_split or (new_split is not None)
        phys = op(x._masked(fill), axis=axis, keepdims=keepdims, **kwargs) if ok_split else None
        if phys is not None and (new_split is None or new_split < phys.ndim):
            if dtype is not None:
                phys = phys.astype(types.canonical_heat_type(dtype).jax_dtype())
            if reduces_split:
                # pad axis reduced away under identity masking: result logical
                phys = x.comm.shard(phys, None)
                ret = DNDarray(
                    phys, tuple(phys.shape), types.canonical_heat_type(phys.dtype),
                    None, x.device, x.comm, True,
                )
                return _checked(ret, "dispatch.reduce.pad")
            # split axis survives (still padded in phys): logical gshape shrinks
            gshape = list(phys.shape)
            gshape[new_split] -= x._pad
            ret = DNDarray(
                phys, tuple(gshape), types.canonical_heat_type(phys.dtype),
                new_split, x.device, x.comm, True,
            )
            return _checked(ret, "dispatch.reduce.pad-split")

    j = x._jarray
    axkey = axis if axis is None or isinstance(axis, int) else tuple(axis)
    if (
        out is None
        and not x._pad
        and _stable_op(op)
        and _cacheable(j)
        and _hashable(kw := tuple(sorted(kwargs.items())))
    ):
        dkey = None if dtype is None else types.canonical_heat_type(dtype)
        entry = _cache.cached_program(
            x.comm,
            ("reduce", op, _sig(j), axkey, keepdims, dkey, new_split, kw),
            lambda: _build_reduce(x.comm, op, j, axis, keepdims, dkey, new_split, kwargs),
        )
        if entry is not _SLOW:
            return _launch("dispatch.reduce", op, entry, (j,), x.device, x.comm)
    result = op(j, axis=axis, keepdims=keepdims, **kwargs)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_dtype())
    if new_split is not None and new_split >= result.ndim:
        new_split = None
    result = x.comm.shard(result, new_split)
    if out is not None:
        sanitation.sanitize_out(out, result.shape, new_split, x.device)
        out._jarray = result.astype(out.dtype.jax_dtype())
        return _checked(out, "dispatch.reduce.out")
    ret = DNDarray(
        result,
        tuple(result.shape),
        types.canonical_heat_type(result.dtype),
        new_split,
        x.device,
        x.comm,
        True,
    )
    return _checked(ret, "dispatch.reduce.general")


def _build_reduce(comm, op, j, axis, keepdims, dtype, new_split, kwargs):
    jdt = None if dtype is None else dtype.jax_dtype()

    def compute(a):
        r = op(a, axis=axis, keepdims=keepdims, **kwargs)
        return r if jdt is None else r.astype(jdt)

    return _compile_tail(comm, compute, new_split, j)


def _cum_op(
    op: Callable,
    x: DNDarray,
    axis: int,
    dtype=None,
    out: Optional[DNDarray] = None,
) -> DNDarray:
    """Cumulative op along ``axis`` (reference __cum_op via Exscan; here XLA scan)."""
    if not _cache.recording():
        return _cum(op, x, axis, dtype, out)
    with _cache.TraceAnnotation("ht.dispatch.cum", op=_op_name(op)):
        return _cum(op, x, axis, dtype, out)


def _cum(op, x, axis, dtype, out):
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if axis is not None and x._pad and out is None:
        # ragged fast path: identity-masked physical cumulation — the valid
        # prefix is exact (pad contributes the identity); pad region is dead
        fill = {getattr(jnp, "cumsum", None): 0, getattr(jnp, "cumprod", None): 1}.get(op)
        if fill is not None:
            src = x._masked(fill) if axis == x.split else x._parray
            phys = op(src, axis=axis)
            if dtype is not None:
                phys = phys.astype(types.canonical_heat_type(dtype).jax_dtype())
            ret = DNDarray(
                phys, x.shape, types.canonical_heat_type(phys.dtype),
                x.split, x.device, x.comm, True,
            )
            return _checked(ret, "dispatch.cum.pad")
    j = x._jarray
    split = None if axis is None else x.split
    if out is None and not x._pad and _stable_op(op) and _cacheable(j):
        dkey = None if dtype is None else types.canonical_heat_type(dtype)
        entry = _cache.cached_program(
            x.comm,
            ("cum", op, _sig(j), axis, dkey, split),
            lambda: _build_cum(x.comm, op, j, axis, dkey, split),
        )
        if entry is not _SLOW:
            return _launch("dispatch.cum", op, entry, (j,), x.device, x.comm)
    if axis is None:
        # numpy semantics: flatten
        flat = j.reshape(-1)
        result = op(flat, axis=0)
    else:
        result = op(j, axis=axis)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_dtype())
    result = x.comm.shard(result, split)
    if out is not None:
        sanitation.sanitize_out(out, result.shape, split, x.device)
        out._jarray = result.astype(out.dtype.jax_dtype())
        return _checked(out, "dispatch.cum.out")
    ret = DNDarray(
        result,
        tuple(result.shape),
        types.canonical_heat_type(result.dtype),
        split,
        x.device,
        x.comm,
        True,
    )
    return _checked(ret, "dispatch.cum.general")


def _build_cum(comm, op, j, axis, dtype, split):
    jdt = None if dtype is None else dtype.jax_dtype()

    def compute(a):
        r = op(a.reshape(-1), axis=0) if axis is None else op(a, axis=axis)
        return r if jdt is None else r.astype(jdt)

    return _compile_tail(comm, compute, split, j)


def _program_op(compute: Callable, operands: tuple, split: Optional[int], static: tuple = ()) -> DNDarray:
    """A library function's whole computation, ``compute(*arrays, *static)``,
    as ONE cached program where it would otherwise be one launch per ``jnp``
    call (``spatial.cdist``: eleven).  ``compute`` is a module-level function
    (its identity keys the cache); ``operands`` are DNDarrays, the first at
    least, or python scalars that ride as runtime arguments (a new value
    compiles nothing); ``split`` is the result's.  Which path runs is read
    off the operands, as in the other tails: concrete and pad-free take the
    program, whose result leaves it on the canonical sharding; tracers,
    padded operands and ragged results call ``compute`` itself, un-jitted,
    and place the result."""
    if not _cache.recording():
        return _program(compute, operands, split, static)
    with _cache.TraceAnnotation("ht.dispatch.program", op=_op_name(compute)):
        return _program(compute, operands, split, static)


def _program(compute, operands, split, static):
    proto = operands[0]
    comm = proto.comm
    args = tuple(t._jarray if isinstance(t, DNDarray) else t for t in operands)
    descs = tuple(_plan_desc(t, comm) for t in operands)
    if None not in descs:
        entry = _cache.cached_program(
            comm,
            ("program", compute, descs, split, static),
            lambda: _build_program(comm, compute, operands, args, split, static),
        )
        if entry is not _SLOW:
            return _launch("dispatch.program", compute, entry, args, proto.device, comm)
    result = compute(*args, *static)
    if split is not None and split >= result.ndim:
        split = None
    result = comm.shard(result, split)
    ret = DNDarray(
        result,
        tuple(result.shape),
        types.canonical_heat_type(result.dtype),
        split,
        proto.device,
        comm,
        True,
    )
    return _checked(ret, "dispatch.program.general")


def _build_program(comm, compute, operands, args, split, static):
    if any(isinstance(t, DNDarray) and t._pad for t in operands):
        return _SLOW  # padded operands: compute sees the logical arrays
    return _compile_tail(comm, lambda *a: compute(*a, *static), split, *args)
