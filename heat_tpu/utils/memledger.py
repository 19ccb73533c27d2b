"""Device-memory ledger: per-buffer provenance for the resource that kills jobs.

The observability plane sees time (telemetry spans/histograms), wire bytes
(seq-stamped collectives), and causality (trace ids) — but it was blind to
device memory: an XLA ``RESOURCE_EXHAUSTED`` died with no account of what
was live or why.  This module is the missing ledger: a **weakref-keyed
registry of live device buffers**, registered at the same choke points the
runtime sanitizer already owns, each entry carrying

- ``nbytes`` computed from the aval (shape × itemsize — no value read, no
  device sync);
- the **minting site**: the op name (``add``, ``arange``, ``resplit``, a
  checkpoint load), the registration choke point (``factory`` / ``dispatch``
  / ``resplit`` / ``ckpt``), the enclosing telemetry span (when armed) and
  the ambient trace id (the PR 11 contextvar — read even with telemetry
  disabled);
- a **category** — ``param`` / ``opt-state`` / ``activation`` /
  ``transient`` — inferred from the span/site context, overridable with the
  explicit ``category=`` kwarg or scoped via :func:`category`.

**Lifecycle.**  A buffer leaves the ledger three ways: its Python object
dies (the weakref callback decrements — CPython refcounting makes this
deterministic), it is **donated/deleted** (:func:`consume`, called at the
``device_put(donate=True)`` / ``.delete()`` sites), or it is aliased in
place by a donating update (:func:`transfer` — the tiled-resplit
accumulator: the entry moves to the new handle without double-counting the
shared buffer).  ``mem.live_bytes`` therefore telescopes exactly against
the runtime's own byte accounting (asserted by the reconciliation tests).

**Gauges.**  ``live_bytes()`` rides a gauge (a ``utils.profiler`` counter
provider + the ``/metrics`` endpoint reads this module directly);
``peak_bytes()`` is mirrored through the existing ``profiler.counter_max``
high-water path.  Both come per-category too.  Where the backend provides
``device.memory_stats()`` (TPU/GPU; CPU returns None), :func:`snapshot`
cross-checks the ledger against the allocator's ``bytes_in_use``.

**OOM post-mortem.**  Allocation-failure handling closes the loop:
``alloc_check(nbytes, where)`` fires the new ``mem.alloc`` fault site at
the resplit/factory staging points (so chaos CI can inject a deterministic
allocation failure), and the dispatch/resplit paths catch
``RESOURCE_EXHAUSTED`` (or an injected ``mem.alloc`` fault) and call
:func:`note_oom`, which renders a ledger dump — the failed request size
plus the top-K live buffers by bytes with full minting provenance — into
the crash-durable flight ring (``mem`` + ``membuf`` records) before the
error re-raises.  ``scripts/postmortem.py`` turns those records into a
``verdict=oom`` naming the rank, the failed allocation and the dominant
live buffers; ``scripts/telemetry_report.py`` renders the per-rank
watermark timeline and top-buffers table from the same records.

**Cost.**  :func:`enable`/:func:`disable` set the ``memledger`` slot of
``core._hooks``, which every instrumentation site reads: disarmed, one
attribute load.  Armed, a dispatch registration is one weakref + one dict
store + aval byte math.  What the whole dispatch layer costs on the chip is
``dispatch_py_ms_per_job`` (``chipbench``).

Arming: ``memledger.enable()`` in-process or ``HEAT_TPU_MEMLEDGER=1`` in
the environment (checked once at import; ``core.io`` imports this module
at package import, so the env arming is process-wide).

Stdlib-only at module level on purpose: jax classes are resolved through
``sys.modules`` at enable time, so the module stays loadable from tooling
that never imports jax.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import sys
import threading
import time
import weakref
from typing import Dict, List, Optional

__all__ = [
    "enable",
    "disable",
    "enabled",
    "register",
    "register_dispatch",
    "set_dispatch_threshold",
    "reclassify",
    "consume",
    "transfer",
    "category",
    "category_of",
    "live_bytes",
    "peak_bytes",
    "live_by_category",
    "peak_by_category",
    "top_buffers",
    "counters",
    "snapshot",
    "reset_peak",
    "peak_window",
    "alloc_check",
    "is_oom",
    "note_oom",
    "dump_to_ring",
    "CATEGORIES",
    "OOM_TOP_K",
]

CATEGORIES = ("param", "opt-state", "activation", "transient")
OOM_TOP_K = 5

# dispatch-tier registration threshold (bytes): the per-op hot path may
# not afford a weakref + entry per µs-scale intermediate (weakref creation
# on a dispatching main thread measurably taxes the GIL the async XLA
# workers need — the same contention the flight recorder's coalesced "d"
# records exist for), so dispatch outputs BELOW this size coalesce into
# the ``mem.dispatch.small_*`` counters (volume visible, never silently
# dropped) and only buffers of consequence pay for full provenance.
# Factories, resplit, checkpoint load and optimizer init register
# EVERYTHING — none of them is on the µs dispatch path.
DISPATCH_MIN_DEFAULT = 1 << 20  # 1 MiB

# a new peak is mirrored into the flight ring as a ``mem`` watermark record
# when it exceeds the last recorded one by this fraction — bounds the record
# volume without losing the shape of the high-water timeline
WATERMARK_FRACTION = 0.05

_ENABLED = False
_lock = threading.Lock()
_entries: Dict[int, "_Entry"] = {}
_live = 0
_peak = 0
_live_cat: Dict[str, int] = {}
_peak_cat: Dict[str, int] = {}
# open peak_window() scopes: each dict tracks the max live bytes seen
# while the window was open (updated under the lock on every live
# increase — BEFORE the global-peak early return, since a window opened
# below the all-time high must still see its own local maximum)
_windows: List[dict] = []
_registered_total = 0
_oom_dumps = 0
_last_ring_peak = 0
# coalesced under-threshold dispatch volume: [count, bytes] — the hot
# tier takes no lock (see register_dispatch for the lost-increment trade)
_small = [0, 0]
_dispatch_min = DISPATCH_MIN_DEFAULT

# jax classes resolved at enable() via sys.modules (never imported here).
# jax.Array is an ABC — its __instancecheck__ is measurable on the dispatch
# path — so concrete-type verdicts are memoized per type in _TYPE_OK and
# the ABC protocol only runs once per distinct type.
_JAX_ARRAY: Optional[type] = None
_JAX_TRACER: Optional[type] = None
_TYPE_OK: Dict[type, bool] = {}

_provider_registered = False

# scoped category default (the ergonomic override: ``with
# memledger.category("param"): load_checkpoint(...)``)
_CATEGORY: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "heat_tpu_mem_category", default=None
)


class _Entry:
    __slots__ = ("ref", "key", "nbytes", "op", "site", "cat", "span", "tid", "t")

    def __init__(self, ref, key, nbytes, op, site, cat, span, tid, t):
        self.ref = ref
        self.key = key
        self.nbytes = nbytes
        self.op = op
        self.site = site
        self.cat = cat
        self.span = span
        self.tid = tid
        self.t = t


# ---------------------------------------------------------------------- #
# provenance helpers (cheap, armed-only)
# ---------------------------------------------------------------------- #
def _telemetry():
    return sys.modules.get("heat_tpu.utils.telemetry")


def _flightrec():
    fr = sys.modules.get("heat_tpu.utils.flightrec")
    if fr is not None and fr.enabled():
        return fr
    return None


def _current_span_name() -> Optional[str]:
    tel = _telemetry()
    if tel is None or not getattr(tel, "_ENABLED", False):
        return None
    try:
        stack = tel._stack()
        return stack[-1].name if stack else None
    except Exception:
        return None


def _current_trace_id() -> Optional[str]:
    tel = _telemetry()
    if tel is None:
        return None
    try:
        return tel.current_trace_id()
    except Exception:
        return None


def _nbytes_of(arr) -> int:
    """Bytes from the aval: shape product × dtype itemsize — metadata only,
    identical math to ``communication._payload_nbytes``."""
    try:
        n = 1
        for s in arr.shape:
            n *= int(s)
        return n * arr.dtype.itemsize
    except Exception:
        return 0


def _is_concrete(arr) -> bool:
    """True for a concrete jax array (a real device buffer); tracers and
    foreign objects are never ledger entries.  Memoized per type — the ABC
    ``isinstance`` protocol costs real time on the dispatch path and the
    set of distinct runtime array types is tiny."""
    t = type(arr)
    ok = _TYPE_OK.get(t)
    if ok is None:
        ok = (
            _JAX_ARRAY is not None
            and isinstance(arr, _JAX_ARRAY)
            and not (_JAX_TRACER is not None and isinstance(arr, _JAX_TRACER))
        )
        _TYPE_OK[t] = ok
    return ok


def _infer_category(site: str, span: Optional[str]) -> str:
    """The category taxonomy, applied when no override is in scope:
    checkpoint loads mint ``param``, resplit tiles mint ``transient``,
    buffers minted inside an optimizer/DASO step span are ``opt-state``,
    everything else is ``activation`` (the honest default for dispatch
    intermediates and bare factory outputs)."""
    if site == "ckpt":
        return "param"
    if site == "resplit.tile":
        return "transient"
    if span:
        if span.startswith(("optim.", "daso.")):
            return "opt-state"
        if span.startswith(("io.", "ckpt")):
            return "param"
    return "activation"


# ---------------------------------------------------------------------- #
# core registry operations
# ---------------------------------------------------------------------- #
# deferred finalizer queue: weakref callbacks can fire on ANY thread at
# ANY allocation point — including while THIS module holds the
# (non-reentrant) _lock, where taking it again would self-deadlock — so
# the callback only records the death (list.append is GIL-atomic) and the
# decrement happens at the next locked operation via _drain_locked()
_dead: List = []


def _on_collect(wr, key):
    """Weakref finalizer: the buffer's Python handle died.  Deferred —
    see ``_dead`` above; the identity check (a reused id must never pop a
    later buffer's entry) happens at drain time."""
    try:
        _dead.append((key, wr))
    except Exception:  # interpreter shutdown: module globals may be gone
        pass


def _drain_locked() -> None:
    """Apply the deferred finalizer decrements.  Caller holds ``_lock``."""
    global _live
    while _dead:
        try:
            key, wr = _dead.pop()
        except IndexError:
            return
        e = _entries.get(key)
        if e is None or e.ref is not wr:
            continue  # stale callback for a reused id — not our entry
        del _entries[key]
        _live -= e.nbytes
        _bump_cat_locked(e.cat, -e.nbytes)


def _drain() -> None:
    """Take the lock and drain iff there is anything pending — the read
    APIs call this so gauges never lag behind dead buffers."""
    if _dead:
        with _lock:
            _drain_locked()


def _bump_peak_locked() -> None:
    """Called under the lock after a live-bytes increase: update the peak
    high-water marks, mirror the total into ``profiler.counter_max``, and
    emit a ``mem`` watermark record into the flight ring when the new peak
    clears the hysteresis threshold."""
    global _peak, _last_ring_peak
    for w in _windows:
        if _live > w["peak"]:
            w["peak"] = _live
    if _live <= _peak:
        return
    _peak = _live
    prof = sys.modules.get("heat_tpu.utils.profiler")
    if prof is not None:
        try:
            prof.counter_max("mem.peak_bytes", _peak)
        except Exception:
            pass
    fr = _flightrec()
    if fr is not None and _peak > _last_ring_peak * (1.0 + WATERMARK_FRACTION):
        _last_ring_peak = _peak
        try:
            fr.record_event(
                "mem",
                live=int(_live),
                peak=int(_peak),
                by={c: int(v) for c, v in _live_cat.items() if v > 0},
            )
        except Exception:
            pass


def _bump_cat_locked(cat: str, delta: int) -> None:
    """Adjust one category's live bytes (under the lock) and keep its own
    independent high-water mark."""
    v = _live_cat.get(cat, 0) + delta
    _live_cat[cat] = v
    if v > _peak_cat.get(cat, 0):
        _peak_cat[cat] = v


def set_dispatch_threshold(nbytes: int) -> int:
    """Set the dispatch-tier full-registration threshold (bytes); returns
    the previous value.  0 registers every dispatch output with full
    provenance — the reconciliation tests run that way; production keeps
    the default so µs-scale intermediates stay one coalesced counter."""
    global _dispatch_min
    prev = _dispatch_min
    _dispatch_min = int(nbytes)
    return prev


def register_dispatch(arr, op: Optional[str] = None) -> None:
    """The dispatch tails' recorder — the leanest path here (one call,
    aval byte math, one coalesced counter bump for under-threshold
    buffers): weakref + entry creation per µs-scale dispatch measurably
    taxes the GIL the async XLA workers are bidding for (the flight
    recorder's coalescing lesson, re-measured for this module), so only
    buffers of consequence (≥ the dispatch threshold) pay for the full
    provenance entry."""
    if not _ENABLED:
        return
    try:
        n = 1
        for s in arr.shape:
            n *= int(s)
        n *= arr.dtype.itemsize
    except Exception:
        return
    if n < _dispatch_min:
        # lock-free slot bumps: `list[i] += x` is a read-modify-write, so a
        # cross-thread interleave can lose one count — the flightrec
        # record_dispatch trade, accepted for the same reason (any lock
        # here taxes the GIL the XLA workers need); the volume stays a
        # visible counter either way, never a silent drop of the tier
        _small[0] += 1
        _small[1] += n
        return
    register(arr, op=op, site="dispatch", nbytes=n)


def register(
    arr,
    op: Optional[str] = None,
    site: str = "dispatch",
    category: Optional[str] = None,
    nbytes: Optional[int] = None,
) -> None:
    """Register a live device buffer (idempotent per buffer: a second
    registration of the same object is a cheap no-op, so choke points may
    overlap).

    ``category`` overrides the inference (the resplit call sites pass their
    source's category through explicitly — captured BEFORE the source is
    consumed, which is why there is no implicit inherit-from parameter
    here)."""
    global _live, _registered_total
    if not _ENABLED:
        return
    if not _provider_registered:
        # env-armed processes enable() at memledger import, BEFORE
        # utils.profiler exists in sys.modules — retry the gauge-provider
        # registration here (one bool check once it has succeeded), so the
        # documented profiler gauge contract holds however arming happened
        _ensure_provider()
    # already-registered fast path FIRST (one dict probe): overlapping
    # choke points (a factory output flowing through _from_parts) cost a
    # lookup, not a duplicate entry
    e = _entries.get(id(arr))
    if e is not None and e.ref() is arr:
        return
    if not _is_concrete(arr):
        return
    key = id(arr)
    if nbytes is None:
        nbytes = _nbytes_of(arr)
    span = _current_span_name()
    if category is None:
        category = _CATEGORY.get() or _infer_category(site, span)
    if op is None:
        # frame peek: the nearest PUBLIC function up-stack is the minting
        # op (``add`` above ``_binary_op`` above ``_from_parts``); only
        # paid when the caller had nothing better, and only for
        # full-provenance registrations
        try:
            op = "?"
            for depth in (1, 2, 3, 4, 5, 6):
                name = sys._getframe(depth).f_code.co_name
                if name in ("register", "register_dispatch") or name.startswith("<"):
                    continue  # our own shims and <listcomp>/<genexpr> frames
                op = name
                if not name.startswith("_"):
                    break
        except Exception:
            pass
    tid = _current_trace_id()
    entry = _Entry(None, key, nbytes, op, site, category, span, tid, time.time())
    wr = weakref.ref(arr, lambda r, k=key: _on_collect(r, k))
    entry.ref = wr
    with _lock:
        _drain_locked()
        old = _entries.get(key)
        if old is not None and old.ref() is arr:
            return  # lost the race to an identical registration
        if old is not None:
            # stale entry whose callback never ran (shouldn't happen under
            # refcounting, but never let it corrupt the ledger)
            _live -= old.nbytes
            _bump_cat_locked(old.cat, -old.nbytes)
        _entries[key] = entry
        _live += nbytes
        _bump_cat_locked(category, nbytes)
        _registered_total += 1
        _bump_peak_locked()


def reclassify(arr, op: Optional[str] = None, category: Optional[str] = None,
               site: Optional[str] = None) -> None:
    """Update an existing entry's provenance in place (the tiled-resplit
    output stops being 'transient' once it IS the destination array)."""
    global _live
    if not _ENABLED:
        return
    with _lock:
        _drain_locked()
        e = _entries.get(id(arr))
        if e is None or e.ref() is not arr:
            return
        if op is not None:
            e.op = op
        if site is not None:
            e.site = site
        if category is not None and category != e.cat:
            _bump_cat_locked(e.cat, -e.nbytes)
            _bump_cat_locked(category, e.nbytes)
            e.cat = category


def consume(arr) -> None:
    """Donation/deletion decrement: the buffer's storage is gone (donated
    into a program, ``.delete()``-ed) even though the Python handle may
    linger.  Safe to call for unregistered or already-consumed buffers."""
    global _live
    if not _ENABLED or arr is None:
        return
    with _lock:
        _drain_locked()
        e = _entries.get(id(arr))
        if e is None or e.ref() is not arr:
            return
        del _entries[id(arr)]
        _live -= e.nbytes
        _bump_cat_locked(e.cat, -e.nbytes)


def transfer(old, new, op: Optional[str] = None) -> None:
    """Move a registration from ``old`` to ``new`` WITHOUT the transient
    double-count: a donating in-place update (the tiled-resplit accumulator)
    aliases the same physical buffer under a new Python handle, so the swap
    must be atomic against the peak tracking."""
    global _live
    if not _ENABLED:
        return
    if not _is_concrete(new):
        consume(old)
        return
    with _lock:
        _drain_locked()
        e = _entries.pop(id(old), None) if old is not None else None
        if e is not None and e.ref() is not old:
            _entries[e.key] = e  # id collision with a different object
            e = None
        new_bytes = _nbytes_of(new)
        if e is None:
            # nothing to move: fall through to a plain registration
            span = _current_span_name()
            cat = _CATEGORY.get() or _infer_category("dispatch", span)
            e = _Entry(None, 0, 0, op or "transfer", "dispatch", cat, span,
                       _current_trace_id(), time.time())
            _live_cat[e.cat] = _live_cat.get(e.cat, 0)
        # net live delta is new - old (0 for the aliased same-shape update)
        _live += new_bytes - e.nbytes
        _bump_cat_locked(e.cat, new_bytes - e.nbytes)
        key = id(new)
        stale = _entries.get(key)
        if stale is not None and stale.ref() is not new:
            # a dead predecessor at a reused id whose deferred callback has
            # not drained yet: decrement it HERE or its bytes leak forever
            # (register() has the identical guard)
            _live -= stale.nbytes
            _bump_cat_locked(stale.cat, -stale.nbytes)
        wr = weakref.ref(new, lambda r, k=key: _on_collect(r, k))
        moved = _Entry(wr, key, new_bytes, op or e.op, e.site, e.cat, e.span,
                       e.tid, e.t)
        _entries[key] = moved
        _bump_peak_locked()


@contextlib.contextmanager
def category(name: str):
    """Scope a default category for every registration in the block —
    the explicit-override story for call sites that cannot pass the kwarg
    through (``with memledger.category("param"): model.init(...)``)."""
    token = _CATEGORY.set(str(name))
    try:
        yield
    finally:
        _CATEGORY.reset(token)


def category_of(arr) -> Optional[str]:
    """The registered category of ``arr``, or None when unregistered."""
    e = _entries.get(id(arr))
    if e is not None and e.ref() is arr:
        return e.cat
    return None


# ---------------------------------------------------------------------- #
# readout
# ---------------------------------------------------------------------- #
def live_bytes() -> int:
    _drain()
    return max(_live, 0)


def peak_bytes() -> int:
    return _peak


def live_by_category() -> Dict[str, int]:
    _drain()
    return {c: v for c, v in sorted(_live_cat.items()) if v > 0}


def peak_by_category() -> Dict[str, int]:
    return {c: v for c, v in sorted(_peak_cat.items()) if v > 0}


def top_buffers(k: int = OOM_TOP_K) -> List[dict]:
    """The K largest live buffers with full minting provenance, largest
    first — the OOM dump's payload and the report's table."""
    with _lock:
        _drain_locked()
        rows = [
            {
                "nbytes": e.nbytes,
                "op": e.op,
                "site": e.site,
                "category": e.cat,
                "span": e.span,
                "tid": e.tid,
                "age_s": round(time.time() - e.t, 3),
            }
            for e in _entries.values()
            if e.ref() is not None
        ]
    rows.sort(key=lambda r: -r["nbytes"])
    return rows[:k]


def counters() -> Dict[str, int]:
    """The gauge view: live/peak totals + per-category — read by the
    ``utils.profiler`` provider, the ``/metrics`` endpoint and the
    heartbeat beacon."""
    if not _provider_registered:
        _ensure_provider()
    out = {
        "mem.live_bytes": live_bytes(),
        "mem.peak_bytes": _peak,
        "mem.buffers": len(_entries),
        "mem.registered.total": _registered_total,
    }
    if _small[0]:
        # cumulative under-threshold dispatch volume (count, bytes): the
        # hot tier coalesces these instead of minting entries — visible
        # here so the cap is never silent
        out["mem.dispatch.small.count"] = _small[0]
        out["mem.dispatch.small.bytes"] = _small[1]
    if _oom_dumps:
        out["mem.oom.dumps"] = _oom_dumps
    for c, v in live_by_category().items():
        out[f"mem.live_bytes.{c}"] = v
    for c, v in peak_by_category().items():
        out[f"mem.peak_bytes.{c}"] = v
    return out


def device_memory_stats() -> Optional[dict]:
    """The backend allocator's own view (``device.memory_stats()``) where
    it provides one — TPU/GPU report ``bytes_in_use``/``peak_bytes_in_use``;
    CPU returns None.  Used as the ledger's cross-check, never its source
    of truth (the allocator sees XLA temporaries the ledger deliberately
    does not)."""
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return None
    try:
        stats = jax_mod.local_devices()[0].memory_stats()
    except Exception:
        return None
    return dict(stats) if stats else None


def snapshot() -> dict:
    """One structured view of everything: totals, categories, top buffers,
    and the allocator cross-check when the backend provides it."""
    out = {
        "live_bytes": live_bytes(),
        "peak_bytes": _peak,
        "buffers": len(_entries),
        "live_by_category": live_by_category(),
        "peak_by_category": peak_by_category(),
        "top_buffers": top_buffers(),
    }
    dev = device_memory_stats()
    if dev is not None:
        out["device_bytes_in_use"] = int(dev.get("bytes_in_use", 0))
        if "peak_bytes_in_use" in dev:
            out["device_peak_bytes_in_use"] = int(dev["peak_bytes_in_use"])
    return out


def reset_peak() -> None:
    """Re-anchor the high-water marks at the current live set (benchmark
    and reconciliation-test boundary)."""
    global _peak, _last_ring_peak
    with _lock:
        _drain_locked()
        _peak = _live
        _peak_cat.clear()
        for c, v in _live_cat.items():
            if v > 0:
                _peak_cat[c] = v
        _last_ring_peak = 0


@contextlib.contextmanager
def peak_window():
    """Scoped incremental-peak measurement: yields a dict whose ``base``
    is the live bytes at entry and whose ``peak`` tracks the maximum live
    bytes observed while the block runs (updated on every registration,
    independent of the GLOBAL high-water mark — a window opened below the
    all-time peak still sees its own local maximum).  ``peak - base`` is
    the block's incremental device-memory footprint — what the federation
    admission predictor records per job kind (``serving.make_executor``
    brackets each batch in one of these).  Nestable and thread-tolerant:
    concurrent registrations from other threads inflate the window (an
    honest over-estimate for admission — never an under-estimate of this
    block alone... beyond what concurrency genuinely added)."""
    with _lock:
        _drain_locked()
        w = {"base": _live, "peak": _live}
        _windows.append(w)
    try:
        yield w
    finally:
        with _lock:
            _drain_locked()
            try:
                _windows.remove(w)
            except ValueError:
                pass


# ---------------------------------------------------------------------- #
# allocation-failure path: the mem.alloc fault site + the OOM dump
# ---------------------------------------------------------------------- #
# the most recent alloc_check request: [nbytes, where] — lock-free slots
# (GIL-atomic single-slot stores); dump_oom falls back to it when its
# caller could not size the failed request itself, provided the dump is
# for the SAME site (a stale request from another path must not lie)
_pending_alloc: List = [None, None]


def alloc_check(nbytes: Optional[int], where: str) -> None:
    """Record the pending allocation (``nbytes`` at ``where``) and fire
    the ``mem.alloc`` fault site ahead of it (the resplit/tile staging
    points) — chaos CI injects a deterministic allocation failure here;
    the surrounding catch treats it exactly like a real
    RESOURCE_EXHAUSTED, and the recorded request sizes the dump when the
    catch site cannot."""
    _pending_alloc[0] = nbytes
    _pending_alloc[1] = where
    from . import faults as _flt

    _flt.fire("mem.alloc")


def is_oom(exc: BaseException) -> bool:
    """True when ``exc`` is an allocation failure: a real XLA
    ``RESOURCE_EXHAUSTED`` or an injected ``mem.alloc`` fault (whose
    message names the site)."""
    text = str(exc)
    return "RESOURCE_EXHAUSTED" in text or "mem.alloc" in text


def note_oom(exc: BaseException, where: str, nbytes: Optional[int]) -> bool:
    """Called from the dispatch/resplit catch blocks with the failure in
    hand: when it is OOM-shaped, render the ledger dump into the flight
    ring (and return True); any other failure passes through untouched.
    The caller ALWAYS re-raises — this only explains, never swallows."""
    if not is_oom(exc):
        return False
    dump_oom(where=where, req_bytes=nbytes, err=type(exc).__name__)
    return True


def dump_oom(where: str, req_bytes: Optional[int], err: str = "") -> None:
    """The post-mortem payload: one ``mem`` record with ``oom=1`` (failed
    request size, site, live/peak at failure) followed by one ``membuf``
    record per top-K live buffer with its minting provenance — all into
    the crash-durable ring, so the account survives the death that usually
    follows."""
    global _oom_dumps
    _oom_dumps += 1
    if req_bytes is None and _pending_alloc[1] == where:
        # the caller could not size the request; the alloc_check that
        # preceded the failure AT THIS SITE could
        req_bytes = _pending_alloc[0]
    fr = _flightrec()
    if fr is None:
        return
    try:
        fr.record_event(
            "mem",
            oom=1,
            where=where,
            req=int(req_bytes or 0),
            live=int(live_bytes()),
            peak=int(_peak),
            err=err,
        )
        for i, b in enumerate(top_buffers(OOM_TOP_K)):
            fr.record_event(
                "membuf",
                i=i,
                op=b["op"],
                nb=int(b["nbytes"]),
                cat=b["category"],
                **({"span": b["span"]} if b["span"] else {}),
                **({"tid": b["tid"]} if b["tid"] else {}),
            )
        fr.sync()
    except Exception:
        pass


def dump_to_ring() -> None:
    """Write the current watermark + top buffers into the flight ring on
    demand (the mp dryrun worker's end-of-run attestation)."""
    fr = _flightrec()
    if fr is None:
        return
    try:
        # att=1 marks a DUMP header (vs a mid-burst watermark record):
        # the post-mortem membuf collectors stop at it
        fr.record_event(
            "mem",
            att=1,
            live=int(live_bytes()),
            peak=int(_peak),
            by={c: int(v) for c, v in live_by_category().items()},
        )
        for i, b in enumerate(top_buffers(OOM_TOP_K)):
            fr.record_event(
                "membuf", i=i, op=b["op"], nb=int(b["nbytes"]),
                cat=b["category"],
            )
    except Exception:
        pass


# ---------------------------------------------------------------------- #
# enable / disable — the runtime's ``memledger`` observer slot
# ---------------------------------------------------------------------- #
def _set_slot(on: bool) -> None:
    """Point the runtime's ``memledger`` observer slot at this module, or
    clear it.  A standalone load (tooling) has no runtime to observe."""
    if __package__:
        from ..core import _hooks

        _hooks.memledger = sys.modules[__name__] if on else None


def _ensure_provider() -> None:
    """Register the pre-prefixed ``mem`` provider with ``utils.profiler``
    iff it is already loaded (importing it pulls jax)."""
    global _provider_registered
    if _provider_registered:
        return
    prof = sys.modules.get("heat_tpu.utils.profiler")
    if prof is None:
        return
    prof.register_counter_provider("mem", counters)
    _provider_registered = True


def enabled() -> bool:
    return _ENABLED


def enable() -> None:
    """Arm the ledger: resolve the jax classes, set the observer slot,
    register the profiler gauge provider."""
    global _ENABLED, _JAX_ARRAY, _JAX_TRACER
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        try:
            import jax as jax_mod  # the runtime always has it; tooling never calls enable()
        except ImportError:
            jax_mod = None
    if jax_mod is not None:
        _JAX_ARRAY = jax_mod.Array
        try:
            _JAX_TRACER = jax_mod.core.Tracer
        except Exception:
            _JAX_TRACER = None
    _ENABLED = True
    _set_slot(True)
    _ensure_provider()


def disable() -> None:
    """Disarm: the registry keeps its entries (a re-enable resumes), but
    every site reverts to the one-attribute-load no-op."""
    global _ENABLED
    _ENABLED = False
    _set_slot(False)


def _reset_for_tests() -> None:
    """Drop every entry and zero the ledger (test isolation only)."""
    global _live, _peak, _registered_total, _oom_dumps, _last_ring_peak
    with _lock:
        _entries.clear()
        _live = 0
        _peak = 0
        _live_cat.clear()
        _peak_cat.clear()
        _registered_total = 0
        _oom_dumps = 0
        _last_ring_peak = 0
        _small[0] = _small[1] = 0
        del _dead[:]


# env arming: one check at import (``core.io`` imports this module at
# package import, so HEAT_TPU_MEMLEDGER takes effect process-wide).  Gated
# on __package__ like telemetry/flightrec: a STANDALONE load of this file
# is tooling and must not resolve jax or set the slot.
try:
    _dispatch_min = int(
        os.environ.get("HEAT_TPU_MEMLEDGER_DISPATCH_MIN", "")
        or DISPATCH_MIN_DEFAULT
    )
except ValueError:
    _dispatch_min = DISPATCH_MIN_DEFAULT

if __package__ and os.environ.get(
    "HEAT_TPU_MEMLEDGER", ""
).strip().lower() in ("1", "true", "on", "yes"):
    enable()
