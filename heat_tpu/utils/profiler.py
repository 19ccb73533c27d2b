"""Profiling shim (SURVEY §5.1).

The reference has no built-in tracer (external perun only).  On TPU we get a
first-class story: this wraps ``jax.profiler`` so benchmarks are one-liner
instrumented, plus a wall-clock timer that blocks on the result before it
stops the clock.
"""

from __future__ import annotations

import contextlib
import time
import weakref
from typing import Callable, Dict, Optional

import jax

from ..core._cache import cache_stats, reset_cache_stats

__all__ = [
    "trace",
    "timer",
    "sync",
    "annotate",
    "cache_stats",
    "reset_cache_stats",
    "cache_hit_rate",
    "counter_inc",
    "counter_max",
    "counters",
    "reset_counters",
    "register_counter_provider",
]


# ---------------------------------------------------------------------- #
# generic event counters (retry attempts, skipped train steps, ...)
# ---------------------------------------------------------------------- #
# Two sources merge in counters(): plain incremented counters (retry.<site>
# from utils.faults) and registered *providers* — callbacks polled at read
# time so device-resident counters (DASO's skip counter is a jax array,
# updated asynchronously with NO host sync on the step path) only
# materialize when somebody actually asks.
_counters: Dict[str, int] = {}
_providers: Dict[str, Callable[[], Dict[str, int]]] = {}


def counter_inc(name: str, n: int = 1) -> None:
    """Increment a named event counter (host-side, cheap)."""
    _counters[name] = _counters.get(name, 0) + int(n)


def counter_max(name: str, value: int) -> None:
    """High-water-mark counter: keep the MAX of all observed values (e.g.
    ``comm.resplit.peak_tile_bytes`` — additive semantics would be a lie
    for a peak).  Reads/resets/exports exactly like any other counter."""
    v = int(value)
    if v > _counters.get(name, 0):
        _counters[name] = v


def register_counter_provider(name: str, fn: Callable[[], Dict[str, int]]) -> str:
    """Register a callback polled by :func:`counters`.  Bound methods are
    held weakly so registering does not pin the owning object alive (a dead
    provider is pruned at the next :func:`counters` read).  ``name`` is
    de-duplicated with a numeric suffix — a second registrant never silently
    replaces the first — and the effective name is returned."""
    if hasattr(fn, "__self__"):
        ref = weakref.WeakMethod(fn)

        def fn():  # noqa: F811 — the weak indirection replaces the strong ref
            m = ref()
            return m() if m is not None else None  # None: owner was collected

    base, k = name, 2
    while name in _providers:
        name = f"{base}{k}"
        k += 1
    _providers[name] = fn
    return name


def counters() -> Dict[str, int]:
    """Snapshot of all counters: incremented ones plus every provider's
    current values.  May sync device-resident counters — call it at
    reporting boundaries, not inside the hot loop.

    Provider values are namespaced unambiguously under ``<provider>.<key>``
    (a key already carrying that exact dotted prefix is kept as-is).  The
    earlier rule — any key merely *starting with* the provider name passed
    through un-prefixed — let a provider key like ``daso_total`` silently
    overwrite an identically-named plain counter."""
    out = dict(_counters)
    for name, fn in list(_providers.items()):
        vals = fn()
        if vals is None:  # provider's owner was garbage collected
            _providers.pop(name, None)
            continue
        prefix = name + "."
        for k, v in vals.items():
            out[k if k.startswith(prefix) else f"{name}.{k}"] = int(v)
    return out


def reset_counters() -> None:
    """Clear the incremented counters (providers re-report on next read)."""
    _counters.clear()


def cache_hit_rate() -> float:
    """Hit rate of the sharding-keyed program caches since the last
    ``reset_cache_stats()`` — 1.0 means every dispatched op reused a
    compiled executable (zero recompilation)."""
    s = cache_stats()
    total = s["hits"] + s["misses"]
    return s["hits"] / total if total else 1.0


def sync(x=None) -> None:
    """Block until ``x`` (a DNDarray, jax.Array or pytree of them) is computed."""
    if x is not None:
        jax.block_until_ready(getattr(x, "_parray", x))


@contextlib.contextmanager
def timer(label: str = "", result_holder: Optional[dict] = None, sync_on=None):
    """Wall-clock a block; forces completion of ``sync_on`` before stopping.

    Exception-safe: a raising block still records its elapsed time into
    ``result_holder`` (and still syncs) — the exception propagates, but the
    measurement of the partial work is not lost."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        sync(sync_on)
        dt = time.perf_counter() - t0
        if result_holder is not None:
            result_holder[label or "elapsed"] = dt


@contextlib.contextmanager
def trace(logdir: str = "/tmp/heat_tpu_trace"):
    """XProf/TensorBoard trace of the block (``jax.profiler.trace``)."""
    with jax.profiler.trace(logdir):
        yield


annotate = jax.profiler.TraceAnnotation

# the program-cache stats surface in counters() too (counter naming scheme
# cache.* — see design.md "Telemetry & metrics"), so telemetry.report()
# carries hit/miss/slow next to comm.*/retry.*/io.* without a second API
register_counter_provider("cache", lambda: {k: int(v) for k, v in cache_stats().items()})
