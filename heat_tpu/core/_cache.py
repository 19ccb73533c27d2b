"""Per-communicator compiled-program caches.

Compiled collective pipelines (shard_map + jit) close over a
``Communication``'s mesh and pin XLA executables.  Caching them with
``functools.lru_cache`` keyed on the comm strongly pins comm + mesh +
executables until LRU eviction: a leak once a comm is dropped.

``comm_cached`` stores each function's programs in a dict ON the comm
instance (``comm._compiled_programs``), so:

- lifetime is tied to the comm by construction — programs die exactly when
  the comm is garbage collected, with no global registry pinning either;
- keying is by *instance identity*, not ``Communication.__eq__`` (which
  compares (mesh, axis)) — two value-equal comms never alias or steal each
  other's cache entries, which a ``WeakKeyDictionary`` would get wrong;
- each (comm, function) table is LRU-bounded: some static keys derive from
  user data (global length ``n``, ``k``), so an unbounded table on the
  process-lifetime world comm would accumulate executables forever.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

from jax.profiler import TraceAnnotation

__all__ = [
    "comm_cached",
    "cached_program",
    "cache_stats",
    "reset_cache_stats",
    "recording",
    "launch",
]

# ---------------------------------------------------------------------- #
# program spans.  A span of the dispatch layer is a profiler annotation
# named ``ht.<layer>.<what>``, made only while a profile records: the
# profiler itself answers "is anyone listening" (one C call, no flag of
# ours), and its annotations share the device trace's clock.
# ---------------------------------------------------------------------- #
recording = TraceAnnotation.is_enabled

LAUNCH_SPAN = "ht.dispatch.launch"


def launch(prog, *args):
    """Call a program that came out of :func:`cached_program` (jax's jit
    call, which holds the runtime's launch), under ``ht.dispatch.launch``
    while a profile records."""
    if recording():
        with TraceAnnotation(LAUNCH_SPAN):
            return prog(*args)
    return prog(*args)


# ---------------------------------------------------------------------- #
# global hit/miss accounting for every program table (dispatch cache +
# comm_cached shard_map pipelines).  Exposed through utils.profiler so
# benchmarks can assert "zero recompilations across N repeated ops".
# ---------------------------------------------------------------------- #
_STATS = {"hits": 0, "misses": 0, "slow": 0}

# negative-cache sentinel: a builder may return SLOW to record "this
# signature must take the general (eager) path".  Lookups that find SLOW
# count under the separate "slow" stat — NOT as hits — so a 100% hit rate
# genuinely means compiled programs were reused, not that everything fell
# through to the eager path.
SLOW = object()


def cache_stats() -> dict:
    """Snapshot of the program-cache counters: ``hits``/``misses`` for real
    compiled-program reuse/builds, ``slow`` for negative-cache lookups."""
    return dict(_STATS)


def reset_cache_stats() -> None:
    _STATS["hits"] = 0
    _STATS["misses"] = 0
    _STATS["slow"] = 0


# the shared dispatch table's slot name and bound.  One slot (not one per
# op) so the LRU bound caps TOTAL dispatch executables per comm: signatures
# derive from user data shapes, and an unbounded table on the
# process-lifetime world comm would accumulate executables forever.
_DISPATCH_SLOT = f"{__name__}.dispatch"
_DISPATCH_MAXSIZE = 1024


def cached_program(comm, key, builder):
    """Fetch-or-build a compiled program in ``comm``'s dispatch table.

    The zero-copy dispatch core: jitted executables are keyed on
    ``(op identity, input avals, split, static kwargs, donation)`` — the
    mesh fingerprint is implicit because the table lives ON the comm
    instance (same lifetime discipline as :func:`comm_cached`).  ``key``
    must be hashable; ``builder()`` is called once per distinct key and
    must return the compiled callable.  Hits and misses feed the global
    :func:`cache_stats` counters.
    """
    tables = comm.__dict__.setdefault("_compiled_programs", {})
    table = tables.get(_DISPATCH_SLOT)
    if table is None:
        table = tables[_DISPATCH_SLOT] = OrderedDict()
    prog = table.get(key)
    if prog is None:
        _STATS["misses"] += 1
        prog = table[key] = builder()
        if len(table) > _DISPATCH_MAXSIZE:
            table.popitem(last=False)
    else:
        _STATS["slow" if prog is SLOW else "hits"] += 1
        table.move_to_end(key)
    return prog


def comm_cached(fn=None, *, maxsize: int = 32, key=None):
    """Memoize ``fn(comm, *args)`` on the comm instance, LRU-bounded.

    ``args`` must be hashable (static ints/strings/tuples — the same
    contract ``lru_cache`` imposed).  ``key``, if given, maps ``*args`` to
    the cache key instead of using the args themselves — layer-program
    caches key on a *config tuple* (e.g. ``MoE._program_key``) so
    identical-config layers share one executable and the table *key* never
    pins a layer.  Note the cached *value* may still close over the first
    instance of each config (a bound method inside the compiled program) —
    retention drops from every-instance to one representative per config,
    LRU-bounded.  Without ``key``, object-valued args are retained until
    eviction, acceptable only for long-lived objects (see
    ``parallel.pipeline._pipeline_program``).
    """
    if fn is None:
        return lambda f: comm_cached(f, maxsize=maxsize, key=key)

    slot = f"{fn.__module__}.{fn.__qualname__}"

    @functools.wraps(fn)
    def wrapper(comm, *args):
        tables = comm.__dict__.setdefault("_compiled_programs", {})
        table = tables.get(slot)
        if table is None:
            table = tables[slot] = OrderedDict()
        k = key(*args) if key is not None else args
        prog = table.get(k)
        if prog is None:
            _STATS["misses"] += 1
            prog = table[k] = fn(comm, *args)
            if len(table) > maxsize:
                table.popitem(last=False)
        else:
            _STATS["hits"] += 1
            table.move_to_end(k)
        return prog

    wrapper._cache_slot = slot  # introspection hook for tests
    return wrapper
