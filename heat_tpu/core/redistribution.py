"""Memory-bounded streaming redistribution: tiled resplit under a byte budget.

Redistribution (``DNDarray.resplit_`` → ``Communication.resplit``) is the
reference framework's signature data movement (SURVEY §3.3).  The monolithic
realization — one :func:`identity_program` with the target sharding, lowered
by XLA to a single all-to-all — materializes source and destination WHOLE:
peak memory is ~2× the array plus collective staging, and donation recovers
almost nothing because the transfer itself holds both copies.  Following
"Memory-efficient array redistribution through portable collective
communication" (arXiv 2112.01075), any split→split transition decomposes into
a *sequence of tiled collectives* with bounded peak memory.  This module is
that decomposition:

- :func:`plan_resplit` — a PURE planner: given (gshape, itemsize, src split,
  dst split, world size, budget bytes) it picks a tiling axis that is neither
  the source nor the destination split, sizes uniform tiles so each moves at
  most ``budget`` bytes (a shorter tail tile absorbs ragged extents — the
  "padded final tile" clipped to its true length so no byte is moved or
  accounted twice), and returns a :class:`ResplitPlan` with K tiles.  K=1
  degenerates to the monolithic fast path, with the reason recorded.

- :func:`execute_plan` — the streaming executor: preallocate the destination
  (dst-sharded zeros), then per tile *slice → reshard (the tiled all-to-all)
  → write into the destination in place*.  Every per-tile program is jitted
  and kept in the PR 1 sharding-keyed program cache (``cached_program``), so
  a steady-state chunked resplit recompiles nothing; the move and update
  programs DONATE their inputs, so each staged tile is freed before the next
  stage begins, and the in-place update aliases the accumulator (same shape/
  dtype/sharding → ``input_output_alias``).  With ``donate=True`` the source
  buffer is additionally ``delete()``-ed the moment the last tile has been
  sliced out of it.

**Peak-memory model** (documented contract, which the memory ledger observes
from inside in ``tests/test_memledger.py``): beyond source + destination, the
transient working set is at most ``budget + one tile`` (one tile staged out
of the source plus its resharded copy in flight).  The monolithic path's transient is O(array).

**Budget semantics**: ``memory_budget`` bounds the bytes MOVED PER STEP.  The
resolution order is: explicit ``memory_budget=`` kwarg → process-wide default
(:func:`set_redistribution_budget`) → ``HEAT_TPU_RESPLIT_BUDGET`` env (read
once at import; suffixes K/M/G accepted).  ``None``/``0`` means unbounded
(monolithic).  A budget below one tiling-axis slice floors at one slice per
tile — best effort, recorded as the plan's ``reason``.

Transitions that cannot tile fall back to K=1 monolithic, recorded in
``ResplitPlan.reason``: tracers (nothing concrete to stream), ragged
source/destination extents (their placement is XLA's, not the
canonical sharding tiles are built from), 0-d/1-d arrays and 2-d k→j (no
non-split axis to tile along — the general basis-change decompositions of
arXiv 2112.01075 §5 are future work), and arrays whose total size already
fits the budget.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from . import _hooks

__all__ = [
    "ResplitPlan",
    "plan_resplit",
    "make_plan",
    "execute_plan",
    "parse_budget",
    "set_redistribution_budget",
    "get_redistribution_budget",
]


# ---------------------------------------------------------------------- #
# process-wide default budget
# ---------------------------------------------------------------------- #
def parse_budget(budget) -> Optional[int]:
    """Normalize a budget spec to bytes: ints pass through, strings accept
    K/M/G(B) suffixes (``"64M"`` → 67108864).  ``None``, ``0``, negative and
    the empty string all mean "unbounded" and normalize to ``None``."""
    if budget is None:
        return None
    if isinstance(budget, str):
        text = budget.strip().upper().removesuffix("B")
        if not text:
            return None
        scale = 1
        if text[-1] in "KMG":
            scale = 1024 ** ("KMG".index(text[-1]) + 1)
            text = text[:-1]
        # scale BEFORE truncating: "0.5G" is 512M, not int(0.5)=0 -> unbounded
        budget = int(float(text) * scale)
    else:
        budget = int(budget)
    return budget if budget > 0 else None


_DEFAULT_BUDGET: Optional[int] = parse_budget(
    os.environ.get("HEAT_TPU_RESPLIT_BUDGET")
)


def set_redistribution_budget(budget) -> Optional[int]:
    """Set the process-wide default resplit memory budget (bytes; K/M/G
    string suffixes accepted; ``None``/``0`` restores unbounded).  Returns
    the previous value so callers can scope-and-restore."""
    global _DEFAULT_BUDGET
    prev = _DEFAULT_BUDGET
    _DEFAULT_BUDGET = parse_budget(budget)
    return prev


def get_redistribution_budget() -> Optional[int]:
    """The process-wide default resplit budget in bytes (None = unbounded)."""
    return _DEFAULT_BUDGET


# ---------------------------------------------------------------------- #
# planner (pure — no jax, no mesh; unit-testable standalone)
# ---------------------------------------------------------------------- #
@dataclass(frozen=True)
class ResplitPlan:
    """A split→split transition decomposed into K tiled all-to-all steps.

    ``tile_axis`` is None iff the plan is monolithic (``n_tiles == 1`` via
    any fallback ``reason``); otherwise tile ``i`` covers
    ``[i*tile_extent, min((i+1)*tile_extent, gshape[tile_axis]))`` along
    ``tile_axis`` — the final tile is clipped to the true extent, so the
    tiles partition the array exactly (no overlap, no double-accounting).
    """

    gshape: Tuple[int, ...]
    itemsize: int
    src_split: Optional[int]
    dst_split: Optional[int]
    size: int
    budget: Optional[int]
    tile_axis: Optional[int]
    tile_extent: int
    n_tiles: int
    total_bytes: int
    reason: str

    def tile_bounds(self, i: int) -> Tuple[int, int]:
        """(start, length) of tile ``i`` along ``tile_axis``."""
        if self.tile_axis is None:
            return 0, self.gshape[0] if self.gshape else 0
        n = self.gshape[self.tile_axis]
        start = i * self.tile_extent
        return start, min(self.tile_extent, n - start)

    def tile_nbytes(self, length: int) -> int:
        """Payload bytes of a tile spanning ``length`` along ``tile_axis``."""
        if self.tile_axis is None:
            return self.total_bytes
        n = self.gshape[self.tile_axis]
        return (self.total_bytes // n) * length if n else 0

    @property
    def max_tile_bytes(self) -> int:
        return self.tile_nbytes(self.tile_extent) if self.tile_axis is not None else self.total_bytes


def _mono(gshape, itemsize, src, dst, size, budget, total, reason) -> ResplitPlan:
    return ResplitPlan(
        gshape=tuple(gshape), itemsize=itemsize, src_split=src, dst_split=dst,
        size=size, budget=budget, tile_axis=None, tile_extent=0, n_tiles=1,
        total_bytes=total, reason=reason,
    )


def plan_resplit(
    gshape,
    itemsize: int,
    src_split: Optional[int],
    dst_split: Optional[int],
    size: int,
    memory_budget: Optional[int],
) -> ResplitPlan:
    """Decompose the (src_split → dst_split) transition of a ``gshape`` array
    of ``itemsize``-byte elements over ``size`` shards into tiles of at most
    ``memory_budget`` bytes each.  Pure shard math — returns a monolithic
    K=1 plan (with ``reason``) whenever tiling does not apply."""
    gshape = tuple(int(s) for s in gshape)
    ndim = len(gshape)
    if src_split is not None and ndim:
        src_split = src_split % ndim
    if dst_split is not None and ndim:
        dst_split = dst_split % ndim
    total = int(np.prod(gshape, dtype=np.int64)) * int(itemsize) if gshape else int(itemsize)
    budget = parse_budget(memory_budget)
    args = (gshape, int(itemsize), src_split, dst_split, int(size), budget, total)
    if budget is None:
        return _mono(*args, "no-budget")
    if ndim < 2:
        return _mono(*args, "too-few-dims")
    if total <= budget:
        return _mono(*args, "fits-in-budget")
    # canonical shardings on both ends are what the per-tile programs are
    # built from; a ragged extent's placement is XLA's, not canonical
    if src_split is not None and gshape[src_split] % size != 0:
        return _mono(*args, "ragged-src")
    if dst_split is not None and gshape[dst_split] % size != 0:
        return _mono(*args, "ragged-dst")
    candidates = [
        i for i in range(ndim)
        if i != src_split and i != dst_split and gshape[i] >= 2
    ]
    if not candidates:
        return _mono(*args, "no-free-axis")
    # largest extent → finest achievable granularity (ties: lowest axis)
    axis = max(candidates, key=lambda i: (gshape[i], -i))
    n = gshape[axis]
    per_index = total // n  # bytes of one tiling-axis slice
    extent = max(1, budget // per_index) if per_index else n
    if extent >= n:
        return _mono(*args, "fits-in-budget")
    n_tiles = -(-n // extent)
    reason = "tiled" if per_index <= budget else "tiled-floor-one-slice"
    return ResplitPlan(
        gshape=gshape, itemsize=int(itemsize), src_split=src_split,
        dst_split=dst_split, size=int(size), budget=budget, tile_axis=axis,
        tile_extent=extent, n_tiles=n_tiles, total_bytes=total, reason=reason,
    )


# ---------------------------------------------------------------------- #
# eligibility + execution (jax-touching half)
# ---------------------------------------------------------------------- #
def on_mesh(comm, array, src_split: Optional[int]) -> bool:
    """True when the concrete ``array`` carries ``comm``'s canonical sharding
    of ``src_split`` (its own ``comm.split_of(array)``) — the placement every
    reshard program (monolithic or per tile) is built from.  Anything else
    (XLA's opportunistic ragged placement, sub-meshes, a single default
    device) is moved by ``Communication.shard``'s ``device_put``."""
    cur = getattr(array, "sharding", None)
    want = comm.sharding(array.ndim, src_split)
    if cur == want:
        return True
    try:
        return cur is not None and cur.is_equivalent_to(want, array.ndim)
    except Exception:
        return False


def identity_program(dst_sh, donate: bool):
    """THE reshard program of the comm layer: a jitted identity whose
    ``out_shardings`` differ from its input's, which XLA lowers to the
    collective the transition needs (all-to-all k→j, all-gather k→None, a
    local slice None→k).  ``donate`` hands the input buffer to the program,
    so it is freed as soon as the transfer has consumed it."""
    import jax

    return jax.jit(
        lambda t: t, out_shardings=dst_sh, donate_argnums=(0,) if donate else ()
    )


def launch_quiet(prog, *args):
    """``_cache.launch`` of a DONATING program whose donated inputs cannot
    ALIAS their (differently-shaped) outputs — the donation is for the early
    free, which still happens; jax's compile-time "donated buffers were not
    usable" warning is expected noise, filtered at the call (= first-compile)
    site only."""
    import warnings

    from ._cache import launch

    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message=".*[Dd]onated buffers were not usable.*"
        )
        return launch(prog, *args)


def make_plan(comm, array, dst_split: Optional[int], memory_budget=None) -> Optional[ResplitPlan]:
    """Plan the redistribution of a CONCRETE array, or None when the tiled
    pipeline cannot apply (tracer, non-canonical current
    placement) — the caller then takes the monolithic path unconditionally.

    ``memory_budget=None`` resolves to the process default
    (:func:`set_redistribution_budget` / ``HEAT_TPU_RESPLIT_BUDGET``); pass
    ``0`` to force monolithic regardless of the default."""
    import jax

    if memory_budget is None:
        budget = get_redistribution_budget()
    else:
        budget = parse_budget(memory_budget)
    if budget is None:
        return None
    if isinstance(array, jax.core.Tracer) or not isinstance(array, jax.Array):
        return None
    # the per-tile slice programs assume the source carries exactly the
    # canonical sharding of its split; anything else takes the monolithic path
    src_split = comm.split_of(array)
    if not on_mesh(comm, array, src_split):
        return None
    return plan_resplit(
        array.shape, np.dtype(array.dtype).itemsize, src_split, dst_split,
        comm.size, budget,
    )


def execute_plan(comm, array, plan: ResplitPlan, donate: bool = False):
    """Run a K>1 :class:`ResplitPlan`: stream the array to its new sharding
    tile by tile, peak transient memory ≤ budget + one tile beyond the
    source and destination buffers.

    Per tile: *slice* (jitted dynamic-slice along the tiling axis, source
    sharding preserved, no communication) → *move* (jitted identity with the
    destination ``out_shardings`` — THE tiled all-to-all; input donated, so
    the staged slice is freed as soon as the transfer consumed it) →
    *update* (jitted ``dynamic_update_slice`` into the preallocated
    destination; the accumulator is donated and aliases in place, the moved
    tile is donated and freed).  All programs live in the PR 1 program cache
    keyed on (shape, dtype, splits, tile geometry): a steady-state chunked
    resplit is 100% cache hits.

    Accounting: each tile is byte-accounted exactly once at its staging
    point under ``comm.resplit.calls/.bytes`` with the resplit traffic
    factor (p-1)/p, using telescoped cumulative rounding so the SUM over
    tiles equals the monolithic path's single accounting to the byte;
    ``comm.resplit.tiles`` and ``comm.resplit.peak_tile_bytes`` record the
    plan shape.  The per-tile ``_account_bytes`` choke point also fires the
    ``comm.collective`` fault site and refuses to stage past a blown
    ``comm.deadline`` — and under an armed deadline every tile's transfer is
    awaited through the ``guard_blocking`` watchdog, so ONE hung tile trips
    ``CollectiveTimeoutError`` instead of wedging the whole plan.

    ``donate=True`` additionally deletes the source buffer once the last
    tile has been sliced out of it (the caller must not use it afterwards).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ._cache import cached_program, launch

    ndim = array.ndim
    axis = plan.tile_axis
    src_sh = comm.sharding(ndim, plan.src_split)
    dst_sh = comm.sharding(ndim, plan.dst_split)
    dtype = array.dtype
    shape = tuple(array.shape)
    sig = (shape, str(jnp.dtype(dtype)), plan.src_split, plan.dst_split, axis)
    factor = (comm.size - 1) / comm.size

    def _program(kind: str, length: int, builder):
        return cached_program(comm, ("resplit", kind, sig, length), builder)

    def _build_init():
        return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=dst_sh)

    def _build_slice(length: int):
        def f(src, start):
            return lax.dynamic_slice_in_dim(src, start, length, axis=axis)

        return jax.jit(f, out_shardings=src_sh)

    def _build_move():
        # the tile-sized all-to-all; donation frees the staged slice as soon
        # as the transfer has consumed it
        return identity_program(dst_sh, donate=True)

    def _build_update():
        def f(acc, tile, start):
            return lax.dynamic_update_slice_in_dim(acc, tile, start, axis=axis)

        # acc donated: same shape/dtype/sharding as the output, so XLA
        # aliases the buffers (true in-place); tile donated: freed on use
        return jax.jit(f, out_shardings=dst_sh, donate_argnums=(0, 1))

    from ..utils import health as _hlth
    from ..utils import telemetry as _tel

    from ..utils import profiler as _prof

    ml = _hooks.memledger
    out = launch(_program("init", 0, _build_init))
    if ml is not None:
        # the preallocated destination: a transient until the finished plan
        # reclassifies it (comm.resplit_tiled)
        ml.register(out, op="resplit.init", site="resplit.tile")
    accounted = 0  # telescoped: totals match the monolithic path to the byte
    moved = 0
    for i in range(plan.n_tiles):
        start, length = plan.tile_bounds(i)
        tile_bytes = plan.tile_nbytes(length)
        moved += tile_bytes
        wire = int(round(moved * factor)) - accounted
        accounted += wire
        comm._account_bytes(
            "resplit", wire, x=array,
            src_split=plan.src_split, dst_split=plan.dst_split,
        )
        if ml is not None:
            # the mem.alloc fault site, per tile: chaos CI injects the
            # deterministic mid-resplit allocation failure HERE — the
            # caller's catch dumps the ledger and re-raises
            ml.alloc_check(tile_bytes, "comm.resplit.tile")
        # plan-shape counters advance PER TILE so a mid-plan failure (hung
        # tile tripping the deadline) leaves calls/bytes/tiles consistent in
        # the post-mortem report instead of tiles=0 masquerading as monolithic
        _tel.counter_inc("comm.resplit.tiles", 1)
        _prof.counter_max("comm.resplit.peak_tile_bytes", tile_bytes)
        staged = launch(_program("slice", length, lambda: _build_slice(length)), array, start)
        if ml is not None:
            ml.register(staged, op="resplit.tile", site="resplit.tile")
        if donate and i == plan.n_tiles - 1:
            # every byte has been sliced out — free the source NOW, before
            # the last transfer, so peak memory never holds src + dst + tile
            try:
                array.delete()
            except Exception:
                pass
            if ml is not None:
                ml.consume(array)
        tile = launch_quiet(_program("move", length, _build_move), staged)
        if ml is not None:
            # consumed only AFTER the donating program ran (the monolithic
            # path's rule): an OOM inside the move must still find the
            # in-flight staged tile in the dump.  The ledger briefly holds
            # both tile stages — still within budget + one tile whenever a
            # tile fits the budget (the floor-at-one-slice case overcounts
            # transiently).
            ml.consume(staged)
            ml.register(tile, op="resplit.tile", site="resplit.tile")
        prev = out
        out = launch_quiet(_program("update", length, _build_update), prev, tile, start)
        if ml is not None:
            ml.consume(tile)  # donated into (and consumed by) the update
            # the accumulator was donated and aliases in place: move the
            # entry to the new handle without double-counting the buffer
            ml.transfer(prev, out, op="resplit.init")
        if _hlth.active_deadline() is not None:
            # deadline armed: await this tile under the watchdog so a hung
            # transfer raises CollectiveTimeoutError at the offending tile
            # (guarded + only reachable under an active deadline, which is
            # what HT107 wants — the rule's lexical with-block heuristic
            # cannot see the dynamic check one line up)
            _hlth.guard_blocking(
                lambda: jax.block_until_ready(out),  # heatlint: disable=HT107 — runs only under an armed deadline, via guard_blocking
                "comm.resplit.tile",
            )
    return out


