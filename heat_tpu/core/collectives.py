"""Bucketed hierarchical gradient sync: two-level collectives + overlap.

DASO and ``DataParallelOptimizer`` historically synchronized in one
monolithic, serialized shot — the exact stall ``scripts/stepprof.py``'s
``STEP-OVERLAP kind=daso.step`` line made measurable (PR 11's committed
before-number).  Following "Generalized hierarchical all-reduce"
(arXiv 2004.09362), an allreduce over ``p = d·i`` participants decomposes
into *reduce-scatter in the fast domain (i members) → cross-domain exchange
of the 1/i shard (d domains) → allgather back in the fast domain*, and
following the dominant-term analysis of "The Big Send-off" (arXiv
2504.18658), the sync payload splits into byte-budgeted **buckets** whose
transfers pipeline against the consuming compute — bucket k's blend/update
runs while bucket k+1's collective is in flight.  This module is both
halves:

- **Bucket planner** (:func:`plan_grad_buckets`): PURE — packs the
  flattened grad/param pytree's leaves into contiguous buckets of at most
  ``budget`` bytes (an oversized leaf gets its own bucket; K=1 degenerates
  to the monolithic path, reason recorded).  Budget resolution order:
  explicit ``grad_bucket_bytes=`` kwarg → process default
  (:func:`set_grad_bucket_budget`) → ``HEAT_TPU_GRAD_BUCKET_BYTES`` env
  (read once at import; K/M/G suffixes via the same
  :func:`~heat_tpu.core.redistribution.parse_budget` the resplit budget
  uses).

- **Stage math** (:func:`_hier_stage_factors` / :func:`_daso_stage_factors`):
  per-stage wire-traffic factors.  The two-level decomposition telescopes
  EXACTLY — (i−1)/i + 2(d−1)/(d·i) + (i−1)/i = 2(p−1)/p, the flat ring
  factor — so ``comm.allreduce.bytes`` accounted stage-by-stage reconciles
  against the monolithic accounting to the byte (cumulative-rounding
  telescoping across stages AND buckets, the ``execute_plan`` discipline:
  the sum over any K-bucket split equals the K=1 total exactly).

- **Executors** (:func:`bucketed_param_sync`, :func:`bucketed_grad_allreduce`
  and their dispatch/consume halves): double-buffered lookahead-1 pipelines.
  Bucket k+1's collective is dispatched before bucket k is awaited, so at
  most TWO buckets are ever in flight (transient peak ≤ budget + one
  bucket, the resplit bound, observed by the memledger from inside); every
  bucket's staging routes through ``Communication._account_bytes`` — the
  existing choke point — so flight-ring seq stamps, the ``comm.collective``
  fault site, armed deadlines, and telemetry counters see the new path for
  free, and each bucket is awaited through ``health.guard_blocking`` so one
  hung bucket trips ``CollectiveTimeoutError`` at the offending bucket
  (with its seq/op in the flight ring for the post-mortem) instead of
  wedging the step.  Per-bucket programs live in the PR 1 sharding-keyed
  program cache: steady state recompiles nothing.

Opt-in only: ``DASO(overlap_sync=True)``, ``DataParallelOptimizer(
overlap_sync=True)`` and ``DataParallel.make_train_step(overlap_sync=True)``
route here; the default paths are bit-exact untouched.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from ._cache import launch
from .redistribution import parse_budget

# device-memory-ledger hook (``utils.memledger.enable()`` pokes the module
# in): the executors register every in-flight bucket average (category
# ``transient``), fire the ``mem.alloc`` fault site ahead of each bucket's
# staging, and consume each bucket the moment its blend/update dispatched —
# so ``mem.live_bytes`` observes the budget + one-bucket pipeline contract
# FROM INSIDE.  Disabled cost: one module-global load per sync.  Module
# bottom re-arms.
_MEMLEDGER = None

__all__ = [
    "GradBucketPlan",
    "plan_grad_buckets",
    "set_grad_bucket_budget",
    "get_grad_bucket_budget",
    "bucketed_param_sync",
    "dispatch_bucket_averages",
    "consume_bucket_averages",
    "bucketed_grad_allreduce",
    "dispatch_bucket_allreduce",
]


# ---------------------------------------------------------------------- #
# process-wide default bucket budget (same resolution order as resplit)
# ---------------------------------------------------------------------- #
_DEFAULT_BUDGET: Optional[int] = parse_budget(
    os.environ.get("HEAT_TPU_GRAD_BUCKET_BYTES")
)


def set_grad_bucket_budget(budget) -> Optional[int]:
    """Set the process-wide default gradient-bucket budget (bytes; K/M/G
    string suffixes accepted; ``None``/``0`` restores unbounded =
    monolithic single-bucket sync).  Returns the previous value so callers
    can scope-and-restore."""
    global _DEFAULT_BUDGET
    prev = _DEFAULT_BUDGET
    _DEFAULT_BUDGET = parse_budget(budget)
    return prev


def get_grad_bucket_budget() -> Optional[int]:
    """The process-wide default grad-bucket budget in bytes (None =
    unbounded: the whole tree syncs as one bucket)."""
    return _DEFAULT_BUDGET


# ---------------------------------------------------------------------- #
# planner (pure — no jax; unit-testable standalone)
# ---------------------------------------------------------------------- #
@dataclass(frozen=True)
class GradBucketPlan:
    """A flattened pytree's leaves packed into K contiguous byte-budgeted
    buckets.  ``buckets[k]`` holds the leaf indices of bucket k, in tree
    order — contiguity keeps the per-bucket programs' signatures stable
    across steps, which is what keeps the program cache at 100% hits."""

    leaf_nbytes: Tuple[int, ...]
    budget: Optional[int]
    buckets: Tuple[Tuple[int, ...], ...]
    total_bytes: int
    reason: str

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def bucket_nbytes(self, k: int) -> int:
        return sum(self.leaf_nbytes[j] for j in self.buckets[k])

    @property
    def max_bucket_bytes(self) -> int:
        return max(
            (self.bucket_nbytes(k) for k in range(self.n_buckets)), default=0
        )


def plan_grad_buckets(leaf_nbytes: Sequence[int], budget=None) -> GradBucketPlan:
    """Pack leaves (given by their byte sizes, tree order) into buckets of
    at most ``budget`` bytes each.  ``budget=None`` resolves to the process
    default (:func:`set_grad_bucket_budget` / ``HEAT_TPU_GRAD_BUCKET_BYTES``);
    pass ``0`` to force the monolithic single bucket regardless of the
    default.  A leaf larger than the budget gets its own bucket (best
    effort — the budget floors at one leaf, like resplit's floor-at-one-
    slice)."""
    sizes = tuple(int(n) for n in leaf_nbytes)
    total = sum(sizes)
    if budget is None:
        budget = get_grad_bucket_budget()
    else:
        budget = parse_budget(budget)
    if not sizes:
        return GradBucketPlan(sizes, budget, (), 0, "no-leaves")
    if budget is None:
        return GradBucketPlan(
            sizes, None, (tuple(range(len(sizes))),), total, "no-budget"
        )
    if total <= budget:
        return GradBucketPlan(
            sizes, budget, (tuple(range(len(sizes))),), total, "fits-in-budget"
        )
    buckets: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_bytes = 0
    for j, nb in enumerate(sizes):
        if cur and cur_bytes + nb > budget:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(j)
        cur_bytes += nb
    if cur:
        buckets.append(tuple(cur))
    return GradBucketPlan(sizes, budget, tuple(buckets), total, "bucketed")


# ---------------------------------------------------------------------- #
# stage math: per-stage wire-traffic factors of the two-level path
# ---------------------------------------------------------------------- #
def _hier_stage_factors(p: int, d: int) -> Optional[Tuple[float, float, float]]:
    """Wire factors (reduce-scatter, cross-domain exchange, allgather) of a
    two-level allreduce over ``p = d·i`` participants, in units of one
    participant's payload.  ``None`` means the hierarchy degenerates (one
    domain, or one member per domain) and the caller takes the flat path.
    The three stages telescope exactly to the flat ring factor:
    (i−1)/i + 2(d−1)/(d·i) + (i−1)/i = 2(p−1)/p."""
    if d <= 1 or p % d or p // d <= 1:
        return None
    i = p // d
    return ((i - 1) / i, 2.0 * (d - 1) / (d * i), (i - 1) / i)


def _daso_stage_factors(d: int, i: int) -> Tuple[float, float]:
    """Wire factors (cross-domain exchange, allgather) of the DASO bucket
    sync on the ('dcn', 'ici') mesh, in units of one GROUP's payload.  The
    reduce-scatter stage is a local slice (params are replicated over
    'ici'), so it moves zero wire bytes; the exchange psums the 1/i chunk
    across the d groups; the allgather rebuilds the full payload in the
    fast domain."""
    return (2.0 * (d - 1) / (d * i), (i - 1) / i)


class _Telescope:
    """Cumulative-rounding byte accountant (the ``execute_plan``
    discipline): ``wire(x)`` returns ``round(moved+x) − accounted`` so the
    SUM over any split into stages/buckets equals the monolithic
    ``round(total)`` to the byte — K-invariance of ``comm.allreduce.bytes``."""

    __slots__ = ("moved", "accounted")

    def __init__(self):
        self.moved = 0.0
        self.accounted = 0

    def wire(self, nbytes: float) -> int:
        self.moved += nbytes
        w = int(round(self.moved)) - self.accounted
        self.accounted += w
        return w


def _account_stages(comm, tele: _Telescope, payload: float, factors, x=None) -> None:
    """Stage each hierarchical stage's wire bytes through the existing
    ``Communication._account_bytes`` choke point under ``comm.allreduce``:
    one flight-ring seq stamp + ``comm.collective`` fault firing +
    telemetry counter per stage, telescoped so the K-bucket total
    reconciles against the monolithic accounting exactly."""
    for f in factors:
        if f <= 0.0:
            continue
        comm._account_bytes("allreduce", tele.wire(payload * f), x=x)


def _await_bucket(arrs, what: str = "comm.allreduce") -> None:
    """Await one in-flight bucket through the watchdog: under an armed
    ``comm.deadline`` a hung bucket trips ``CollectiveTimeoutError`` at the
    offending bucket; with telemetry armed the blocked time lands as a
    ``comm.allreduce.wait`` leaf record (stepprof's comm-wait input);
    disarmed it is a bare await."""
    import jax

    from ..utils import health as _hlth

    _hlth.guard_blocking(
        lambda: jax.block_until_ready(arrs),  # heatlint: disable=HT107 — routed through guard_blocking: watchdogged under an armed deadline, timed leaf record otherwise
        what,
    )


def _ledger_dispatch(bucket_bytes: int, avg_leaves) -> None:
    ml = _MEMLEDGER
    if ml is None:
        return
    # the mem.alloc fault site, per bucket: chaos CI injects deterministic
    # mid-sync allocation failures HERE
    ml.alloc_check(bucket_bytes, "comm.allreduce.bucket")
    for a in avg_leaves:
        # explicit category: these are in-flight sync transients even when
        # dispatched inside a daso.step span (which would infer opt-state)
        ml.register(
            a, op="allreduce.bucket", site="allreduce.bucket", category="transient"
        )


def _ledger_consume(avg_leaves) -> None:
    ml = _MEMLEDGER
    if ml is None:
        return
    for a in avg_leaves:
        ml.consume(a)


def _bucket_counters(bucket_bytes: int) -> None:
    from ..utils import profiler as _prof
    from ..utils import telemetry as _tel

    _tel.counter_inc("comm.allreduce.buckets", 1)
    _prof.counter_max("comm.allreduce.peak_bucket_bytes", bucket_bytes)


# ---------------------------------------------------------------------- #
# shard-level two-level allreduce body (single mesh axis, subgroup-based)
# ---------------------------------------------------------------------- #
def _hier_groups(p: int, d: int):
    """(intra, inter) ``axis_index_groups`` for a two-level decomposition
    of ``p`` participants into ``d`` contiguous domains of ``i = p // d``
    members: intra-domain groups are the contiguous blocks (the fast tier),
    inter-domain groups are the strided transversals (member k of every
    domain — the slow tier exchanging chunk k)."""
    i = p // d
    intra = [list(range(g * i, (g + 1) * i)) for g in range(d)]
    inter = [[g * i + k for g in range(d)] for k in range(i)]
    return intra, inter


def _hierarchical_body(x, axis: str, p: int, d: int, mean: bool = False):
    """Shard-level two-level allreduce of ``x`` over mesh axis ``axis``
    (valid only inside ``shard_map``): reduce-scatter within each domain,
    cross-domain exchange of the 1/i shard, allgather back.  Raw ``lax``
    collectives — byte accounting belongs to the STAGING caller (the
    ``_account_stages`` choke-point delegation), never to the traced body,
    so cached program replays can never under-account."""
    import jax.numpy as jnp
    from jax import lax

    factors = _hier_stage_factors(p, d)
    if factors is None:
        out = lax.psum(x, axis)
        return out / p if mean else out
    i = p // d
    intra, inter = _hier_groups(p, d)
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % i
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    # stage 1: reduce-scatter in the fast domain — member k of each domain
    # ends up owning chunk k of the domain-local sum
    chunk = lax.psum_scatter(
        flat, axis, scatter_dimension=0, axis_index_groups=intra, tiled=True
    )
    # stage 2: cross-domain exchange — the 1/i shard allreduces across the
    # d domains (the only traffic that crosses the slow tier)
    chunk = lax.psum(chunk, axis, axis_index_groups=inter)
    if mean:
        chunk = chunk / p
    # stage 3: allgather in the fast domain rebuilds the full payload
    full = lax.all_gather(chunk, axis, axis=0, axis_index_groups=intra, tiled=True)
    if pad:
        full = full[:n]
    return full.reshape(x.shape)


def _derive_domains(comm, domains=None) -> int:
    """Topology-derived slow-domain count: one domain per host process when
    that divides the axis size (the DCN/ICI boundary a multi-host mesh
    exposes), else 1 (single domain → flat fallback).  An explicit
    ``domains`` overrides — tests and single-host benches use it to model a
    multi-host topology."""
    p = comm.size
    d = comm.n_processes if domains is None else int(domains)
    if d <= 1 or p % d or p // d <= 1:
        return 1
    return d


# ---------------------------------------------------------------------- #
# DASO bucket engine: ('dcn', 'ici') mesh, params stacked over groups
# ---------------------------------------------------------------------- #
def _daso_sig(leaves, idxs) -> tuple:
    import jax.numpy as jnp

    return tuple((tuple(leaves[j].shape), str(jnp.dtype(leaves[j].dtype))) for j in idxs)


def _daso_avg_program(comm, mesh, sig, n_leaves: int, d: int, i: int):
    from ._cache import cached_program

    def build():
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(*leaves):
            outs = []
            for g in leaves:
                flat = g.reshape(-1)
                n = flat.shape[0]
                pad = (-n) % i
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
                chunk = (n + pad) // i
                k = lax.axis_index("ici")
                # reduce-scatter degenerates to a local slice: params are
                # replicated over 'ici', so chunk k needs no wire traffic
                mine = lax.dynamic_slice_in_dim(flat, k * chunk, chunk)
                # cross-domain exchange: the 1/i chunk allreduces over the
                # d groups (the slow tier) and becomes the group mean
                mine = lax.psum(mine, "dcn") / d
                # allgather in the fast domain rebuilds the full payload
                full = lax.all_gather(mine, "ici", axis=0, tiled=True)
                if pad:
                    full = full[:n]
                outs.append(full.reshape(g.shape))
            return tuple(outs)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P("dcn"),) * n_leaves,
            out_specs=(P("dcn"),) * n_leaves,
            check_vma=False,
        )
        return jax.jit(fn)

    return cached_program(comm, ("daso.bucket_avg", sig, d, i), build)


def _blend_program(comm, sig, n_leaves: int):
    from ._cache import cached_program

    def build():
        import jax

        def f(ps, avgs, w):
            return tuple((1.0 - w) * p + w * a for p, a in zip(ps, avgs))

        # pre-blend replicas donated (freed into the blend); the averages
        # are kept — the ledger consume below is their logical death
        return jax.jit(f, donate_argnums=(0,))

    return cached_program(comm, ("daso.bucket_blend", sig, n_leaves), build)


def dispatch_bucket_averages(comm, leaves, plan: GradBucketPlan, k: int, tele: _Telescope):
    """Stage bucket ``k``'s cross-group average: byte-account every
    hierarchical stage through ``comm._account_bytes`` (seq stamps, fault
    site, deadline, counters), fire the ledger's ``mem.alloc`` site, then
    dispatch the cached per-bucket program.  Returns the in-flight average
    leaves (async)."""
    mesh = comm.mesh
    d = int(mesh.shape["dcn"])
    i = int(mesh.shape["ici"])
    idxs = plan.buckets[k]
    bucket_bytes = plan.bucket_nbytes(k)
    # accounting basis: one GROUP's payload (the per-shard convention of
    # the flat collectives — stacked bytes / d)
    _account_stages(
        comm, tele, bucket_bytes / d, _daso_stage_factors(d, i), x=leaves[idxs[0]]
    )
    _bucket_counters(bucket_bytes)
    prog = _daso_avg_program(comm, mesh, _daso_sig(leaves, idxs), len(idxs), d, i)
    avgs = list(launch(prog, *(leaves[j] for j in idxs)))
    _ledger_dispatch(bucket_bytes, avgs)
    return avgs


def consume_bucket_averages(comm, leaves, avgs, plan: GradBucketPlan, k: int, w):
    """Consume bucket ``k``: await its in-flight average under the
    watchdog, blend it into the bucket's parameter leaves (donating the
    pre-blend replicas), and retire the transient from the ledger.
    Mutates ``leaves`` in place."""
    idxs = plan.buckets[k]
    _await_bucket(avgs)
    blend = _blend_program(comm, _daso_sig(leaves, idxs), len(idxs))
    out = launch(blend, tuple(leaves[j] for j in idxs), tuple(avgs), w)
    for j, b in zip(idxs, out):
        leaves[j] = b
    _ledger_consume(avgs)


def bucketed_param_sync(comm, params, w, plan: Optional[GradBucketPlan] = None, budget=None):
    """DASO's overlapped cross-group parameter sync: bucket the stacked
    parameter tree, pipeline bucket k+1's collective against bucket k's
    blend (lookahead-1: at most two buckets in flight, transient peak ≤
    budget + one bucket), and return the blended tree.  ``w`` is the blend
    weight (1.0 = full sync).  Semantically identical to
    ``blend(params, global_average(params), w)`` for every bucket count —
    bucketing splits work, never math."""
    import jax

    mesh = comm.mesh
    if int(mesh.shape["dcn"]) <= 1:
        return params  # one group: the cross-group mean is the identity
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if plan is None:
        plan = plan_grad_buckets([a.nbytes for a in leaves], budget)
    if not plan.n_buckets:
        return params
    leaves = list(leaves)
    tele = _Telescope()
    avgs = dispatch_bucket_averages(comm, leaves, plan, 0, tele)
    for k in range(plan.n_buckets):
        nxt = (
            dispatch_bucket_averages(comm, leaves, plan, k + 1, tele)
            if k + 1 < plan.n_buckets
            else None
        )
        consume_bucket_averages(comm, leaves, avgs, plan, k, w)
        avgs = nxt
    return jax.tree_util.tree_unflatten(treedef, leaves)


def dispatch_all_bucket_averages(comm, params, plan: Optional[GradBucketPlan] = None, budget=None):
    """Dispatch EVERY bucket's average without consuming (DASO's stale
    pending path: averages dispatched at step t, blended ``stale_steps``
    later).  All K transients ride in flight — the lookahead-1 bound
    applies to the immediate path, not this one (documented in design.md).
    Returns ``(plan, [bucket averages])`` or None when the mesh has one
    group."""
    import jax

    mesh = comm.mesh
    if int(mesh.shape["dcn"]) <= 1:
        return None
    leaves = jax.tree_util.tree_flatten(params)[0]
    if plan is None:
        plan = plan_grad_buckets([a.nbytes for a in leaves], budget)
    tele = _Telescope()
    return plan, [
        dispatch_bucket_averages(comm, list(leaves), plan, k, tele)
        for k in range(plan.n_buckets)
    ]


def consume_bucket_averages_all(comm, params, pending, w):
    """Blend a :func:`dispatch_all_bucket_averages` result into ``params``
    bucket by bucket (each awaited under the watchdog)."""
    import jax

    if pending is None:
        return params
    plan, all_avgs = pending
    leaves, treedef = jax.tree_util.tree_flatten(params)
    leaves = list(leaves)
    for k in range(plan.n_buckets):
        consume_bucket_averages(comm, leaves, all_avgs[k], plan, k, w)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------- #
# DataParallel bucket engine: stacked per-shard grads → replicated mean
# ---------------------------------------------------------------------- #
def _grad_mean_program(comm, sig, n_leaves: int, p: int, d: int):
    from ._cache import cached_program

    axis = comm.axis
    mesh = comm.mesh

    def build():
        import jax
        from jax.sharding import PartitionSpec as P

        def body(*leaves):
            outs = []
            for g in leaves:
                # g: (1, ...) — this shard's gradient block
                outs.append(_hierarchical_body(g[0], axis, p, d, mean=True))
            return tuple(outs)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis),) * n_leaves,
            # the two-level path ends in an allgather: every shard holds the
            # full mean, so the outputs are replicated
            out_specs=(P(),) * n_leaves,
            check_vma=False,
        )
        return jax.jit(fn)

    return cached_program(comm, ("grad.bucket_mean", sig, p, d), build)


def dispatch_bucket_allreduce(comm, leaves, plan: GradBucketPlan, k: int, tele: _Telescope, d: int):
    """Stage bucket ``k`` of a stacked-gradient mean-allreduce: account the
    two-level stages (or the flat factor when ``d == 1``) through
    ``comm._account_bytes``, then dispatch the cached program.  Returns the
    in-flight replicated mean leaves."""
    p = comm.size
    idxs = plan.buckets[k]
    bucket_bytes = plan.bucket_nbytes(k)
    factors = _hier_stage_factors(p, d)
    if factors is None:
        factors = (2.0 * (p - 1) / p,)  # flat fallback: one ring stage
    # accounting basis: one shard's payload (stacked bytes / p)
    _account_stages(comm, tele, bucket_bytes / p, factors, x=leaves[idxs[0]])
    _bucket_counters(bucket_bytes)
    prog = _grad_mean_program(comm, _daso_sig(leaves, idxs), len(idxs), p, d)
    means = list(launch(prog, *(leaves[j] for j in idxs)))
    _ledger_dispatch(bucket_bytes, means)
    return means


def bucketed_grad_allreduce(
    comm,
    stacked_grads,
    budget=None,
    domains=None,
    plan: Optional[GradBucketPlan] = None,
):
    """Mean-allreduce a pytree of per-shard gradients stacked on a leading
    axis sharded over ``comm``'s mesh axis, bucketed and hierarchical:
    reduce-scatter in the fast domain → cross-domain exchange → allgather,
    with bucket k+1 in flight while bucket k is awaited.  ``domains=None``
    derives the slow-domain count from the process topology (flat allreduce
    when the world has one domain).  Returns the replicated mean tree (leaf
    shapes without the stacking axis)."""
    import jax

    d = _derive_domains(comm, domains)
    leaves, treedef = jax.tree_util.tree_flatten(stacked_grads)
    if plan is None:
        plan = plan_grad_buckets([a.nbytes for a in leaves], budget)
    if not plan.n_buckets:
        return stacked_grads
    tele = _Telescope()
    out: List = [None] * len(leaves)
    means = dispatch_bucket_allreduce(comm, leaves, plan, 0, tele, d)
    for k in range(plan.n_buckets):
        nxt = (
            dispatch_bucket_allreduce(comm, leaves, plan, k + 1, tele, d)
            if k + 1 < plan.n_buckets
            else None
        )
        _await_bucket(means)
        for j, m in zip(plan.buckets[k], means):
            out[j] = m
        _ledger_consume(means)
        means = nxt
    return jax.tree_util.tree_unflatten(treedef, out)


# the memory ledger may have been env-armed (HEAT_TPU_MEMLEDGER=1) while
# this module was still importing — re-read the flag now (defensive
# module-bottom re-arm, the established hot-path-hook pattern)
import sys as _sys  # noqa: E402

_ml = _sys.modules.get("heat_tpu.utils.memledger")
if _ml is not None and getattr(_ml, "enabled", lambda: False)():
    _MEMLEDGER = _ml
del _sys, _ml
