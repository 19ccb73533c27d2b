"""Data-parallel optimizers + DASO (reference: ``heat/optim/dp_optimizer.py``).

``DataParallelOptimizer`` wraps any optax optimizer (or a named torch-style
optimizer) and coordinates with ``nn.DataParallel``'s fused train step.

``DASO`` — Distributed Asynchronous and Selective Optimization — is the
reference's hierarchical data-parallel SGD (SURVEY §2.5/§3.5): NCCL allreduce
across each node's GPUs every step, asynchronous MPI allreduce of PARAMETERS
across nodes every ``global_skip`` steps, blended with a staleness weight.
The TPU translation per SURVEY §2.8: a 2-axis mesh ``('dcn', 'ici')`` —
every step syncs gradients over the fast ``ici`` axis only (each dcn-group
keeps its own parameter replica, sharded over 'dcn'); every ``global_skip``
steps the parameter psum over ``dcn`` is dispatched, and — because JAX
dispatch is asynchronous — consumed ``stale_steps`` later with the staleness
blend, giving the reference's fire-and-forget overlap without request objects.
"""

from __future__ import annotations

import os
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


__all__ = [
    "DataParallelOptimizer",
    "DASO",
    "SGD",
    "Adam",
    "AdamW",
    "nonfinite_guard",
    "NonFiniteGuardState",
]


class NonFiniteGuardState(NamedTuple):
    """State of :func:`nonfinite_guard`: the wrapped optimizer's state plus
    DEVICE-RESIDENT step/skip counters (0-d int32 — reading them is the only
    host sync, and it happens at reporting time, never on the step path)."""

    inner_state: Any
    steps: Any
    skipped: Any


def nonfinite_guard(inner: "optax.GradientTransformation") -> "optax.GradientTransformation":
    """Wrap ``inner`` so a non-finite gradient skips the whole update ON
    DEVICE (SURVEY §5.4 guarded training): one all-reduced finite flag —
    under data parallelism the gradients arriving here are already the
    cross-replica mean, so any replica's NaN/Inf has propagated into every
    replica's copy and the flag agrees SPMD-wide — selects between the
    updated and the previous params/optimizer state with ``jnp.where``.  No
    host sync, no ``float()``: a NaN blow-up costs one skipped step, not a
    poisoned model.  Skip/step counters ride in the state and surface via
    ``DataParallelOptimizer.guard_stats`` / ``DASO.skip_stats`` /
    ``utils.profiler.counters()``."""

    def init_fn(params):
        return NonFiniteGuardState(
            inner.init(params), jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
        )

    def update_fn(updates, state, params=None):
        leaves = jax.tree_util.tree_leaves(updates)
        if leaves:
            finite = jnp.all(
                jnp.stack([jnp.all(jnp.isfinite(u)) for u in leaves])
            )
        else:
            finite = jnp.asarray(True)
        new_updates, new_inner = inner.update(updates, state.inner_state, params)

        def sel(new, old):
            try:
                return jnp.where(finite, new, old)
            except TypeError:
                return new  # non-numeric state leaf: keep the update

        guarded = jax.tree.map(lambda u: sel(u, jnp.zeros_like(u)), new_updates)
        inner_sel = jax.tree.map(sel, new_inner, state.inner_state)
        return guarded, NonFiniteGuardState(
            inner_sel,
            state.steps + 1,
            state.skipped + jnp.where(finite, 0, 1).astype(jnp.int32),
        )

    return optax.GradientTransformation(init_fn, update_fn)


def _guard_counters(opt_state) -> dict:
    """{'steps': int, 'skipped': int} summed over any leading replica axes
    (DASO broadcasts the counters per dcn group).  Syncs the two 0-d/1-d
    counter arrays — call at reporting boundaries only.

    Under multi-process SPMD the per-group counters are sharded over
    processes and a plain ``device_get`` would raise; this reads the
    LOCALLY addressable shards only — a per-rank view, deliberately not a
    collective (reporting must never be able to deadlock a rank whose
    peers aren't reporting), and the multi-rank telemetry merge sums the
    per-rank counter snapshots anyway."""
    if not isinstance(opt_state, NonFiniteGuardState):
        return {}

    def _local(x):
        if getattr(x, "is_fully_addressable", True):
            return jax.device_get(x)  # heatlint: disable=HT101 local-shard read, never collective
        import numpy as _np

        # one value per DISTINCT shard index: each group's counter is
        # replicated over 'ici', so raw addressable_shards holds duplicates
        uniq = {}
        for s in x.addressable_shards:
            uniq.setdefault(str(s.index), _np.asarray(s.data))
        return _np.concatenate([v.reshape(-1) for _, v in sorted(uniq.items())])

    try:
        steps, skipped = _local(opt_state.steps), _local(opt_state.skipped)
    except RuntimeError as e:
        if "deleted" not in str(e).lower():
            raise
        # the tracked tree was DONATED to a jitted step (make_train_step's
        # default) — the live state is whatever the train loop rebound
        raise RuntimeError(
            "optimizer state buffers were donated to the train step; pass "
            "the current state explicitly: guard_stats(opt_state)"
        ) from e
    import numpy as _np

    return {"steps": int(_np.max(steps)), "skipped": int(_np.sum(skipped))}


# buffers by name: BatchNorm's ``running_*`` statistics and an expert layer's
# selection bias (``nn.MoE(expert_bias=True)``)
_BUFFER_PREFIX, _BUFFER_NAMES = "running_", ("expert_bias",)


def _nontrainable_mask(params):
    """True for trainable leaves, False for buffers (``running_*`` stats of
    BatchNorm and the experts' selection bias live in the params pytree but
    must receive no updates and no weight decay)."""
    import jax

    def is_trainable(path):
        names = [str(getattr(k, "key", "")) for k in path if getattr(k, "key", None) is not None]
        return not any(n.startswith(_BUFFER_PREFIX) or n in _BUFFER_NAMES for n in names)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [is_trainable(p) for p, _ in flat])


def _mask_buffers(opt: "optax.GradientTransformation") -> "optax.GradientTransformation":
    """Mask any ``running_*`` buffer leaves out of an optax transformation."""
    return optax.masked(opt, _nontrainable_mask)


def _named_optimizer(name: str, **kw):
    table = {
        "sgd": lambda lr=0.01, momentum=0.0, weight_decay=0.0, nesterov=False: optax.chain(
            optax.add_decayed_weights(weight_decay) if weight_decay else optax.identity(),
            optax.sgd(lr, momentum=momentum if momentum else None, nesterov=nesterov),
        ),
        "adam": lambda lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0: optax.adam(
            lr, b1=betas[0], b2=betas[1], eps=eps
        ),
        "adamw": lambda lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=1e-2, mask=None: optax.adamw(
            lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=weight_decay, mask=mask
        ),
    }
    if name.lower() not in table:
        raise ValueError(f"Unknown optimizer {name!r}")
    return table[name.lower()](**kw)


def SGD(params=None, lr: float = 0.01, momentum: float = 0.0, weight_decay: float = 0.0, nesterov: bool = False):
    """torch-style constructor returning an optax optimizer."""
    return _named_optimizer("sgd", lr=lr, momentum=momentum, weight_decay=weight_decay, nesterov=nesterov)


def Adam(params=None, lr: float = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8):
    return _named_optimizer("adam", lr=lr, betas=betas, eps=eps)


def AdamW(params=None, lr: float = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8, weight_decay: float = 1e-2,
          mask=None):
    """``mask`` (a pytree of bools like the parameters, or a function from
    the parameters to one) names the leaves that decay; ``None``: all."""
    return _named_optimizer("adamw", lr=lr, betas=betas, eps=eps, weight_decay=weight_decay, mask=mask)


class DataParallelOptimizer:
    """Wrap an optax optimizer for use with ``nn.DataParallel``.

    Accepts an optax GradientTransformation, or a name ('sgd' | 'adam' |
    'adamw') + kwargs, mirroring ``ht.optim.DataParallelOptimizer(torch_opt)``.

    ``guard_nonfinite`` (default True) compiles a non-finite guard into every
    update — a NaN/Inf gradient skips the step on device (params and inner
    optimizer state unchanged, skip counter incremented) instead of poisoning
    the model; see :func:`nonfinite_guard`.  Counters: :meth:`guard_stats`.
    """

    def __init__(
        self,
        optimizer,
        blocking: bool = False,
        guard_nonfinite: bool = True,
        overlap_sync: bool = False,
        grad_bucket_bytes=None,
        **kwargs,
    ):
        if isinstance(optimizer, str):
            optimizer = _named_optimizer(optimizer, **kwargs)
        # buffers (BatchNorm running stats) get neither updates nor decay
        base = _mask_buffers(optimizer)
        self.guarded = bool(guard_nonfinite)
        self.optax_optimizer = nonfinite_guard(base) if self.guarded else base
        self.blocking = blocking
        # opt-in bucketed hierarchical gradient sync (core.collectives):
        # picked up by DataParallel.make_train_step / allreduce_grads; the
        # default train step is bit-exact unchanged when False
        self.overlap_sync = bool(overlap_sync)
        self.grad_bucket_bytes = grad_bucket_bytes
        self._dp = None
        self._opt_state = None
        from ..utils import profiler as _profiler

        # guard step/skip counters surface in profiler.counters() /
        # telemetry.report() like DASO's; the provider name is unique per
        # instance and the bound method is held weakly (dies with self)
        self.profiler_key = _profiler.register_counter_provider(
            "optim", self._counter_snapshot
        )

    def _counter_snapshot(self) -> dict:
        """Profiler counter provider.  Returns {} (not None — None would
        deregister) when the eagerly-tracked state is absent or was donated
        to a jitted step (the live state lives in the caller's loop)."""
        try:
            s = _guard_counters(self._opt_state)
        except RuntimeError:
            return {}
        if not s:
            return {}
        return {"steps": s["steps"], "skipped_steps": s["skipped"]}

    def _attach(self, dp) -> None:
        self._dp = dp

    def init_state(self, params):
        self._opt_state = self.optax_optimizer.init(params)
        return self._opt_state

    @property
    def state(self):
        return self._opt_state

    @state.setter
    def state(self, s):
        self._opt_state = s

    def _update(self, params, grads, opt_state):
        updates, new_state = self.optax_optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state

    def step(self, params, grads):
        """Eager parameter update (gradients already globally averaged by XLA)."""
        from ..utils import telemetry as _tel

        if self._opt_state is None:
            self.init_state(params)
        if not _tel._ENABLED:
            with _tel.span("optim.step"):  # the profiler's annotation while a profile records
                new_params, self._opt_state = self._update(params, grads, self._opt_state)
            return new_params
        t0 = time.perf_counter()
        with _tel.span("optim.step"):
            new_params, self._opt_state = self._update(params, grads, self._opt_state)
        # dispatch-side latency (JAX is async — no host sync is added here)
        _tel.observe("optim.step_dispatch_s", time.perf_counter() - t0)
        return new_params

    def allreduce_grads(self, comm, stacked_grads, domains=None):
        """Bucketed hierarchical mean-allreduce of per-shard gradients
        stacked on a leading axis sharded over ``comm``'s mesh axis
        (``core.collectives.bucketed_grad_allreduce``): byte-budgeted
        buckets (``grad_bucket_bytes`` / ``ht.set_grad_bucket_budget`` /
        ``HEAT_TPU_GRAD_BUCKET_BYTES``), bucket k+1's transfer in flight
        while bucket k is consumed, two-level reduce-scatter → cross-domain
        exchange → allgather when the topology has more than one domain
        (flat allreduce otherwise).  Returns the replicated mean tree."""
        from ..core import collectives as _coll

        return _coll.bucketed_grad_allreduce(
            comm, stacked_grads, budget=self.grad_bucket_bytes, domains=domains
        )

    def zero_grad(self) -> None:
        """No-op: JAX gradients are functional (kept for API parity)."""

    def guard_stats(self, opt_state=None) -> dict:
        """{'steps', 'skipped'} of the non-finite guard.  Pass the state your
        train loop threads through a jitted step; defaults to the eagerly
        tracked one.  Syncs two scalars — call at reporting boundaries."""
        s = opt_state if opt_state is not None else self._opt_state
        return _guard_counters(s) or {"steps": 0, "skipped": 0}


class DASO:
    """Hierarchical async data parallelism on a ('dcn', 'ici') mesh.

    Parameters (reference names): ``local_optimizer``, ``total_local_comm_size``
    (size of the fast axis; default = all devices on one host ring),
    ``global_skip`` (steps between inter-group syncs), ``stale_steps``
    (dispatch-to-consume delay of the global average), ``staleness_weight``
    (blend factor for the stale global params), ``warmup_steps`` (full sync
    every step at the start), ``cooldown_epochs`` + ``total_epochs`` (fully
    synchronous final phase), ``plateau_tol`` (relative improvement below
    which :meth:`epoch_loss_logic` halves ``global_skip``).
    """

    def __init__(
        self,
        local_optimizer: DataParallelOptimizer,
        total_local_comm_size: Optional[int] = None,
        global_skip: int = 4,
        stale_steps: int = 1,
        staleness_weight: float = 0.5,
        warmup_steps: int = 4,
        cooldown_epochs: int = 0,
        total_epochs: Optional[int] = None,
        plateau_tol: float = 0.05,
        mesh=None,
        checkpoint_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        overlap_sync: bool = False,
        grad_bucket_bytes=None,
    ):
        if isinstance(local_optimizer, DataParallelOptimizer):
            self.local_optimizer = local_optimizer
        else:
            self.local_optimizer = DataParallelOptimizer(local_optimizer)
        self.global_skip = max(int(global_skip), 1)
        self.stale_steps = max(int(stale_steps), 0)
        self.staleness_weight = float(staleness_weight)
        self.warmup_steps = int(warmup_steps)
        self.cooldown_epochs = int(cooldown_epochs)
        self.total_epochs = total_epochs
        self.plateau_tol = float(plateau_tol)
        if self.cooldown_epochs > 0 and total_epochs is None:
            raise ValueError(
                "cooldown_epochs requires total_epochs so DASO knows when the "
                "final synchronous phase begins (reference: DASO's cooldown "
                "switches to full sync for the LAST cooldown_epochs epochs)"
            )
        self._epoch = 0
        self._best_epoch_loss = None
        self.in_cooldown = False

        if mesh is None:
            all_devs = jax.devices()
            n = len(all_devs)
            ici = total_local_comm_size or self._default_ici(n)
            if n % ici != 0:
                raise ValueError(f"total_local_comm_size {ici} must divide device count {n}")
            import numpy as np
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(all_devs).reshape(n // ici, ici), ("dcn", "ici"))
        self.mesh = mesh
        self.n_groups = mesh.shape["dcn"]
        self.ici_size = mesh.shape["ici"]
        self._step_count = 0
        self._pending = None  # (dispatched global average, due_step)
        self._train_step = None
        self._sync_step = None
        # opt-in bucketed hierarchical dcn-tier sync (core.collectives):
        # the default schedule below is bit-exact unchanged when False
        self.overlap_sync = bool(overlap_sync)
        self.grad_bucket_bytes = grad_bucket_bytes
        self._sync_comm = None  # lazy Communication(mesh, 'dcn') + bucket plan
        self._bucket_plan = None
        # opt-in durable auto-checkpoint: every K steps the full training
        # state (per-group params + opt state + step count) is written
        # atomically; resume() restores it after a preemption/crash
        if checkpoint_every is not None and checkpoint_dir is None:
            raise ValueError("checkpoint_every requires checkpoint_dir")
        self.checkpoint_every = int(checkpoint_every) if checkpoint_every else None
        self.checkpoint_dir = checkpoint_dir
        from ..utils import profiler as _profiler

        # unique per instance ("daso", "daso2", ...): concurrent optimizers
        # never shadow each other's counters in profiler.counters()
        self.profiler_key = _profiler.register_counter_provider(
            "daso", self._counter_snapshot
        )

    def _overlap_state(self):
        """Lazy (Communication('dcn'), GradBucketPlan) for the opt-in
        overlapped sync — the comm instance carries the per-bucket program
        cache and the accounting/flight-ring/deadline choke point; the plan
        is computed ONCE (leaf sizes are static for a model's lifetime), so
        steady state re-plans and recompiles nothing."""
        if self._sync_comm is None:
            from ..core import collectives as _coll
            from ..core.communication import Communication

            self._sync_comm = Communication(self.mesh, "dcn")
            leaves = jax.tree_util.tree_leaves(self._params)
            self._bucket_plan = _coll.plan_grad_buckets(
                [a.nbytes for a in leaves], self.grad_bucket_bytes
            )
        return self._sync_comm, self._bucket_plan

    def _sync_label(self) -> str:
        """``sync=`` attribute of the ``daso.step`` span: 'bucketed' when
        the opt-in overlapped path splits the sync, 'monolithic' otherwise
        (stepprof groups on it and prints STEP-OVERLAP-DELTA when a merge
        dir holds both)."""
        if not self.overlap_sync or getattr(self, "_params", None) is None:
            return "monolithic"
        return "bucketed" if self._overlap_state()[1].n_buckets > 1 else "monolithic"

    @staticmethod
    def _default_ici(n: int) -> int:
        ici = 1
        while ici * 2 <= n and n % (ici * 2) == 0 and ici * 2 <= 8:
            ici *= 2
        return ici

    # ------------------------------------------------------------------ #
    def init(self, module, key=None, sample_input=None):
        """Per-group parameter replicas: leading axis n_groups, sharded over dcn."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if key is None:
            key = jax.random.key(0)
        params = module.init(key)
        # stack one replica per dcn group
        stacked = jax.tree.map(lambda p: jnp.broadcast_to(p[None], (self.n_groups,) + p.shape), params)
        sh = lambda p: jax.device_put(p, NamedSharding(self.mesh, P("dcn", *([None] * (p.ndim - 1)))))
        self._params = jax.tree.map(sh, stacked)
        # per-group optimizer states
        self._opt_state = jax.tree.map(
            lambda s: jnp.broadcast_to(s[None], (self.n_groups,) + s.shape) if hasattr(s, "ndim") else s,
            self.local_optimizer.optax_optimizer.init(jax.tree.map(lambda p: p[0], self._params)),
        )
        # memory-ledger registration (HT111 registrar): params and the
        # per-group optimizer moments are the long-lived buffers the
        # ROADMAP's ZeRO-1 item promises to shrink — categorized here so
        # mem.live_bytes.opt-state IS the before-number that PR must beat
        from ..utils import memledger

        if memledger.enabled():
            jax.tree.map(
                lambda p: memledger.register(
                    p, op="daso.init", site="factory", category="param"
                ),
                self._params,
            )
            jax.tree.map(
                lambda s: memledger.register(
                    s, op="daso.init", site="factory", category="opt-state"
                )
                if hasattr(s, "ndim")
                else None,
                self._opt_state,
            )
        self.module = module
        return self._params

    @property
    def parameters(self):
        return self._params

    def _build_steps(self, loss_fn):
        from ..nn.modules import _module_accepts_train

        apply = self.module.apply
        opt = self.local_optimizer.optax_optimizer
        mesh = self.mesh

        # training-mode forward for heat modules and duck-typed modules with
        # an explicit train parameter (BatchNorm batch statistics, keyed
        # Dropout); flax-style **kwargs applies are called plain
        accepts_train = _module_accepts_train(self.module)

        def fwd(p, x, key):
            if not accepts_train:
                return apply(p, x)
            if key is not None:
                return apply(p, x, train=True, key=key)
            return apply(p, x, train=True)

        from jax.sharding import PartitionSpec as P

        def shard_step(params, opt_state, x, y, key):
            """Per-(dcn, ici) mesh cell: params/opt_state are ONE group's
            replica (leading axis 1, replicated over 'ici'); x/y are this
            cell's slice of the group's batch (sharded over 'ici').

            The reference's two tiers map exactly (SURVEY §2.8):
            - per-step node-local NCCL allreduce  →  the EXPLICIT
              ``lax.pmean(grads, 'ici')`` below, a per-step collective over
              the fast axis only;
            - every-k async MPI parameter averaging  →  the dcn-tier
              ``_global_average``/``_blend`` schedule in :meth:`step`.
            """
            p0 = jax.tree.map(lambda q: q[0], params)
            s0 = jax.tree.map(lambda q: q[0], opt_state)
            x, y = x[0], y[0]  # drop the per-cell group axis (size 1)

            def loss(p):
                return loss_fn(fwd(p, x, key), y)

            lval, grads = jax.value_and_grad(loss)(p0)
            grads = jax.lax.pmean(grads, "ici")  # in-group gradient allreduce
            lval = jax.lax.pmean(lval, "ici")
            updates, new_state = opt.update(grads, s0, p0)
            new_p = optax.apply_updates(p0, updates)
            lift = lambda t: jax.tree.map(lambda q: jnp.asarray(q)[None], t)
            return lift(new_p), lift(new_state), lval[None]

        def _smap(fn, with_keys: bool):
            in_specs = [P("dcn"), P("dcn"), P("dcn", "ici"), P("dcn", "ici")]
            if with_keys:
                in_specs.append(P("dcn", "ici"))
            return jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=(P("dcn"), P("dcn"), P("dcn")),
                check_vma=False,
            )

        import functools

        # params/opt_state are DONATED: each step's replicas alias (or free
        # early into) the previous step's buffers, so training never holds
        # two full copies of the model state — the donate_argnums discipline
        # of a production train loop.  self._params/_opt_state are rebound
        # immediately on return, so nothing reads the consumed buffers.
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, xs, ys):
            return _smap(
                lambda p, s, x, y: shard_step(p, s, x, y, None), with_keys=False
            )(params, opt_state, xs, ys)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step_rng(params, opt_state, xs, ys, keys):
            # keys: (n_groups, ici) key array; each mesh cell gets its (1,1) block
            def fn(p, s, x, y, k):
                return shard_step(p, s, x, y, k[0, 0])

            return _smap(fn, with_keys=True)(params, opt_state, xs, ys, keys)

        # NOT donated: step() reads params again after dispatching the average
        @jax.jit
        def global_average(params):
            return jax.tree.map(lambda p: jnp.mean(p, axis=0, keepdims=True), params)

        # the blend CONSUMES the pre-blend replicas (donated); avg is kept —
        # a pending stale average must survive if the same tree is reused
        @functools.partial(jax.jit, donate_argnums=(0,))
        def blend(params, avg, w):
            return jax.tree.map(
                lambda p, a: (1.0 - w) * p + w * jnp.broadcast_to(a, p.shape), params, avg
            )

        self._train_step = train_step
        self._train_step_rng = train_step_rng
        self._global_average = global_average
        self._blend = blend

    def step(self, loss_fn, x, y, key=None):
        """One DASO step on a global batch (leading axis divisible by n_groups).

        Every step: per-group sync training (the 'ici' tier).  Every
        ``global_skip`` steps: dispatch the cross-group parameter average (the
        'dcn' tier); consume it ``stale_steps`` later with the staleness blend.
        During warmup, sync fully every step.  Pass ``key`` when the model
        contains stochastic layers (Dropout): each group receives a split.

        Telemetry (when enabled): each step runs under a ``daso.step`` span
        and its DISPATCH-side wall time feeds the ``daso.step_dispatch_s``
        latency histogram — the step stays asynchronous (no host sync is
        added; the returned loss is still a 0-d device array).
        """
        from ..utils import telemetry as _tel

        if not _tel._ENABLED:
            with _tel.span("daso.step"):  # the profiler's annotation while a profile records
                return self._step_impl(loss_fn, x, y, key)
        t0 = time.perf_counter()
        with _tel.span(
            "daso.step", step=self._step_count + 1, sync=self._sync_label()
        ):
            out = self._step_impl(loss_fn, x, y, key)
        _tel.observe("daso.step_dispatch_s", time.perf_counter() - t0)
        return out

    def _step_impl(self, loss_fn, x, y, key=None):
        if self._train_step is None:
            self._build_steps(loss_fn)
        jx = x._jarray if hasattr(x, "_jarray") else jnp.asarray(x)
        jy = y._jarray if hasattr(y, "_jarray") else jnp.asarray(y)
        g = self.n_groups
        if jx.shape[0] % (g * self.ici_size):
            raise ValueError(
                f"global batch {jx.shape[0]} must be divisible by n_groups*ici "
                f"= {g}*{self.ici_size} (each ici shard computes a batch slice)"
            )
        xs = jx.reshape((g, jx.shape[0] // g) + jx.shape[1:])
        ys = jy.reshape((g, jy.shape[0] // g) + jy.shape[1:])

        if key is not None:
            keys = jax.random.split(key, g * self.ici_size).reshape(g, self.ici_size)
            self._params, self._opt_state, losses = self._train_step_rng(
                self._params, self._opt_state, xs, ys, keys
            )
        else:
            self._params, self._opt_state, losses = self._train_step(self._params, self._opt_state, xs, ys)
        self._step_count += 1
        t = self._step_count

        if self.overlap_sync:
            from ..core import collectives as _coll

        if t <= self.warmup_steps:
            if self.overlap_sync:
                comm, plan = self._overlap_state()
                self._params = _coll.bucketed_param_sync(
                    comm, self._params, 1.0, plan=plan
                )
            else:
                avg = self._global_average(self._params)
                self._params = self._blend(self._params, avg, 1.0)  # full sync
        else:
            if self._pending is not None and t >= self._pending[1]:
                avg, _ = self._pending
                if self.overlap_sync:
                    self._params = _coll.consume_bucket_averages_all(
                        self._sync_comm, self._params, avg, self.staleness_weight
                    )
                else:
                    self._params = self._blend(self._params, avg, self.staleness_weight)
                self._pending = None
            # dispatch a new global average only when none is in flight —
            # otherwise stale_steps > global_skip would overwrite the pending
            # average forever and the dcn tier would never sync
            if t % self.global_skip == 0 and self._pending is None:
                if self.overlap_sync:
                    comm, plan = self._overlap_state()
                    if self.stale_steps == 0:
                        self._params = _coll.bucketed_param_sync(
                            comm, self._params, self.staleness_weight, plan=plan
                        )
                    else:
                        # pending payload = every bucket's average in flight at
                        # once (the stale window IS the overlap); consumed
                        # stale_steps later by consume_bucket_averages_all
                        self._pending = (
                            _coll.dispatch_all_bucket_averages(
                                comm, self._params, plan=plan
                            ),
                            t + self.stale_steps,
                        )
                else:
                    # dispatched now (async under JAX), consumed stale_steps later
                    avg = self._global_average(self._params)
                    if self.stale_steps == 0:
                        self._params = self._blend(self._params, avg, self.staleness_weight)
                    else:
                        self._pending = (avg, t + self.stale_steps)
        if self.checkpoint_every and t % self.checkpoint_every == 0:
            self.checkpoint()
        # fault site ``proc.exit`` (elastic-runtime chaos lane): arming
        # ``proc.exit:exit=N`` on one rank SIGKILLs it after its Nth step —
        # the deterministic "rank dies mid-training" the supervisor must
        # detect and recover from.  Disarmed cost: one dict miss.
        from ..utils import faults as _flt

        _flt.fire("proc.exit")
        # asynchronous loss: a 0-d device array (duck-types float) — the old
        # float(...) here was a blocking host sync on EVERY step, serializing
        # the train loop on the slowest collective.  Callers that need the
        # number call float() at their own materialization point.
        return jnp.mean(losses)

    def epoch_loss_logic(self, epoch_loss) -> int:
        """Adaptive skip schedule — call once per epoch with the epoch's mean
        loss (reference: ``heat/optim/dp_optimizer.py`` ``DASO.epoch_loss_logic``,
        SURVEY §2.5 "auto-tuned skips shrinking as loss plateaus").

        Two mechanisms, applied in priority order:

        - **cooldown**: the call ends epoch ``e``; when every remaining
          epoch lies in the final ``cooldown_epochs`` of ``total_epochs``,
          switch to fully synchronous training (``global_skip=1``, no
          staleness, full-weight blend) so the final model is exactly
          averaged — the reference's cooldown phase.
        - **plateau**: if the epoch loss failed to improve on the best loss
          so far by more than ``plateau_tol`` (relative), halve
          ``global_skip`` (floor 1): stale wide-interval averaging is cheap
          while loss falls fast, but once progress stalls the groups must
          sync tighter to keep converging.

        Returns the ``global_skip`` now in force.
        """
        self._epoch += 1
        epoch_loss = float(epoch_loss)
        if (
            self.total_epochs is not None
            and self.cooldown_epochs > 0
            and self._epoch >= self.total_epochs - self.cooldown_epochs
        ):
            self.in_cooldown = True
            self.global_skip = 1
            self.stale_steps = 0
            self.staleness_weight = 1.0
            # drop any in-flight pre-cooldown average: consuming it at the
            # cooldown's full blend weight would overwrite every replica
            # with stale parameters and discard the updates since dispatch
            self._pending = None
        elif self._best_epoch_loss is not None:
            ref = abs(self._best_epoch_loss)
            improved = (self._best_epoch_loss - epoch_loss) > self.plateau_tol * (
                ref if ref > 0 else 1.0
            )
            if not improved and self.global_skip > 1:
                self.global_skip = max(self.global_skip // 2, 1)
        if self._best_epoch_loss is None or epoch_loss < self._best_epoch_loss:
            self._best_epoch_loss = epoch_loss
        return self.global_skip

    def consolidated_params(self):
        """The cross-group averaged parameters (for eval/checkpoint)."""
        avg = self._global_average(self._params)
        return jax.tree.map(lambda a: a[0], avg)

    def zero_grad(self) -> None:
        """No-op (API parity)."""

    # ------------------------------------------------------------------ #
    # failure hardening: skip counters + durable checkpoint/resume
    # ------------------------------------------------------------------ #
    def skip_stats(self) -> dict:
        """{'steps': train steps taken, 'skipped': group-updates suppressed
        by the non-finite guard}.  The skip counter lives ON DEVICE inside
        the optimizer state (no host sync on the step path); reading here
        syncs it."""
        counters = _guard_counters(getattr(self, "_opt_state", None))
        return {"steps": self._step_count, "skipped": counters.get("skipped", 0)}

    def _counter_snapshot(self) -> dict:
        """utils.profiler counter provider (polled at reporting time)."""
        s = self.skip_stats()
        return {"steps": s["steps"], "skipped_steps": s["skipped"]}

    _CKPT_NAME = "daso_state.npz"
    _PREV_NAME = "daso_state.prev.npz"
    _META_NAME = "daso_state.meta.json"

    def _world_meta(self) -> dict:
        return {
            "n_groups": int(self.n_groups),
            "ici": int(self.ici_size),
            "devices": int(len(self.mesh.devices.ravel())),
        }

    def checkpoint(self, directory: Optional[str] = None) -> str:
        """Atomically checkpoint the full training state (per-group params,
        optimizer state incl. guard counters, step count) to
        ``<dir>/daso_state.npz`` via the durable pytree writer; returns the
        path.  Called automatically every ``checkpoint_every`` steps.

        Two durability extras for the elastic runtime:

        - the previously durable state is preserved as
          ``daso_state.prev.npz`` before the new save, so :meth:`resume`
          has a verified-fallback target when the newest file is corrupt
          (bit rot between crash and restart);
        - a ``daso_state.meta.json`` sidecar records the step count and the
          world shape (n_groups, ici, device count) so a restarted world
          can refuse a mismatched topology with a clear error instead of a
          shape crash deep inside the loader.
        """
        import json as _json
        import shutil as _shutil

        from ..core import io as _io

        d = directory or self.checkpoint_dir
        if d is None:
            raise ValueError("no checkpoint directory configured")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, self._CKPT_NAME)
        if os.path.exists(path):
            # copy (not rename): `path` stays durable through the whole new
            # save; `prev` only ever holds a complete older state
            try:
                _shutil.copy2(path, os.path.join(d, self._PREV_NAME))
            except OSError:
                pass  # a missing fallback degrades recovery, never the save
        tree = {
            "params": self._params,
            "opt_state": self._opt_state,
            "step": jnp.asarray(self._step_count, jnp.int32),
        }
        _io.save_checkpoint(tree, path)
        meta = dict(self._world_meta(), step=int(self._step_count), time=time.time())
        mpath = os.path.join(d, self._META_NAME)
        tmp = f"{mpath}.tmp.{os.getpid()}"  # per-pid: SPMD ranks share the dir
        with open(tmp, "w") as fh:
            _json.dump(meta, fh)
        os.replace(tmp, mpath)
        return path

    def resume(self, directory: Optional[str] = None) -> bool:
        """Restore the newest auto-checkpoint (False when none exists yet).
        Call after :meth:`init` — the live params/opt-state tree provides the
        structure, dtypes and shardings the loaded leaves are validated
        against and placed back onto.  Any in-flight global average is
        dropped (it refers to pre-crash state).

        Validation and fallback (the restart-with-resume contract):

        - the sidecar's world shape must match this optimizer's mesh — a
          restarted world with a different n_groups/ici/device count gets a
          clear ``ValueError`` naming both topologies, not a shape crash;
        - a corrupt/torn ``daso_state.npz`` falls back (with a warning and
          a ``health.resume.fallbacks`` counter) to the preserved
          ``daso_state.prev.npz``; only when nothing verifies does the
          corruption error surface;
        - a sidecar step disagreeing with the restored tree's step (the
          crash window between the two writes) is warned about — the tree,
          which is what actually restores, wins.
        """
        import json as _json
        import warnings as _warnings

        from ..core import io as _io
        from ..utils import health as _health

        d = directory or self.checkpoint_dir
        if d is None:
            raise ValueError("no checkpoint directory configured")
        path = os.path.join(d, self._CKPT_NAME)
        prev = os.path.join(d, self._PREV_NAME)
        if not os.path.exists(path) and not os.path.exists(prev):
            return False
        if not hasattr(self, "_params"):
            raise RuntimeError("call init() before resume(): the live tree "
                               "provides the structure to restore into")
        meta = None
        try:
            with open(os.path.join(d, self._META_NAME)) as fh:
                meta = _json.load(fh)
        except (OSError, ValueError):
            meta = None  # pre-sidecar checkpoint or torn write: skip checks
        if meta is not None:
            want = self._world_meta()
            got = {k: int(meta.get(k, want[k])) for k in want}
            if got != want:
                raise ValueError(
                    f"checkpoint under {d!r} was written by a different world: "
                    f"checkpoint {got} vs this optimizer {want} — a restarted "
                    "world must be rebuilt with the same n_groups/ici/device "
                    "count to resume this state"
                )
        tree_like = {
            "params": self._params,
            "opt_state": self._opt_state,
            "step": jnp.asarray(0, jnp.int32),
        }
        used_fallback = False
        try:
            loaded = _io.load_checkpoint(tree_like, path)
        except (_io.CheckpointCorruptionError, FileNotFoundError) as e:
            if not os.path.exists(prev):
                raise
            _warnings.warn(
                f"newest DASO checkpoint is unusable ({e}); falling back to "
                f"the preserved previous state {prev!r}"
            )
            _health.counter_inc("health.resume.fallbacks")
            loaded = _io.load_checkpoint(tree_like, prev)
            used_fallback = True
        from jax.sharding import NamedSharding

        multiprocess = jax.process_count() > 1

        def place(new, old):
            # restore mesh shardings (params live sharded over 'dcn');
            # everything else stays UNcommitted like init() leaves it, so
            # jit remains free to co-locate it with the params
            sh = getattr(old, "sharding", None)
            if isinstance(sh, NamedSharding):
                if multiprocess:
                    # device_put of host data onto a multi-process mesh runs
                    # the NaN-hostile multihost assert_equal; build the
                    # global array from per-device slices instead (same
                    # hazard Communication.shard handles)
                    import numpy as _np

                    from ..core.communication import _array_from_callback

                    return _array_from_callback(_np.asarray(new), sh)
                return jax.device_put(jnp.asarray(new), sh)
            return jnp.asarray(new)

        self._params = jax.tree.map(place, loaded["params"], self._params)
        self._opt_state = jax.tree.map(place, loaded["opt_state"], self._opt_state)
        # re-register the REPLACEMENT buffers with the memory ledger, like
        # init() does: the leaves io.load_checkpoint registered were the
        # host-side intermediates place() discarded (their weakref deaths
        # decrement), and without this a resumed job's mem.live_bytes.param/
        # .opt-state would collapse to ~0 — losing the very before-numbers
        # the ZeRO-1 ROADMAP item measures
        from ..utils import memledger as _memledger

        if _memledger.enabled():

            def _reg(leaf, cat):
                # register covers the freshly-placed buffers; reclassify
                # corrects leaves place() passed through UNCHANGED — those
                # are the very objects load_checkpoint already registered
                # (site=ckpt defaults to `param`), and first-registration-
                # wins would otherwise leave moments misfiled as params
                _memledger.register(leaf, op="daso.resume", site="ckpt",
                                    category=cat)
                _memledger.reclassify(leaf, op="daso.resume", category=cat)

            jax.tree.map(lambda p: _reg(p, "param"), self._params)
            jax.tree.map(
                lambda s: _reg(s, "opt-state") if hasattr(s, "ndim") else None,
                self._opt_state,
            )
        self._step_count = int(loaded["step"])
        if meta is not None and not used_fallback and int(meta.get("step", -1)) not in (
            -1, self._step_count
        ):
            _warnings.warn(
                f"checkpoint sidecar records step {meta.get('step')} but the "
                f"restored tree holds step {self._step_count} (crash window "
                "between the two writes); trusting the restored tree"
            )
        self._pending = None
        # restart-with-resume marker in the flight recorder: the analyzer
        # reads `resume` events to tell a relaunched generation's ring from
        # a first boot (no-op when the recorder is disarmed)
        from ..utils import flightrec as _flightrec

        _flightrec.record_event(
            "resume", step=int(self._step_count),
            epoch=_health.restart_epoch(), fallback=bool(used_fallback),
        )
        return True
