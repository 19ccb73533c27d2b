"""Data-parallel NN training (reference: ``heat/nn/data_parallel.py``).

The reference registers per-parameter backward hooks that fire nonblocking
MPI ``Iallreduce``s as gradients become ready, overlapping communication with
the rest of backward (SURVEY §3.5).  The TPU-native design makes that entire
mechanism disappear: parameters are replicated, the batch is sharded over the
mesh, and ``jax.grad`` of the global-mean loss *is* the gradient allreduce —
XLA's latency-hiding scheduler overlaps the psum with backward computation,
which is exactly the hook/bucket machinery, minus the code.

``DataParallel`` therefore carries the reference's API (module wrapper,
``comm``, ``optimizer`` coordination, ``blocking`` accepted for parity) while
the train step is ONE compiled program.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core import types
from ..core.communication import Communication, sanitize_comm
from ..core.dndarray import DNDarray
from .modules import Module

__all__ = ["DataParallel", "DataParallelMultiGPU"]


def _as_jax(x):
    return x._jarray if isinstance(x, DNDarray) else x


def _instrumented_step(jitted, sync=None):
    """Wrap a jitted train step with the telemetry tail: an ``nn.train_step``
    span plus the ``nn.train_step_dispatch_s`` latency histogram when
    telemetry is enabled (dispatch-side wall time — the step stays async,
    no host sync is added).  Disabled cost: one flag check.  The jitted
    function's introspection surface (``.lower``) is preserved.  ``sync``
    (a 0-arg callable or a string) labels the span's ``sync=`` attribute so
    stepprof can split monolithic vs bucketed runs."""
    import functools
    import time

    from ..utils import telemetry as _tel

    @functools.wraps(jitted)
    def step(*args):
        if not _tel._ENABLED:
            with _tel.span("nn.train_step"):  # the profiler's annotation while a profile records
                return jitted(*args)
        t0 = time.perf_counter()
        attrs = {} if sync is None else {"sync": sync() if callable(sync) else sync}
        with _tel.span("nn.train_step", **attrs):
            out = jitted(*args)
        _tel.observe("nn.train_step_dispatch_s", time.perf_counter() - t0)
        return out

    if hasattr(jitted, "lower"):
        step.lower = jitted.lower
    return step


class DataParallel:
    """Wrap a module for synchronous data-parallel training.

    Parameters
    ----------
    module : Module (or flax-style object with init/apply)
    comm : Communication, optional
        Mesh axis the batch is sharded over (default world).
    optimizer : DataParallelOptimizer, optional
        If given, ``train_step`` fuses forward+backward+psum+update.
    blocking : bool
        Accepted for reference parity; XLA collectives are always
        asynchronously scheduled, so both modes are the overlapped one.
    """

    def __init__(self, module: Module, comm: Optional[Communication] = None,
                 optimizer=None, blocking: bool = False, scale_gradient_average=None):
        self.module = module
        self.comm = sanitize_comm(comm)
        self.optimizer = optimizer
        self.blocking = blocking
        self._params = None
        self._train_step = None
        if optimizer is not None:
            optimizer._attach(self)

    # -- parameter management ------------------------------------------- #
    def init(self, key=None, sample_input=None):
        """Initialize (replicated) parameters."""
        if key is None:
            key = jax.random.key(0)
        if hasattr(self.module, "init"):
            try:
                self._params = self.module.init(key)
            except TypeError:
                # flax signature: init(key, x)
                self._params = self.module.init(key, _as_jax(sample_input))
        else:
            raise TypeError("module must provide init()")
        # replicate across the mesh
        self._params = jax.tree.map(lambda p: self.comm.shard(p, None), self._params)
        return self._params

    @property
    def parameters(self):
        return self._params

    @parameters.setter
    def parameters(self, params):
        self._params = params

    def state_dict(self):
        """Flat {path: array} of parameters (torch-style checkpoint dict)."""
        flat = jax.tree_util.tree_flatten_with_path(self._params)[0]
        return {jax.tree_util.keystr(path): leaf for path, leaf in flat}

    def load_state_dict(self, state):
        flat, treedef = jax.tree_util.tree_flatten_with_path(self._params)
        new_leaves = [jnp.asarray(state[jax.tree_util.keystr(p)]) for p, _ in flat]
        self._params = jax.tree_util.tree_unflatten(treedef, new_leaves)

    # -- forward -------------------------------------------------------- #
    def forward(self, x, **kw):
        if self._params is None:
            self.init(sample_input=x)
        jx = _as_jax(x)
        y = self.module.apply(self._params, jx, **kw)
        if isinstance(x, DNDarray):
            split = x.split
            y = x.comm.shard(y, split if split is not None and split < y.ndim else None)
            return DNDarray(
                y, tuple(y.shape), types.canonical_heat_type(y.dtype),
                split if split is not None and split < y.ndim else None,
                x.device, x.comm, True,
            )
        return y

    __call__ = forward

    # -- fused train step ----------------------------------------------- #
    def make_train_step(self, loss_fn: Callable, with_rng: bool = False,
                        donate: bool = True, overlap_sync=None,
                        grad_bucket_bytes=None, sync_domains=None, stats=None, forward=None):
        """Build a jitted (params, opt_state, x, y[, key]) →
        (params, opt_state, loss) step.  The batch arrives sharded; the mean
        loss over the GLOBAL batch makes XLA emit the gradient psum (the
        reference's Iallreduce hooks).

        ``with_rng=True`` adds a PRNG-key argument, required for stochastic
        layers (Dropout) — without it, a Dropout layer raises so that
        regularization can never be silently inactive during training.

        The optimizer's non-finite guard (``guard_nonfinite=True``, the
        default) is compiled INTO this step: a NaN/Inf gradient makes the
        jitted program keep params and optimizer state unchanged and bump
        the device-resident skip counter — no host sync, no poisoned model;
        inspect via ``optimizer.guard_stats(opt_state)``.

        ``donate=True`` (default) donates params and opt_state to the step:
        XLA aliases the updated state onto the incoming buffers, so training
        holds ONE copy of the model state instead of two.  The train loop
        must rebind — ``params, state, l = step(params, state, x, y)`` — and
        anything still pointing at the pre-step tree (e.g. this wrapper's
        ``.parameters`` from ``init()``) is consumed; reassign
        ``dp.parameters = params`` before calling ``forward`` again.

        ``overlap_sync`` (default: the optimizer's ``overlap_sync`` flag)
        opts into the bucketed hierarchical gradient sync
        (``core.collectives``): per-shard gradients are computed explicitly,
        mean-allreduced in byte-budgeted buckets (``grad_bucket_bytes`` /
        ``ht.set_grad_bucket_budget`` / ``HEAT_TPU_GRAD_BUCKET_BYTES``) with
        bucket k+1's collective in flight while bucket k is consumed, then
        applied by a donated update program.  ``sync_domains`` overrides the
        topology-derived slow-domain count.  The default (``False``) keeps
        today's single-program path bit-exact; the overlapped step has no
        ``.lower`` (it is three programs, not one).

        ``stats`` (a function ``(grads, aux, params, new_params, new_state)
        -> pytree``) makes the step report on itself without a second
        program: ``loss_fn`` then returns ``(loss, aux)`` (``aux``: whatever
        the forward pass counted, such as an expert layer's routed rows), and
        the step returns a fourth value, what ``stats`` makes of the
        gradients, of the parameters before and after the update and of the
        optimizer's new state, computed on the device in the same program
        (norms by parameter group of the gradient, of the step taken and of
        Adam's moments, say).  The fused path only.

        ``forward`` (a method of the module with ``apply``'s arguments) is
        called in ``apply``'s place: a model that computes its loss with its
        head (``PatternLM.next_token_loss``, which never holds the logits)
        returns from it what ``loss_fn`` then takes as its first argument.
        The fused path only.
        """
        if self.optimizer is None:
            raise RuntimeError("make_train_step requires an attached optimizer")
        import functools

        if overlap_sync is None:
            overlap_sync = getattr(self.optimizer, "overlap_sync", False)
        if grad_bucket_bytes is None:
            grad_bucket_bytes = getattr(self.optimizer, "grad_bucket_bytes", None)
        if overlap_sync and (stats is not None or forward is not None):
            raise ValueError("stats= and forward= are hooks of the one-program step: not with overlap_sync")
        if overlap_sync:
            return self._make_overlapped_step(
                loss_fn, with_rng, donate, grad_bucket_bytes, sync_domains
            )

        _jit = functools.partial(jax.jit, donate_argnums=(0, 1) if donate else ())
        apply = self.module.apply if forward is None else forward
        opt = self.optimizer

        from .modules import _module_accepts_train

        accepts_train = forward is not None or _module_accepts_train(self.module)

        if accepts_train:

            def _forward(p, jx, key):
                return apply(p, jx, train=True, key=key)

        else:

            def _forward(p, jx, key):
                return apply(p, jx)  # flax-style apply without train/key kwargs

        def _step(params, opt_state, jx, jy, key=None):
            def loss(p):
                out = loss_fn(_forward(p, jx, key), jy)
                return out if stats is not None else (out, None)

            (lval, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
            with jax.named_scope("ht.optim.update"):
                new_params, new_state = opt._update(params, grads, opt_state)
            if stats is None:
                return new_params, new_state, lval
            return new_params, new_state, lval, stats(grads, aux, params, new_params, new_state)

        if with_rng:

            @_jit
            def step(params, opt_state, jx, jy, key):
                return _step(params, opt_state, jx, jy, key)

        else:

            @_jit
            def step(params, opt_state, jx, jy):
                return _step(params, opt_state, jx, jy)

        step = _instrumented_step(step)
        self._train_step = step
        return step

    def _make_overlapped_step(self, loss_fn, with_rng, donate,
                              grad_bucket_bytes, sync_domains):
        """The opt-in bucketed path: (1) a shard_map program computes each
        shard's loss and gradient explicitly (stacked over the batch axis),
        (2) ``core.collectives.bucketed_grad_allreduce`` mean-reduces the
        stack in byte-budgeted buckets — two-level hierarchical stages,
        bucket k+1 in flight while bucket k is awaited, every stage
        accounted through ``Communication._account_bytes`` — and (3) a
        donated update program applies the replicated mean.  Math matches
        the fused path (global-mean loss gradient) up to float reordering."""
        import functools

        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..core import collectives as _coll

        apply = self.module.apply
        opt = self.optimizer
        comm = self.comm
        ax, p, mesh = comm.axis, comm.size, comm.mesh

        from .modules import _module_accepts_train

        accepts_train = _module_accepts_train(self.module)

        def _forward(q, jx, key):
            if accepts_train:
                return apply(q, jx, train=True, key=key)
            return apply(q, jx)

        def _body(params, jx, jy, key):
            if key is not None:
                # one independent stream per shard — the fused path's
                # sharded-mask semantics, expressed explicitly
                key = jax.random.fold_in(key, lax.axis_index(ax))

            def loss(q):
                return loss_fn(_forward(q, jx, key), jy)

            lval, grads = jax.value_and_grad(loss)(params)
            # stack under P(ax): shard k contributes block k of the leading
            # axis — the global mean of these IS the fused path's gradient
            return lval[None], jax.tree.map(lambda g: g[None], grads)

        in_specs = (P(), P(ax), P(ax)) + ((P(),) if with_rng else ())
        fn = (
            (lambda q, jx, jy, key: _body(q, jx, jy, key))
            if with_rng
            else (lambda q, jx, jy: _body(q, jx, jy, None))
        )
        # params NOT donated here — the update program reads them again
        grad_prog = jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs,
                out_specs=(P(ax), P(ax)), check_vma=False,
            )
        )
        update_prog = jax.jit(
            opt._update, donate_argnums=(0, 2) if donate else ()
        )
        state = {}  # bucket plan, computed once from the first params tree

        def _plan_for(params):
            if "plan" not in state:
                # grads stack one block per shard: plan over the STACKED
                # payload (p × param bytes), the transient the ledger sees
                state["plan"] = _coll.plan_grad_buckets(
                    [p * a.nbytes for a in jax.tree_util.tree_leaves(params)],
                    grad_bucket_bytes,
                )
            return state["plan"]

        def raw_step(params, opt_state, jx, jy, key=None):
            if jx.shape[0] % p:
                raise ValueError(
                    f"global batch {jx.shape[0]} must be divisible by the "
                    f"data-parallel world size {p} (overlap_sync shards the "
                    "batch explicitly)"
                )
            args = (params, jx, jy) + ((key,) if with_rng else ())
            losses, grads = grad_prog(*args)
            mean_grads = _coll.bucketed_grad_allreduce(
                comm, grads, plan=_plan_for(params), domains=sync_domains
            )
            new_params, new_state = update_prog(params, mean_grads, opt_state)
            return new_params, new_state, jnp.mean(losses)

        step = _instrumented_step(
            raw_step,
            sync=lambda: (
                "bucketed" if state and state["plan"].n_buckets > 1 else "monolithic"
            ),
        )
        self._train_step = step
        return step


class DataParallelMultiGPU(DataParallel):
    """Reference parity alias: the NCCL-node-group variant.  On TPU the
    hierarchy is expressed by the mesh itself (see ``optim.DASO``)."""

    def __init__(self, module: Module, optimizer=None, comm: Optional[Communication] = None):
        super().__init__(module, comm=comm, optimizer=optimizer)
