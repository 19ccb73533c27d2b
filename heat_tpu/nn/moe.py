"""Mixture-of-Experts layer with expert parallelism.

Beyond-reference model family (the reference has no MoE or expert
parallelism — SURVEY §2.8 lists EP as absent).  One layer, two ways to bring
tokens to experts (``dispatch=``):

``"capacity"`` (the default, and what ``comm=`` shards): the token→expert
dispatch and combine are dense einsums over a capacity-bounded
``(experts, capacity, d)`` buffer (static shapes, so the whole layer jits
and rides the MXU), and with ``comm=`` the experts are sharded over the
mesh while tokens travel through TWO ``all_to_all`` collectives — the
canonical expert-parallel data movement on ICI.  Capacity positions are
claimed slot-major (all first choices before any second choice, tokens in
order within a slot) and a token that overflows an expert's capacity is
dropped from that expert (contributing zero — the standard GShard/Switch
overflow semantics).  Its ``(tokens, experts, capacity)`` tensors grow with
the square of the token count, so it is the path of small batches.

``"sorted"``: no capacity and no drop, whatever the loads.  The
``tokens x top_k`` token-slots are sorted by expert, the experts run as
grouped matrix products over the sorted rows (``jax.lax.ragged_dot``: each
expert multiplies exactly the rows routed to it), and the results are
added into their tokens' rows, weighted.  Memory and work are linear in
the tokens.  This path also takes ``experts_held``: the range of expert ids
whose weights live here (one rank's share of an expert-parallel layer).
The router still scores every expert and picks ``top_k`` of them; only the
held experts have parameters, and what the others would add is left out of
the result.  It runs on one chip without any exchange (``comm`` must be
``None``): the exchange of a multi-chip no-drop layer is not built yet.
This path's buffers have a working size that the layer picks itself: twice
the rows expected of the experts held (``2 x tokens x top_k x held /
num_experts``, to the next 1,024), although a rank that holds 8 of 32 experts
is sent a quarter of the token-slots.  The step counts the rows it routed
before it gathers one, and its one body (gather the next rows in expert
order, grouped products, weighted scatter-add into the tokens' rows) runs as
many passes over those buffers as the count needs, in a loop on the device:
one on a balanced step, more under skew, so no routing costs a row of a held
expert.  ``rows_bound`` is the hard size, by default a row for every
token-slot (``tokens x top_k``): a slot of a held expert past it is left out
of the result and counted in ``stats["dropped"]`` (a bound to size generously
and to watch, not a capacity factor).  ``stats["buffer_rows"]`` says how many
rows of buffer the call moved (passes times the working size).  Where the
working size is no less than the hard one (every expert held, a ``rows_bound``
under it, toy shapes) there is one pass of the hard size and no loop.
``shared_dim`` adds a gated FFN of that width that every
token goes through, beside the routed experts (a shared expert).

Routing is token-choice top-k.  ``scoring="softmax"`` renormalizes the
selected probabilities by their sum; ``scoring="sigmoid"`` scores each
expert independently, selects on ``score + expert_bias`` (``expert_bias=
True``: a buffer in the parameters that no optimizer updates), and weights
by the unbiased scores, renormalized (``norm_topk=True``) and scaled by
``routed_scaling``.  ``gated=True`` makes every expert a gated-linear unit,
``w2 (act(w1 x) * w3 x)``, without biases, ``act`` being ``activation``:
``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU).  The router scores the experts'
own input unless ``apply_with_stats`` is handed another tensor for it
(``router_input``: a layer that routes on its input, ahead of its
attention).  Routing is deterministic: no jitter noise, so eval == train and
results are reproducible across device counts.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from .modules import Module, SwiGLU
from ..core._cache import comm_cached

__all__ = ["MoE"]


@comm_cached(key=lambda moe: moe._program_key)
def _ep_program(comm, moe):
    """Compiled expert-parallel forward, cached ON the comm, keyed on the
    layer's *config tuple* (``MoE._program_key``) rather than its identity:
    the trace of ``_ep_fn`` depends only on that config (+ the comm, which
    owns the table), so identical-config layers share one executable and
    per-instance retention shrinks to one config representative (the first
    instance's bound method inside the compiled program; ADVICE r4).  jit's
    own cache handles shape/dtype variation.

    Token sharding: over the expert axis itself by default; with
    ``moe.batch_axis`` set the tokens shard over BOTH axes jointly (dp x ep)
    — within each dp slice this reduces to the pure-ep path over that
    slice's token shard, so there is no replicated expert compute, while
    the expert weights stay sharded over ep only (replicated over dp;
    their gradients psum over dp under GSPMD exactly like any replicated
    parameter)."""
    from jax.sharding import PartitionSpec as P

    tok = P((moe.batch_axis, comm.axis)) if moe.batch_axis else P(comm.axis)
    fn = comm.shard_map(
        moe._ep_fn,
        in_splits=(
            {"router": (2, None), "w1": (3, 0), "b1": (2, 0), "w2": (3, 0), "b2": (2, 0)},
            tok,
            tok,
        ),
        out_splits=tok,
    )
    return jax.jit(fn)


def _topk_gates(gates, top_k: int):
    """Top-k expert selection with sum-renormalized gate weights — THE
    routing rule, shared by the capacity path (:func:`_routing`) and the
    drop-free decode path (:meth:`MoE.decode_apply`) so the
    decode == teacher-forced contract can never drift between them."""
    val, idx = jax.lax.top_k(gates, top_k)  # (n, k)
    return val / (val.sum(axis=-1, keepdims=True) + 1e-9), idx


# the working size of the sorted path's buffers is a multiple of this many rows
_ROWS_MULTIPLE = 1024


def _in_passes(part, passes, shape, operands, indices):
    """Zeros of ``shape`` after ``out = part(c, out, *operands, *indices)`` for
    ``c`` in ``range(passes)``, ``passes`` a device scalar: a ``while`` loop on
    the device, which reverse mode cannot go through by itself.  ``part`` adds
    into ``out`` and is linear in it, so the reverse is a loop of its own:
    pass by pass it computes the part again and adds what that part gives
    the operands (under a block's ``jax.checkpoint`` this is the one
    recomputation the block makes anyway: the recomputed forward's result is
    dead).  Nothing is kept between forward and backward but the inputs."""

    def loop(passes, indices, operands):
        return jax.lax.fori_loop(0, passes, lambda c, out: part(c, out, *operands, *indices),
                                 jnp.zeros(shape, jnp.float32))

    def forward(passes, indices, operands):
        return loop(passes, indices, operands), (passes, indices, operands)

    def backward(saved, g):
        passes, indices, operands = saved

        def add_pass(c, sums):
            (given,) = jax.vjp(lambda o: part(c, jnp.zeros(shape, jnp.float32), *o, *indices), operands)[1](g)
            with jax.named_scope("ht.moe.dispatch"):
                return jax.tree.map(jnp.add, sums, given)

        return None, None, jax.lax.fori_loop(0, passes, add_pass, jax.tree.map(jnp.zeros_like, operands))

    run = jax.custom_vjp(loop)
    run.defvjp(forward, backward)
    return run(passes, indices, operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _sorted_rows(m: int, full: int, top_k: int, gated: bool, activation: str,
                 c, y, weights, x2d, val, order, routed):
    """Pass ``c`` of the sorted path's body over buffers of ``m`` rows: the
    token-slots at places ``c m`` to ``(c + 1) m`` of the expert order
    (``order``; ``val`` holds a weight a slot) are gathered, computed and
    added into their tokens' rows of ``y``, weighted; places past the held
    slots, or past ``full``, add nothing.  A jitted function of its sizes and
    the experts' kind, not of a layer: a step traces the body several times a
    layer (forward, the reverse mode's forward and backward), and layers of
    one shape share one trace."""
    with jax.named_scope("ht.moe.dispatch"):
        first, last = c * m, jnp.minimum((c + 1) * m, full)
        take = jax.lax.dynamic_slice_in_dim(order, first, m)
        before = jnp.cumsum(routed) - routed  # where each expert's slots start in the order
        # what this pass takes of each expert: its slots before ``last`` less those before ``first``
        rows = jnp.clip(last - before, 0, routed) - jnp.clip(first - before, 0, routed)
        in_group = jnp.arange(m) < jnp.sum(rows)
        token = take // top_k
        # a grouped product leaves the rows past its groups undefined
        xs = jnp.where(in_group[:, None], x2d[token], 0)
    with jax.named_scope("ht.moe.experts"):
        ys = _experts_grouped(gated, activation, weights, xs, rows, in_group)
    with jax.named_scope("ht.moe.combine"):
        weight = jnp.where(in_group, val[take], 0).astype(jnp.float32)
        return y.at[token].add(weight[:, None] * ys.astype(jnp.float32))


def _experts_grouped(gated: bool, activation: str, weights, xs, rows, in_group):
    """The held experts over rows sorted by expert, ``rows[e]`` of them for
    expert ``e``; rows past the groups come out 0."""
    dt, n_rows = xs.dtype, xs.shape[0]
    h = jax.lax.ragged_dot(xs, weights["w1"].astype(dt), rows)
    if gated:
        act = jax.nn.silu if activation == "silu" else jax.nn.relu
        h = act(h) * jax.lax.ragged_dot(xs, weights["w3"].astype(dt), rows)
    else:
        h = jax.nn.gelu(h + jnp.repeat(weights["b1"].astype(dt), rows, axis=0, total_repeat_length=n_rows))
    ys = jax.lax.ragged_dot(h, weights["w2"].astype(dt), rows)
    if not gated:
        ys = ys + jnp.repeat(weights["b2"].astype(dt), rows, axis=0, total_repeat_length=n_rows)
    return jnp.where(in_group[:, None], ys, 0)


def _routing(gates, top_k: int, capacity: int):
    """Dispatch/combine tensors for token-choice top-k routing.

    gates: (n, E) softmax router probabilities.
    Returns ``dispatch`` (n, E, C) in {0,1} and ``combine`` (n, E, C)
    carrying the renormalized gate weight at each token's claimed slot.

    Capacity positions are claimed slot-major — every token's first choice
    is ranked before any token's second choice — so dropping under pressure
    removes the *weakest* assignments first.
    """
    n, E = gates.shape
    val, idx = _topk_gates(gates, top_k)

    # slot-major priority: position of (token i, slot j) in its expert's
    # capacity queue counts all slot-<j claims plus earlier tokens' slot-j
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (n, k, E)
    # claims in priority order: reshape (k, n, E) then cumulative count.
    # zero-gate selections (masked pad tokens) must not occupy queue
    # positions, or a pad's phantom slot-0 claim evicts real tokens under
    # capacity pressure — mask them out of the queue entirely
    claims = jnp.moveaxis(onehot, 1, 0)  # (k, n, E)
    claims = claims * (jnp.moveaxis(val, 1, 0) > 0)[..., None]
    flat = claims.reshape(top_k * n, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat  # claims STRICTLY before ours
    pos = (pos_flat * flat).sum(axis=1).reshape(top_k, n)  # (k, n) queue position
    keep = (pos < capacity) & (jnp.moveaxis(val, 1, 0) > 0)

    pos = jnp.where(keep, pos, capacity)  # parked on an out-of-range slot
    slot = jax.nn.one_hot(pos, capacity, dtype=gates.dtype)  # (k, n, C)
    expert = jnp.moveaxis(onehot, 1, 0).astype(gates.dtype)  # (k, n, E)
    # (k,n,E,C) products collapsed over slots
    dispatch = jnp.einsum("kne,knc->nec", expert, slot)
    combine = jnp.einsum("kn,kne,knc->nec", jnp.moveaxis(val, 1, 0), expert, slot)
    return dispatch, combine


class MoE(Module):
    """Token-choice top-k mixture of FFN experts.

    ``apply(params, x)`` with x (B, S, D) or (N, D).  Each expert is a
    two-layer GELU FFN (D → hidden → D) with its own weights; a linear
    router picks ``top_k`` experts per token.

    With ``comm=`` the expert dimension is sharded over the communicator's
    mesh axis (``num_experts % comm.size == 0``): each device routes its
    resident tokens, ships the per-expert buffers to the expert owners with
    one ``all_to_all``, applies its local experts, and ships results back
    with a second ``all_to_all`` — expert parallelism exactly as run on TPU
    pods, composing with the framework's data/sequence parallelism.  Tokens
    are sharded over the batch axis; a ragged batch is pad-and-masked (pad
    tokens carry zero gate weight, so they are never dispatched).

    ``capacity_factor`` scales each expert's token budget
    ``ceil(top_k * n_tokens / num_experts)``; overflow tokens contribute
    zero for that expert.  Under ``comm=`` the budget applies per source
    shard (the standard EP formulation — capacity is a *local* guarantee so
    the all_to_all buffers stay static-shaped).
    """

    def __init__(
        self,
        embed_dim: int,
        num_experts: int,
        hidden_dim: int | None = None,
        top_k: int = 2,
        capacity_factor: float = 1.5,
        comm=None,
        batch_axis: str | None = None,
        *,
        gated: bool = False,
        scoring: str = "softmax",
        expert_bias: bool = False,
        norm_topk: bool = True,
        routed_scaling: float = 1.0,
        dispatch: str = "capacity",
        experts_held=None,
        shared_dim: int | None = None,
        rows_bound: int | None = None,
        activation: str = "silu",
    ):
        if top_k < 1 or top_k > num_experts:
            raise ValueError(f"top_k {top_k} must be in [1, num_experts={num_experts}]")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
        if dispatch not in ("capacity", "sorted"):
            raise ValueError(f"dispatch must be 'capacity' or 'sorted', got {dispatch!r}")
        if activation not in ("silu", "relu") or (activation != "silu" and not gated):
            raise ValueError(f"activation must be 'silu' or 'relu' and gated experts' own, got {activation!r}")
        held = range(num_experts) if experts_held is None else experts_held
        if not isinstance(held, range) or held.step != 1 or not 0 <= held.start < held.stop <= num_experts:
            raise ValueError(f"experts_held {experts_held!r} is not a range of the {num_experts} experts")
        held = (held.start, held.stop)
        if dispatch == "capacity" and (held != (0, num_experts) or gated or scoring != "softmax"
                                       or expert_bias or shared_dim or rows_bound):
            raise ValueError("experts_held, gated experts, sigmoid/bias routing, a shared expert "
                             "and rows_bound need dispatch='sorted'")
        if rows_bound is not None and rows_bound < 1:
            raise ValueError(f"rows_bound {rows_bound} must be at least 1")
        if dispatch == "sorted" and comm is not None:
            raise ValueError("dispatch='sorted' runs on one chip: the no-drop exchange is not built")
        if batch_axis is not None:
            if comm is None:
                raise ValueError(
                    "batch_axis requires a communicator (it names one of its mesh axes)"
                )
            if batch_axis not in comm.mesh.axis_names or batch_axis == comm.axis:
                raise ValueError(
                    f"batch_axis {batch_axis!r} must name a mesh axis other "
                    f"than the expert axis {comm.axis!r}"
                )
        self.embed_dim = embed_dim
        self.num_experts = num_experts
        self.hidden_dim = hidden_dim or 4 * embed_dim
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.comm = comm
        self.batch_axis = batch_axis  # dp axis of a 2-D mesh (see _ep_program)
        self.gated = gated
        self.activation = activation  # of the gated experts' w1 branch
        self.scoring = scoring
        self.expert_bias = expert_bias
        self.norm_topk = norm_topk
        self.routed_scaling = routed_scaling
        self.dispatch = dispatch
        self.experts_held = held  # (first id, one past the last)
        self.shared = SwiGLU(embed_dim, shared_dim) if shared_dim else None
        self.rows_bound = rows_bound

    @property
    def _program_key(self):
        """Everything the ``_ep_fn`` trace depends on besides the comm and
        input shapes — the ``_ep_program`` cache key (see its docstring)."""
        return (type(self), self.embed_dim, self.num_experts, self.hidden_dim,
                self.top_k, self.capacity_factor, self.batch_axis)

    def init(self, key):
        D, H, E = self.embed_dim, self.hidden_dim, self.num_experts
        kr, k1, k2 = jax.random.split(key, 3)
        bound1 = 1.0 / jnp.sqrt(D)
        bound2 = 1.0 / jnp.sqrt(H)
        if self.dispatch == "sorted":
            k1, k3 = jax.random.split(k1)
            held = self.experts_held[1] - self.experts_held[0]
            out = {
                "router": jax.random.uniform(kr, (D, E), minval=-bound1, maxval=bound1),
                "w1": jax.random.uniform(k1, (held, D, H), minval=-bound1, maxval=bound1),
                "w2": jax.random.uniform(k2, (held, H, D), minval=-bound2, maxval=bound2),
            }
            if self.gated:
                out["w3"] = jax.random.uniform(k3, (held, D, H), minval=-bound1, maxval=bound1)
            else:
                out.update(b1=jnp.zeros((held, H)), b2=jnp.zeros((held, D)))
            if self.expert_bias:
                out["expert_bias"] = jnp.zeros((E,))
            if self.shared is not None:
                out["shared"] = self.shared.init(jax.random.fold_in(key, 1))
            return out
        return {
            "router": jax.random.uniform(kr, (D, E), minval=-bound1, maxval=bound1),
            "w1": jax.random.uniform(k1, (E, D, H), minval=-bound1, maxval=bound1),
            "b1": jnp.zeros((E, H)),
            "w2": jax.random.uniform(k2, (E, H, D), minval=-bound2, maxval=bound2),
            "b2": jnp.zeros((E, D)),
        }

    # ------------------------------------------------------------------ #

    def _capacity(self, n_tokens: int) -> int:
        import math

        return max(1, math.ceil(self.top_k * n_tokens / self.num_experts * self.capacity_factor))

    def _experts(self, params, buf):
        """Apply the (possibly local-shard) stacked experts to (e, C, D)."""
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", buf, params["w1"]) + params["b1"][:, None, :])
        return jnp.einsum("ech,ehd->ecd", h, params["w2"]) + params["b2"][:, None, :]

    def _dense(self, params, x2d):
        """``(y, stats)`` of the capacity path on one chip; ``stats`` counts
        what the buffers took and what overflowed."""
        gates = jax.nn.softmax(x2d @ params["router"])
        dispatch, combine = _routing(gates, self.top_k, self._capacity(x2d.shape[0]))
        buf = jnp.einsum("nec,nd->ecd", dispatch, x2d)
        out = self._experts(params, buf)
        rows = jnp.sum(dispatch, axis=(0, 2)).astype(jnp.int32)
        stats = {"rows": rows, "dropped": x2d.shape[0] * self.top_k - jnp.sum(rows)}
        return jnp.einsum("nec,ecd->nd", combine, out), stats

    def _ep_fn(self, params, x_loc, mask_loc):
        """Per-shard body: local routing, all_to_all to expert owners,
        local expert FFNs, all_to_all back, local combine."""
        comm = self.comm
        n_loc = x_loc.shape[0]
        gates = jax.nn.softmax(x_loc @ params["router"]) * mask_loc[:, None]
        dispatch, combine = _routing(gates, self.top_k, self._capacity(n_loc))
        buf = jnp.einsum("nec,nd->ecd", dispatch, x_loc)  # (E, C, D)
        # ship: each owner receives its experts' buffers from every shard
        buf = comm.Alltoall(buf, split_axis=0, concat_axis=1)  # (E/p, C*p, D)
        out = self._experts(params, buf)
        out = comm.Alltoall(out, split_axis=1, concat_axis=0)  # (E, C, D)
        return jnp.einsum("nec,ecd->nd", combine, out)

    # ------------------------------------------------------------------ #
    # the sorted, drop-free path
    # ------------------------------------------------------------------ #

    def _route(self, params, x2d):
        """``(weights (n, k), expert ids (n, k))`` in float32 whatever the
        activations' dtype: a selection decided in bfloat16 differs from the
        float32 one wherever two scores lie within its rounding."""
        logits = jnp.matmul(x2d.astype(jnp.float32), params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            return _topk_gates(jax.nn.softmax(logits), self.top_k)
        scores = jax.nn.sigmoid(logits)
        biased = scores + jax.lax.stop_gradient(params["expert_bias"]) if self.expert_bias else scores
        _, idx = jax.lax.top_k(biased, self.top_k)
        val = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk:
            val = val / (val.sum(axis=-1, keepdims=True) + 1e-6)
        return val * self.routed_scaling, idx

    def _sorted(self, params, x2d, r2d=None):
        """``(y, stats)``: every token-slot whose expert is held goes through
        that expert, as far as the hard size reaches; nothing else is
        computed.  The router reads ``r2d`` where it is given, else the
        experts' input.  The buffers have the working size, and the body runs
        as many passes over them as the rows this call routed need."""
        n, k = x2d.shape[0], self.top_k
        with jax.named_scope("ht.moe.route"):
            val, idx = self._route(params, x2d if r2d is None else r2d)
        with jax.named_scope("ht.moe.dispatch"):
            group, routed = self._held_groups(idx)
            order = jnp.argsort(group, stable=True)  # held slots first, by expert
        working, full = self._buffer_rows(n * k)
        most = -(-full // working)  # passes a call can need
        # every pass slices ``working`` places of the order, the last one too
        order = jnp.pad(order, (0, max(0, most * working - n * k)))
        weights = {name: params[name] for name in ("w1", "w2", "w3", "b1", "b2") if name in params}
        operands, indices = (weights, x2d, val.reshape(-1)), (order, routed)
        part = functools.partial(_sorted_rows, working, full, k, self.gated, self.activation)
        taken = jnp.minimum(jnp.sum(routed), full)
        shape = (n, self.embed_dim)
        if most == 1:
            passes, y = 1, part(0, jnp.zeros(shape, jnp.float32), *operands, *indices)
        else:
            passes = (taken + working - 1) // working
            y = _in_passes(part, passes, shape, operands, indices)
        stats = {"rows": routed, "dropped": jnp.sum(routed) - taken,
                 "buffer_rows": jnp.asarray(passes * working, jnp.int32)}
        return self._add_shared(params, x2d, y.astype(x2d.dtype)), stats

    def _buffer_rows(self, slots: int):
        """``(working, full)`` for a call of ``slots`` token-slots.  ``full``
        is the hard size (``rows_bound``, or a row for every slot); ``working``,
        the rows of the buffers, is twice the rows expected of the experts held,
        rounded up to ``_ROWS_MULTIPLE``, and ``full`` where that is no less
        (every expert held, a ``rows_bound`` already under it, toy shapes)."""
        lo, hi = self.experts_held
        full = min(self.rows_bound or slots, slots)
        working = -(-2 * slots * (hi - lo) // (self.num_experts * _ROWS_MULTIPLE)) * _ROWS_MULTIPLE
        return min(working, full), full

    def _held_groups(self, idx):
        """Of every token-slot (slot ``i`` is token ``i // k``): its group
        (the held expert's place, the experts not held last), and the slots
        routed to each expert held."""
        lo, hi = self.experts_held
        flat = idx.reshape(-1)
        group = jnp.where((flat >= lo) & (flat < hi), flat - lo, hi - lo)
        rows = jnp.sum(group[:, None] == jnp.arange(hi - lo)[None, :], axis=0, dtype=jnp.int32)
        return group, rows

    def _add_shared(self, params, x2d, y):
        if self.shared is None:
            return y
        with jax.named_scope("ht.moe.shared"):
            return y + self.shared.apply(params["shared"], x2d)

    def apply_with_stats(self, params, x, router_input=None):
        """``(y, {"rows": rows routed to each expert held, "dropped": token-
        slots of held experts that no expert computed})``; the capacity path
        counts what its buffers took and what overflowed.  ``router_input``
        (of ``x``'s shape, the sorted path's) is what the router scores in
        ``x``'s place; the experts still compute on ``x``."""
        x2d = x.reshape(-1, self.embed_dim)
        if self.dispatch == "sorted":
            r2d = None if router_input is None else router_input.reshape(x2d.shape)
            y, stats = self._sorted(params, x2d, r2d)
            return y.reshape(x.shape), stats
        if router_input is not None:
            raise ValueError("router_input needs dispatch='sorted'")
        if self.comm is not None:
            raise ValueError("apply_with_stats counts on one chip: comm must be None")
        y, stats = self._dense(params, x2d)
        return y.reshape(x.shape), stats

    def apply(self, params, x, **kw):
        if self.dispatch == "sorted":
            return self.apply_with_stats(params, x)[0]
        orig_shape = x.shape
        x2d = x.reshape(-1, self.embed_dim)
        comm = self.comm
        # a (dp, ep=1) mesh still runs the EP program (the all_to_all is an
        # identity there) so the dp token sharding survives — only the
        # truly-unsharded case takes the dense shortcut
        if comm is None or (comm.size == 1 and self.batch_axis is None):
            return self._dense(params, x2d)[0].reshape(orig_shape)
        if self.num_experts % comm.size:
            warnings.warn(
                f"MoE: num_experts={self.num_experts} not divisible by mesh size "
                f"{comm.size}; running the dense (replicated-expert) path. "
                "This changes ROUTING NUMERICS, not just speed: capacity is "
                "budgeted over the global token pool instead of per source "
                "shard, so drop decisions (and therefore outputs) can differ "
                "from the expert-parallel path for the same config",
                stacklevel=2,
            )
            return self._dense(params, x2d)[0].reshape(orig_shape)

        # tokens shard over dp x ep jointly when batch_axis is given,
        # else over the expert axis alone
        p = comm.size * (comm.mesh.shape[self.batch_axis] if self.batch_axis else 1)
        n = x2d.shape[0]
        pad = (-n) % p
        mask = jnp.ones((n,), x2d.dtype)
        if pad:
            x2d = jnp.concatenate([x2d, jnp.zeros((pad, self.embed_dim), x2d.dtype)])
            mask = jnp.concatenate([mask, jnp.zeros((pad,), x2d.dtype)])

        y = _ep_program(comm, self)(params, x2d, mask)
        if pad:
            y = y[:n]
        return y.reshape(orig_shape)

    def decode_apply(self, params, x):
        """Drop-free per-token path for autoregressive decoding.

        Gathers each token's top-k experts' weights and applies them
        directly — no capacity buffer, so no token is ever dropped.  The
        capacity-bounded :meth:`apply` pools B·S training tokens while a
        decode step sees only B; under capacity pressure the two would
        disagree arbitrarily, so decoding uses this exact path instead
        (== :meth:`apply` whenever apply's capacity was not binding — the
        usual serving regime).  Cost is k gathered FFNs per token; with
        decode batches this is small and stays on the MXU.  The sorted
        dispatch never drops, so there decoding is :meth:`apply` itself.
        """
        if self.dispatch == "sorted":
            return self.apply(params, x)
        orig_shape = x.shape
        x2d = x.reshape(-1, self.embed_dim)
        gates = jax.nn.softmax(x2d @ params["router"])
        val, idx = _topk_gates(gates, self.top_k)  # (n, k)
        w1, b1 = params["w1"][idx], params["b1"][idx]  # (n, k, D, H), (n, k, H)
        w2, b2 = params["w2"][idx], params["b2"][idx]
        h = jax.nn.gelu(jnp.einsum("nd,nkdh->nkh", x2d, w1) + b1)
        y = jnp.einsum("nkh,nkhd->nkd", h, w2) + b2
        return jnp.einsum("nk,nkd->nd", val, y).reshape(orig_shape)

    # ------------------------------------------------------------------ #

    def load_balance_loss(self, params, x):
        """Switch-transformer auxiliary loss: ``E * Σ_e f_e · P_e`` where
        ``f_e`` is the fraction of tokens whose TOP choice is expert e and
        ``P_e`` the mean router probability — minimized (=1) by a uniform
        router.  Add ``coef * load_balance_loss`` to the training loss.
        Defined for softmax scores (sigmoid routing balances by its
        selection bias instead)."""
        if self.scoring != "softmax":
            raise ValueError("load_balance_loss is the softmax router's auxiliary loss")
        x2d = x.reshape(-1, self.embed_dim)
        gates = jax.nn.softmax(x2d @ params["router"])
        top1 = jnp.argmax(gates, axis=-1)
        f = jnp.mean(jax.nn.one_hot(top1, self.num_experts, dtype=gates.dtype), axis=0)
        P = jnp.mean(gates, axis=0)
        return self.num_experts * jnp.sum(f * P)
