"""The sorted expert layer's buffers (``nn/moe.py``): they have a working
size, twice the rows expected of the experts held, and the layer's one body
runs as many passes over them as the rows its routing counted need, up to the
hard size.  On the CPU at toy widths, a quarter of the experts held: one pass
and two against the body at the hard size, the boundary, skew, the hard size
itself, the programs without a loop, and what reverse mode through a
checkpointed block may write."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.nn import moe as moe_module
from heat_tpu.nn.models import PatternLM
from heat_tpu.nn.moe import MoE

D, H, E, K, TOKENS = 24, 12, 8, 2, 1536
HELD = range(2, 4)
SLOTS, WORKING = TOKENS * K, 2048  # twice the 768 rows expected of two experts of eight, to the next 1,024

KINDS = {
    "sigmoid_bias_shared": dict(gated=True, scoring="sigmoid", expert_bias=True, routed_scaling=2.0, shared_dim=16),
    "softmax_relu": dict(gated=True, activation="relu"),
    "gelu_biases": dict(),
}


def layer(kind="sigmoid_bias_shared", held=HELD, rows_bound=None):
    return MoE(D, E, hidden_dim=H, top_k=K, dispatch="sorted", experts_held=held, rows_bound=rows_bound,
               **KINDS[kind])


def parameters(moe, toward_held=0.0):
    """The layer's own draw with biases away from 0; ``toward_held`` added to the
    router's columns of the experts held sends that much more to them."""
    p = moe.init(jax.random.key(3))
    p = {k: 0.1 + v if k in ("b1", "b2") else v for k, v in p.items()}
    lo, hi = moe.experts_held
    return {**p, "router": 4.0 * p["router"].at[:, lo:hi].add(toward_held)}


def inputs():
    return jnp.abs(jax.random.normal(jax.random.key(4), (TOKENS, D)))


def value_stats_grads(moe, p, x, r=None):
    def f(p, x):
        y, stats = moe.apply_with_stats(p, x, router_input=r)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=jnp.float32).reshape(y.shape))), (y, stats)

    with jax.default_matmul_precision("highest"):
        (_, (y, stats)), grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(p, x)
    return y, stats, grads


@pytest.fixture
def one_size(monkeypatch):
    """After it every layer's buffers have the hard size: one pass, no loop."""
    def enter():
        monkeypatch.setattr(moe_module, "_ROWS_MULTIPLE", 1 << 30)
    return enter


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1e-30)


def test_the_sizes_are_twice_the_expectation_and_the_hard_one():
    assert layer()._buffer_rows(SLOTS) == (WORKING, SLOTS)
    assert layer(rows_bound=2560)._buffer_rows(SLOTS) == (WORKING, 2560)
    assert layer(rows_bound=10 * SLOTS)._buffer_rows(SLOTS) == (WORKING, SLOTS)
    # the cells: LFM2 4 x 8,192 tokens, 4 a token, 8 of 32; SmallThinker 16,384, 6, 16 of 64; Kimi 16,384, 8, 8 of 256
    assert MoE(8, 32, top_k=4, dispatch="sorted", experts_held=range(8))._buffer_rows(131072) == (65536, 131072)
    assert MoE(8, 64, top_k=6, dispatch="sorted", experts_held=range(16),
               rows_bound=98304)._buffer_rows(98304) == (49152, 98304)
    assert MoE(8, 256, top_k=8, dispatch="sorted", experts_held=range(8),
               rows_bound=16384)._buffer_rows(131072) == (8192, 16384)
    # nothing to choose: every expert held, a bound under the working size, toy shapes
    assert layer(held=range(E))._buffer_rows(SLOTS) == (SLOTS, SLOTS)
    assert layer(rows_bound=1000)._buffer_rows(SLOTS) == (1000, 1000)
    assert layer()._buffer_rows(160) == (160, 160)


@pytest.mark.parametrize("rows_bound", [None, 2560])
@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_one_pass_or_two_are_the_body_at_the_hard_size(kind, passes, rows_bound, one_size):
    moe = layer(kind, rows_bound=rows_bound)
    full = rows_bound or SLOTS
    p, x = parameters(moe, toward_held=0.0 if passes == 1 else 0.04), inputs()
    r = x[::-1] if kind == "softmax_relu" else None  # the router on another tensor than the experts
    y, stats, grads = value_stats_grads(moe, p, x, r)
    held_rows = int(stats["rows"].sum())
    assert (held_rows <= WORKING) == (passes == 1) and held_rows <= full  # the routing this case is for
    assert int(stats["buffer_rows"]) == passes * WORKING
    assert int(stats["dropped"]) == 0
    one_size()
    want, want_stats, want_grads = value_stats_grads(moe, p, x, r)
    assert int(want_stats["buffer_rows"]) == full
    close(y, want, 1e-6)
    np.testing.assert_array_equal(stats["rows"], want_stats["rows"])
    assert int(want_stats["dropped"]) == 0
    jax.tree.map(lambda a, b: close(a, b, 1e-5), grads, want_grads)
    moved = {jax.tree_util.keystr(path): float(jnp.max(jnp.abs(g)))
             for path, g in jax.tree_util.tree_leaves_with_path(grads)}
    assert all(size > 0 for name, size in moved.items() if "expert_bias" not in name), moved


def _routing_with(held_rows):
    """``(parameters, x, router_input)`` under which exactly ``held_rows`` token-
    slots go to the two experts held: the router reads a tensor of its own, in
    which a token names its two experts."""
    moe = layer("softmax_relu")
    p = parameters(moe)
    p = {**p, "router": jnp.zeros((D, E)).at[jnp.arange(E), jnp.arange(E)].set(1.0)}
    both, one = divmod(held_rows, 2)
    first = jnp.where(jnp.arange(TOKENS) < both + one, HELD[0], 5)
    second = jnp.where(jnp.arange(TOKENS) < both, HELD[1], 6)
    r = jnp.zeros((TOKENS, D)).at[jnp.arange(TOKENS), first].set(10.0).at[jnp.arange(TOKENS), second].set(9.0)
    return moe, p, inputs(), r


@pytest.mark.parametrize("held_rows", [WORKING, WORKING + 1], ids=["fits_exactly", "one_row_over"])
def test_the_boundary(held_rows, one_size):
    moe, p, x, r = _routing_with(held_rows)
    y, stats, grads = value_stats_grads(moe, p, x, r)
    assert int(stats["rows"].sum()) == held_rows and int(stats["dropped"]) == 0
    assert int(stats["buffer_rows"]) == (WORKING if held_rows == WORKING else 2 * WORKING)
    one_size()
    want, _, want_grads = value_stats_grads(moe, p, x, r)
    close(y, want, 1e-6)
    jax.tree.map(lambda a, b: close(a, b, 1e-5), grads, want_grads)


def _every_expert_by_hand(moe, p, x):
    """What the experts held add, every expert over every token, weighted by
    the router's own choice (0 where it chose another)."""
    val, idx = moe._route(p, x)
    lo, hi = moe.experts_held
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        gate = jnp.sum(jnp.where(idx == e, val, 0.0), axis=-1)
        h = jax.nn.silu(x @ p["w1"][e - lo]) * (x @ p["w3"][e - lo])
        y = y + gate[:, None] * (h @ p["w2"][e - lo])
    return y


@pytest.mark.parametrize("rows_bound", [None, SLOTS], ids=["unbounded", "a_row_a_slot"])
def test_no_token_is_dropped_under_skew_past_the_working_size(rows_bound):
    moe = MoE(D, E, hidden_dim=H, top_k=K, dispatch="sorted", experts_held=HELD, rows_bound=rows_bound,
              gated=True, scoring="sigmoid")
    p = moe.init(jax.random.key(3))
    # every token's two choices are the two experts held: all 3,072 slots, half as many again as the buffers hold
    p = {**p, "router": jnp.zeros((D, E)).at[:, HELD[0]].set(1.0).at[:, HELD[1]].set(0.9)}
    x = inputs()
    with jax.default_matmul_precision("highest"):
        y, stats = jax.jit(moe.apply_with_stats)(p, x)
        want = _every_expert_by_hand(moe, p, x)
    assert list(np.asarray(stats["rows"])) == [TOKENS, TOKENS]
    assert int(stats["dropped"]) == 0 and int(stats["buffer_rows"]) == 2 * WORKING
    close(y, want, 1e-5)


@pytest.mark.parametrize("rows_bound", [2560, WORKING + 1, SLOTS - 3])
def test_the_hard_size_still_drops_the_rows_past_it_and_counts_them(rows_bound, one_size):
    """Two passes reach no further than ``rows_bound``: the second takes what
    is left under it."""
    moe, p, x, r = _routing_with(SLOTS - 2)  # all but one token to the experts held
    y, stats, grads = value_stats_grads(layer("softmax_relu", rows_bound=rows_bound), p, x, r)
    assert int(stats["rows"].sum()) == SLOTS - 2 and int(stats["buffer_rows"]) == 2 * WORKING
    assert int(stats["dropped"]) == SLOTS - 2 - rows_bound
    one_size()
    want, want_stats, want_grads = value_stats_grads(layer("softmax_relu", rows_bound=rows_bound), p, x, r)
    assert int(want_stats["dropped"]) == SLOTS - 2 - rows_bound and int(want_stats["buffer_rows"]) == rows_bound
    close(y, want, 1e-6)
    jax.tree.map(lambda a, b: close(a, b, 1e-5), grads, want_grads)
    everything, _, _ = value_stats_grads(moe, p, x, r)
    assert float(jnp.max(jnp.abs(everything - y))) > 1e-3  # the dropped rows are missed


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _loops(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _loops(sub)


def _written(jaxpr):
    """The shapes of everything a jaxpr computes, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape"))
        for sub in _sub_jaxprs(eqn):
            yield from _written(sub)


def _buffers(shapes, rows):
    """The shapes that are ``rows`` rows of a model or an expert width."""
    return {s for s in shapes if len(s) == 2 and s[0] == rows and s[1] in (D, H)}


def _grad_jaxpr(moe, x=None):
    p = parameters(moe)
    x = inputs() if x is None else x
    return jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe.apply(p, x) ** 2), argnums=(0, 1)))(p, x).jaxpr


@pytest.mark.parametrize("case", ["every_expert_held", "bound_at_the_working_size", "bound_under_it", "toy_shapes"])
def test_nothing_to_choose_is_no_loop(case):
    moe = {"every_expert_held": layer(held=range(E)), "bound_at_the_working_size": layer(rows_bound=WORKING),
           "bound_under_it": layer(rows_bound=1000), "toy_shapes": layer()}[case]
    x = inputs()[:80] if case == "toy_shapes" else inputs()
    jaxpr = _grad_jaxpr(moe, x)
    assert not list(_loops(jaxpr)) and "custom_vjp" not in str(jaxpr)
    _, stats = moe.apply_with_stats(parameters(moe), x)
    assert int(stats["buffer_rows"]) == moe._buffer_rows(x.shape[0] * K)[1]


def test_a_quarter_held_is_one_loop_forward_and_one_backward():
    assert len(list(_loops(_grad_jaxpr(layer())))) == 2
    forward = jax.make_jaxpr(layer().apply)(parameters(layer()), inputs()).jaxpr
    assert len(list(_loops(forward))) == 1


@pytest.mark.parametrize("rows_bound", [None, 2560], ids=["unbounded", "bounded"])
def test_a_checkpointed_block_writes_no_buffer_of_more_than_the_working_rows(rows_bound):
    """Where the rows fit one pass, nothing of the hard size is written in the
    forward pass, the recomputed one or the backward pass: a second compiled
    size behind a ``cond`` would have reverse mode zero-fill its residuals in
    the branch not taken, gigabytes a layer under ``PatternLM``'s block
    checkpoint."""
    full = rows_bound or SLOTS
    model = PatternLM(96, D, ["conv", "conv"], num_heads=2, num_kv_heads=1, ffn_dim=40, num_dense_layers=1,
                      num_experts=E, experts_per_token=K, expert_dim=H, experts_held=HELD,
                      shared_expert_dim=16, expert_rows_bound=rows_bound)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, TOKENS // 2), 0, 96)

    def loss(params):
        logits, _ = model.apply(params, tokens, train=True)  # train: every block under jax.checkpoint
        return jnp.mean(logits ** 2)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr
    loops = list(_loops(jaxpr))
    assert len(loops) == 2  # forward and backward; the recomputed forward's result is dead
    for eqn in loops:
        assert _buffers(_written(eqn.params["body_jaxpr"].jaxpr), WORKING)
    written = set(_written(jaxpr))
    assert not _buffers(written, full) and not _buffers(written, SLOTS), _buffers(written, full)
