"""``nn.models.PatternLM`` (gated short convolutions, QK-normed GQA, sigmoid-
routed drop-free experts) against the plain reference ``lfm2_moe_reference``
at a small size on the CPU: every layer kind, the whole model, loss and
gradients, one ``DataParallel`` AdamW step, the expert-parallel share, skewed
routing, causality, the selection bias as a buffer, and a lower-precision
control that the same tolerance must refuse."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
import lfm2_moe_reference as ref
from heat_tpu.nn.models import PatternLM
from heat_tpu.nn.moe import MoE
from heat_tpu.ops.short_conv import gated_short_conv

HERE = os.path.dirname(os.path.abspath(__file__))

CFG = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 96,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention", "conv"],
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2, "experts_held": [0, 8],
    "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1000000.0,
    "norm_topk_prob": True, "routed_scaling_factor": 1.0,
}
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
# float32 against float32 at ``highest`` precision: what is left is the order of
# the sums (1e-6 of a value); operands rounded to bfloat16 are off by 2**-9
TOL = 2e-4


def build(cfg=CFG, **kw):
    held = cfg["experts_held"]
    return PatternLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["layer_types"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"], experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"], experts_held=range(*held),
        conv_taps=cfg["conv_L_cache"], rope_base=cfg["rope_theta"], norm_eps=cfg["norm_eps"],
        bias_std=0.1, **kw)


@pytest.fixture(scope="module")
def setup():
    with jax.default_matmul_precision("highest"):
        model = build()
        params = model.init(jax.random.key(0))
        # norm weights away from 1, so that a norm applied wrongly shows
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
            if any(str(getattr(k, "key", "")).endswith("norm") for k in path) else a, params)
        tokens = jax.random.randint(jax.random.key(1), (3, 24), 0, CFG["vocab_size"])
        return model, params, tokens


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def loss_fn(out, tokens):
    logits, stats = out
    return ht.nn.losses.next_token_cross_entropy(logits, tokens), stats


# --------------------------------------------------------------------- #
# the initial parameters: the model's own draw and the reference's
# --------------------------------------------------------------------- #
WIDE = {**CFG, "hidden_size": 256, "vocab_size": 512, "num_attention_heads": 8, "experts_held": [2, 6]}


@pytest.mark.parametrize("who", ["model", "reference"])
def test_initial_parameters_are_the_stated_draws(who):
    """Every matrix N(0, 0.02^2), every norm weight 1, the selection bias
    N(0, 0.1^2), by name and shape the same tree from either side."""
    model = build(WIDE)
    params = (model.init(jax.random.key(7)) if who == "model"
              else ref.init_params(jax.random.key(7), WIDE, init_std=0.02, bias_std=0.1))
    shapes = jax.eval_shape(model.init, jax.random.key(7))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    biases, seen = [], set()
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        name, a = jax.tree_util.keystr(path), np.asarray(a, np.float64)
        if "norm" in name:
            assert np.all(a == 1.0), name
        elif "expert_bias" in name:
            biases.append(a)
        else:
            # the estimate of a standard deviation from n draws spreads by 1 / sqrt(2 n)
            room = 5.0 / np.sqrt(2 * a.size)
            assert abs(a.std() / 0.02 - 1.0) < room and abs(a.mean()) < 5 * 0.02 / np.sqrt(a.size), name
            assert a.tobytes() not in seen, f"{name} repeats another leaf's draw"
            seen.add(a.tobytes())
    biases = np.concatenate(biases)
    assert biases.size == 4 * 8 and 0.06 < biases.std() < 0.14


# --------------------------------------------------------------------- #
# each layer kind alone, then the whole model
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("layer", range(len(CFG["layer_types"])))
def test_each_block_matches_the_reference(setup, layer):
    model, params, _ = setup
    x = jax.random.normal(jax.random.key(7 + layer), (2, 24, CFG["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got, stats = model.blocks[layer].apply(params["blocks"][layer], x)
        want, rows = ref.block(params["blocks"][layer], x, CFG["layer_types"][layer], CFG)
    assert rel(got, want) < TOL
    assert (stats is None) == (rows is None) == (layer < CFG["num_dense_layers"])
    if rows is not None:
        np.testing.assert_array_equal(stats["rows"], rows)
        assert int(stats["dropped"]) == 0


def test_logits_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, stats = model.apply(params, tokens)
        want = ref.logits(params, tokens, CFG)
    assert got.shape == (3, 24, CFG["vocab_size"]) and len(stats) == 4
    assert rel(got, want) < TOL


def test_loss_matches_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, _ = loss_fn(model.apply(params, tokens), tokens)
        want, _ = ref.loss(params, tokens, CFG)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)


@pytest.fixture(scope="module")
def gradients(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: loss_fn(model.apply(p, tokens, train=True), tokens)[0])(params)
        _, _, want = ref.loss_and_grads(params, tokens, CFG)
    flat = lambda t: {jax.tree_util.keystr(p): a for p, a in jax.tree_util.tree_flatten_with_path(t)[0]}  # noqa: E731
    return flat(got), flat(want)


LEAVES = sorted(jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    jax.eval_shape(build().init, jax.random.key(0)))[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(gradients, leaf):
    got, want = gradients
    assert got[leaf].shape == want[leaf].shape
    if leaf.endswith("['expert_bias']"):
        assert not np.any(np.asarray(got[leaf])) and not np.any(np.asarray(want[leaf]))
    else:
        assert np.any(np.asarray(want[leaf])), "a leaf the loss does not reach proves nothing"
        assert rel(got[leaf], want[leaf]) < TOL


# --------------------------------------------------------------------- #
# one DataParallel AdamW step against the reference's step
# --------------------------------------------------------------------- #
def _train_one_step(setup):
    model, params, tokens = setup
    opt = ht.optim.DataParallelOptimizer(ht.optim.AdamW(
        lr=ADAMW["lr"], betas=(ADAMW["b1"], ADAMW["b2"]), eps=ADAMW["eps"],
        weight_decay=ADAMW["weight_decay"], mask=model.decay_mask))
    dp = ht.nn.DataParallel(model, optimizer=opt)
    dp.parameters = jax.tree.map(jnp.array, params)
    step = dp.make_train_step(loss_fn, stats=lambda grads, aux, *update: (ref.group_norms(grads), aux))
    with jax.default_matmul_precision("highest"):
        return step(dp.parameters, opt.init_state(dp.parameters), tokens, tokens)


def test_one_train_step_matches_the_reference_step(setup):
    _, params, tokens = setup
    new, _, loss, (norms, stats) = _train_one_step(setup)
    with jax.default_matmul_precision("highest"):
        want_loss, rows, grads = ref.loss_and_grads(params, tokens, CFG)
        want, _ = ref.adamw_step(params, grads, ref.adamw_init(params), **ADAMW)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    # Adam's first step is lr * g / (|g| + eps): where |g| is of the order of
    # eps (1e-8) the last bits of g move the step by per cents of lr, so the
    # worst entry gets that room and the typical entry a tight limit
    for (path, a), b, p in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                               jax.tree.leaves(want), jax.tree.leaves(params)):
        if "expert_bias" in jax.tree_util.keystr(path):
            continue
        off = np.abs(np.asarray(a - p, np.float64) - np.asarray(b - p, np.float64)) / ADAMW["lr"]
        assert np.max(off) < 0.1 and np.mean(off) < 1e-3, jax.tree_util.keystr(path)
        assert np.max(np.abs(np.asarray(b - p))) > 0.5 * ADAMW["lr"]
    for got_rows, want_rows in zip(stats, rows):
        np.testing.assert_array_equal(got_rows["rows"], want_rows)
    want_norms = ref.group_norms(grads)
    assert set(norms) == set(want_norms)
    for name in norms:
        assert abs(float(norms[name]) - float(want_norms[name])) <= TOL * float(want_norms[name])


def test_the_selection_bias_receives_no_update(setup):
    _, params, _ = setup
    new = _train_one_step(setup)[0]
    for before, after in zip(params["blocks"], new["blocks"]):
        if "expert_bias" in before["ffn"]:
            assert np.any(np.asarray(before["ffn"]["expert_bias"]))
            np.testing.assert_array_equal(before["ffn"]["expert_bias"], after["ffn"]["expert_bias"])
            assert not np.array_equal(before["ffn"]["router"], after["ffn"]["router"])


def test_weight_decay_spares_norms_bias_and_embedding(setup):
    model, params, _ = setup
    mask = model.decay_mask(params)
    for path, decays in jax.tree_util.tree_flatten_with_path(mask)[0]:
        assert decays == ref.decays(path), jax.tree_util.keystr(path)


# --------------------------------------------------------------------- #
# the expert layer: shares, skew, the two dispatch paths
# --------------------------------------------------------------------- #
def _expert_layer(held, **kw):
    return MoE(CFG["hidden_size"], CFG["num_experts"], hidden_dim=CFG["moe_intermediate_size"],
               top_k=CFG["num_experts_per_tok"], gated=True, scoring="sigmoid", expert_bias=True,
               dispatch="sorted", experts_held=held, **kw)


def _share(p, lo, hi):
    return {**p, "w1": p["w1"][lo:hi], "w3": p["w3"][lo:hi], "w2": p["w2"][lo:hi]}


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(setup, shares):
    _, params, _ = setup
    p = params["blocks"][3]["ffn"]
    x = jax.random.normal(jax.random.key(11), (2, 24, CFG["hidden_size"]))
    per = CFG["num_experts"] // shares
    with jax.default_matmul_precision("highest"):
        want, want_rows = ref.experts(p, x, CFG)
        total, rows = 0.0, []
        for r in range(shares):
            lo, hi = r * per, (r + 1) * per
            y, stats = _expert_layer(range(lo, hi)).apply_with_stats(_share(p, lo, hi), x)
            part, _ = ref.experts(_share(p, lo, hi), x, {**CFG, "experts_held": [lo, hi]})
            assert rel(y, part) < TOL or float(jnp.max(jnp.abs(part))) == 0.0
            total, rows = total + y, rows + [stats["rows"]]
    assert rel(total, want) < TOL
    np.testing.assert_array_equal(np.concatenate(rows), want_rows)
    assert int(np.sum(want_rows)) == 2 * 24 * CFG["num_experts_per_tok"]


def _skewed(p):
    """Router weights that send every token to experts 0 and 1."""
    router = jnp.zeros_like(p["router"]).at[:, 0].set(1.0).at[:, 1].set(0.9)
    return {**p, "router": router, "expert_bias": jnp.zeros_like(p["expert_bias"])}


def test_no_token_is_dropped_under_skew(setup):
    _, params, _ = setup
    p = _skewed(params["blocks"][3]["ffn"])
    x = jnp.abs(jax.random.normal(jax.random.key(12), (4, 24, CFG["hidden_size"])))
    with jax.default_matmul_precision("highest"):
        y, stats = _expert_layer(None).apply_with_stats(p, x)
        want, rows = ref.experts(p, x, CFG)
    assert rel(y, want) < TOL
    assert list(np.asarray(stats["rows"])) == [96, 96, 0, 0, 0, 0, 0, 0] == list(np.asarray(rows))
    assert int(stats["dropped"]) == 0


def test_the_capacity_path_drops_under_the_same_skew():
    layer = MoE(CFG["hidden_size"], CFG["num_experts"], hidden_dim=48, top_k=2)
    p = layer.init(jax.random.key(3))
    p = {**p, "router": jnp.zeros_like(p["router"]).at[:, 0].set(1.0).at[:, 1].set(0.9)}
    x = jnp.abs(jax.random.normal(jax.random.key(12), (4, 24, CFG["hidden_size"])))
    y, stats = layer.apply_with_stats(p, x)
    assert int(stats["dropped"]) > 0
    np.testing.assert_allclose(y, layer.apply(p, x), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_the_two_dispatch_paths_agree_where_nothing_is_dropped(top_k):
    """Softmax routing and GELU experts with biases are the capacity path's;
    sorted dispatch of the same parameters gives the same layer."""
    capacity = MoE(32, 4, hidden_dim=48, top_k=top_k, capacity_factor=4.0)
    p = capacity.init(jax.random.key(5))
    p = {**p, "b1": 0.1 + p["b1"], "b2": 0.2 + p["b2"]}
    x = jax.random.normal(jax.random.key(6), (3, 10, 32))
    with jax.default_matmul_precision("highest"):
        want, stats = capacity.apply_with_stats(p, x)
        got, sorted_stats = MoE(32, 4, hidden_dim=48, top_k=top_k,
                                dispatch="sorted").apply_with_stats(p, x)
    assert int(stats["dropped"]) == 0 == int(sorted_stats["dropped"])
    np.testing.assert_array_equal(stats["rows"], sorted_stats["rows"])
    assert rel(got, want) < TOL


def test_decoding_through_the_sorted_path_is_apply_and_sigmoid_has_no_aux_loss(setup):
    _, params, _ = setup
    layer, p = _expert_layer(None), params["blocks"][3]["ffn"]
    x = jax.random.normal(jax.random.key(13), (2, 1, CFG["hidden_size"]))
    np.testing.assert_array_equal(layer.decode_apply(p, x), layer.apply(p, x))
    with pytest.raises(ValueError, match="softmax"):
        layer.load_balance_loss(p, x)


@pytest.mark.parametrize("bad", [
    dict(dispatch="capacity", experts_held=range(0, 2)),
    dict(dispatch="capacity", gated=True),
    dict(dispatch="capacity", scoring="sigmoid"),
    dict(dispatch="sorted", experts_held=range(2, 9)),
    dict(dispatch="sorted", experts_held=[0, 1]),
    dict(dispatch="nearest"), dict(scoring="tanh"),
])
def test_the_expert_layer_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        MoE(32, 8, **bad)


# --------------------------------------------------------------------- #
# the short convolution
# --------------------------------------------------------------------- #
def _conv_inputs(dtype=jnp.float32):
    bcu = jax.random.normal(jax.random.key(20), (2, 12, 3 * 8)).astype(dtype)
    taps = jax.random.normal(jax.random.key(21), (8, 3))
    return bcu, taps


def test_the_convolution_is_causal():
    bcu, taps = _conv_inputs()
    base = gated_short_conv(bcu, taps)
    for t in (0, 5, 11):
        moved = gated_short_conv(bcu.at[:, t].add(1.0), taps)
        np.testing.assert_array_equal(moved[:, :t], base[:, :t])
        assert np.any(np.asarray(moved[:, t]) != np.asarray(base[:, t]))
        # three taps: position t reaches t, t+1, t+2 and no further
        np.testing.assert_array_equal(moved[:, t + 3:], base[:, t + 3:])


def test_the_convolution_does_not_leak_across_sequences():
    bcu, taps = _conv_inputs()
    base = gated_short_conv(bcu, taps)
    moved = gated_short_conv(bcu.at[0].add(1.0), taps)
    np.testing.assert_array_equal(moved[1], base[1])
    alone = gated_short_conv(bcu[1:], taps)
    np.testing.assert_array_equal(alone[0], base[1])


@pytest.mark.parametrize("arg", [0, 1])
def test_the_convolutions_backward_pass_is_its_derivative(arg):
    bcu, taps = _conv_inputs()

    def plain(bcu, taps):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        v = jnp.pad(b * u, ((0, 0), (2, 0), (0, 0)))
        return c * sum(taps[:, j] * v[:, j:j + bcu.shape[1]] for j in range(3))

    w = jax.random.normal(jax.random.key(22), (2, 12, 8))
    got = jax.grad(lambda *a: jnp.sum(w * gated_short_conv(*a)), argnums=arg)(bcu, taps)
    want = jax.grad(lambda *a: jnp.sum(w * plain(*a)), argnums=arg)(bcu, taps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_convolution_keeps_its_inputs_dtype():
    bcu, taps = _conv_inputs(jnp.bfloat16)
    out = gated_short_conv(bcu, taps)
    assert out.dtype == jnp.bfloat16
    want = gated_short_conv(bcu.astype(jnp.float32), taps)
    assert rel(out, want) < 2.0 ** -7


# --------------------------------------------------------------------- #
# precision: a control one step lower must fail the same tolerance
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("control", ["expert_product_dtype", "product_dtype"])
def test_a_bfloat16_control_fails_the_tolerance(setup, control):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, tokens)
        sound = ref.logits(params, tokens, CFG)
        lowered = ref.logits(params, tokens, CFG, **{control: jnp.bfloat16})
    assert rel(got, sound) < TOL < rel(got, lowered)


def test_bfloat16_activations_stay_near_the_reference(setup):
    _, params, tokens = setup
    model = build(dtype=jnp.bfloat16)
    got, _ = model.apply(params, tokens)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, tokens, CFG)
    assert TOL < rel(got, want) < 0.1
    loss = ht.nn.losses.next_token_cross_entropy(got, tokens)
    assert loss.dtype == jnp.float32 and abs(float(loss) - float(ref.loss(params, tokens, CFG)[0])) < 0.05


# --------------------------------------------------------------------- #
# the pieces the model is built from
# --------------------------------------------------------------------- #
def test_next_token_cross_entropy_is_the_shifted_mean():
    logits = jax.random.normal(jax.random.key(30), (3, 7, 11))
    tokens = jax.random.randint(jax.random.key(31), (3, 7), 0, 11)
    want = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits[:, :-1]), tokens[:, 1:, None], axis=-1))
    np.testing.assert_allclose(ht.nn.losses.next_token_cross_entropy(logits, tokens), want, rtol=1e-6)
    g = jax.grad(lambda lg: ht.nn.losses.next_token_cross_entropy(lg, tokens))(logits.astype(jnp.bfloat16))
    assert g.dtype == jnp.bfloat16 and not np.any(np.asarray(g[:, -1], np.float32))


@pytest.mark.parametrize("pairing", ["interleaved", "half"])
def test_rope_scores_depend_on_relative_position_only(pairing):
    q = jax.random.normal(jax.random.key(40), (16,))
    k = jax.random.normal(jax.random.key(41), (16,))
    rope = lambda x, pos: ht.nn.apply_rope(x[None, :], jnp.asarray([pos]), 1e6, pairing)[0]  # noqa: E731
    a = jnp.dot(rope(q, 5), rope(k, 2))
    b = jnp.dot(rope(q, 105), rope(k, 102))
    np.testing.assert_allclose(a, b, rtol=1e-4)
    if pairing == "half":
        np.testing.assert_allclose(rope(q, 3), ref.rotate_half(q[None, :], jnp.asarray([3]), 1e6)[0],
                                   rtol=1e-6, atol=1e-6)


def test_swiglu_brings_float32_weights_to_the_activations_dtype():
    layer = ht.nn.SwiGLU(16, 40)
    p = layer.init(jax.random.key(50))
    x = jax.random.normal(jax.random.key(51), (5, 16))
    want = ref.dense_ffn(p, x)
    assert rel(layer.apply(p, x), want) < 1e-2
    assert layer.apply(p, x.astype(jnp.bfloat16)).dtype == jnp.bfloat16


def test_rms_norm_of_bfloat16_uses_float32_statistics():
    norm = ht.nn.RMSNorm(64, eps=1e-5)
    x = (100.0 + jax.random.normal(jax.random.key(60), (4, 64))).astype(jnp.bfloat16)
    got = norm.apply(norm.init(None), x)
    want = ref.rms_norm(x.astype(jnp.float32), 1.0, 1e-5)
    assert got.dtype == jnp.bfloat16 and rel(got, want) < 2.0 ** -8


def test_the_pattern_must_name_known_operators():
    with pytest.raises(ValueError):
        PatternLM(32, 16, ["conv", "window"], num_heads=2, ffn_dim=32)


def test_the_two_copies_of_the_reference_are_the_same_file():
    other = os.path.join(os.path.dirname(HERE), "chipbench", "references", "lfm2_moe.py")
    assert filecmp.cmp(os.path.join(HERE, "lfm2_moe_reference.py"), other, shallow=False)
