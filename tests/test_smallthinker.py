"""Attention with heads of their own width and a window, ReLU-gated experts
whose router reads another tensor than they do, each against the plain
reference ``smallthinker_reference`` at a small size on the CPU; then a whole
``PatternLM`` of global layers without positions and windowed rotary layers:
logits, loss, gradients by group, routed rows, three ``DataParallel`` AdamW
steps, and the expert-parallel share."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
import smallthinker_reference as ref
from heat_tpu.nn.attention import MultiheadAttention
from heat_tpu.nn.models import PatternLM
from heat_tpu.nn.moe import MoE

HERE = os.path.dirname(os.path.abspath(__file__))

# 4 query heads of 16 (64 wide) on a 48-wide model: the heads do not divide it
CFG = {
    "hidden_size": 48, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "moe_ffn_hidden_size": 24, "vocab_size": 96, "num_experts_routed": 8, "experts_held": [0, 8],
    "moe_num_active_primary_experts": 3, "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 8, "rope_theta": 1500000, "rms_norm_eps": 1e-6,
}
KINDS = ["global_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
# float32 against float32 at ``highest`` precision: what is left is the order of the sums
TOL = 2e-4


def build(cfg=CFG, **kw):
    return PatternLM(
        cfg["vocab_size"], cfg["hidden_size"], KINDS, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"], qk_norm=False,
        window=cfg["sliding_window_size"], rope_kinds=("sliding_attention",), rope_base=cfg["rope_theta"],
        ffn_dim=None, num_dense_layers=0, num_experts=cfg["num_experts_routed"],
        experts_per_token=cfg["moe_num_active_primary_experts"], expert_dim=cfg["moe_ffn_hidden_size"],
        experts_held=range(*cfg["experts_held"]), router_scoring="softmax", expert_activation="relu",
        route_before_operator=True, norm_eps=cfg["rms_norm_eps"], tie_embedding=False, **kw)


def close(got, want, tol=TOL):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale, rtol=0)


def _both(fn, *args):
    """``(fn(*args)[0], its gradients under a fixed cotangent)``, one program."""
    def scalar(*a):
        out = fn(*a)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out * jax.random.normal(jax.random.key(9), out.shape)), out

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


@pytest.fixture(scope="module")
def setup():
    with jax.default_matmul_precision("highest"):
        model = build()
        # the reference's draw: matrices larger than at the published widths, so that
        # scores have the size they have there and every layer's output that of the stream
        params = ref.init_params(jax.random.key(0), CFG, init_std=0.2)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
            if any(str(getattr(k, "key", "")).endswith("norm") for k in path) else a, params)
        tokens = jax.random.randint(jax.random.key(1), (3, 40), 0, CFG["vocab_size"])
        return model, params, tokens


# ---------------------------------------------------------------------- #
# the layers
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("layer", [0, 1], ids=["global_nope", "windowed_rotary"])
def test_attention_with_heads_of_their_own_matches_the_reference(setup, layer):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 48))
        op, p = model.blocks[layer].operator, params["blocks"][layer]["operator"]
        assert (op.head_dim, op.q_dim, op.kv_dim, op.embed_dim) == (16, 64, 32, 48)
        assert (op.window, op.rope, op.qk_norm) == ((None, False, False), (8, True, False))[layer]
        assert p["in_proj_weight"].shape == (64 + 2 * 32, 48) and p["out_proj"]["weight"].shape == (48, 64)
        rotary, window = bool(CFG["rope_layout"][layer]), (8 if CFG["sliding_window_layout"][layer] else None)
        (got, d_got) = _both(lambda p, z: op.apply(p, z, causal=True), p, z)
        (want, d_want) = _both(lambda p, z: ref.attention(p, z, CFG, rotary, window), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        # the window is not a no-op at this size, nor the rotation
        other, _ = _both(lambda p, z: ref.attention(p, z, CFG, not rotary, window), p, z)
        assert float(jnp.max(jnp.abs(other - want))) > 100 * TOL * float(jnp.max(jnp.abs(want)))


def test_a_windowed_layer_runs_under_its_own_scope(setup):
    model, params, _ = setup
    z = jnp.zeros((1, 16, 48))
    text = lambda i: str(jax.make_jaxpr(  # noqa: E731
        lambda p, z: model.blocks[i].operator.apply(p, z, causal=True))(params["blocks"][i]["operator"], z))
    hlo = lambda i: jax.jit(lambda p, z: model.blocks[i].operator.apply(p, z, causal=True)).lower(  # noqa: E731
        params["blocks"][i]["operator"], z).as_text(debug_info=True)
    assert "ht.attention.window" in hlo(1) and "ht.attention.window" not in hlo(0)
    assert "ht.attention" in hlo(0) and text(0) != text(1)


def test_decoding_through_the_cache_keeps_to_the_window():
    """A decode step sees the cached keys of its window alone: the rows of a
    full causal windowed ``apply``."""
    with jax.default_matmul_precision("highest"):
        op = MultiheadAttention(48, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=2,
                                head_dim=16, window=5)
        p = op.init(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2, 12, 48))
        want = op.apply(p, x, causal=True)
        cache, rows = op.init_cache(2, 12), []
        for t in range(12):
            y, cache = op.decode_step(p, x[:, t:t + 1], cache)
            rows.append(y)
        close(jnp.concatenate(rows, axis=1), want, 1e-5)
        unwindowed = MultiheadAttention(48, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=2, head_dim=16)
        assert float(jnp.max(jnp.abs(unwindowed.apply(p, x, causal=True) - want))) > 1e-3
    with pytest.raises(ValueError, match="window"):
        MultiheadAttention(48, 4, head_dim=16, window=0)
    with pytest.raises(ValueError, match="not divisible"):
        MultiheadAttention(2560, 28)  # what the model asks for without head_dim=
    assert MultiheadAttention(2560, 28, head_dim=128, num_kv_heads=4).q_dim == 3584


def test_relu_gated_experts_routed_on_another_tensor_match_the_reference(setup):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        u = jax.random.normal(jax.random.key(2), (2, 40, 48))
        r = jax.random.normal(jax.random.key(3), (2, 40, 48))
        layer, p = model.blocks[1].ffn, params["blocks"][1]["ffn"]
        assert layer.activation == "relu" and layer.scoring == "softmax" and set(p) == {"router", "w1", "w2", "w3"}
        (got, d_got) = _both(lambda p, u, r: layer.apply_with_stats(p, u, router_input=r), p, u, r)
        (want, d_want) = _both(lambda p, u, r: ref.experts(p, u, r, CFG), p, u, r)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        assert float(jnp.max(jnp.abs(d_want[2]))) > 0  # the router's input has a gradient of its own
        _, stats = layer.apply_with_stats(p, u, router_input=r)
        assert int(stats["dropped"]) == 0 and int(stats["rows"].sum()) == 2 * 40 * 3
        np.testing.assert_array_equal(stats["rows"], ref.experts(p, u, r, CFG)[1])
        # routed on its own input it is another layer, and SwiGLU experts another still
        own, _ = layer.apply_with_stats(p, u)
        close(own, ref.experts(p, u, u, CFG)[0])
        assert float(jnp.max(jnp.abs(own - got))) > 100 * TOL * float(jnp.max(jnp.abs(got)))
        silu = MoE(48, 8, hidden_dim=24, top_k=3, gated=True, dispatch="sorted")
        assert float(jnp.max(jnp.abs(silu.apply_with_stats(p, u, router_input=r)[0] - got))) > 1e-3
    with pytest.raises(ValueError, match="activation"):
        MoE(48, 8, top_k=2, dispatch="sorted", activation="relu")  # of gated experts only
    with pytest.raises(ValueError, match="router_input"):
        MoE(48, 8, top_k=2).apply_with_stats(MoE(48, 8, top_k=2).init(jax.random.key(0)), u, router_input=r)


def test_shares_add_up_to_the_uncut_layer(setup):
    """The four ranks' outputs of one expert layer (two of the eight experts
    each here, 16 of 64 in the cell) add up to the uncut reference layer, and
    their routed rows are its rows."""
    _, params, _ = setup
    with jax.default_matmul_precision("highest"):
        u = jax.random.normal(jax.random.key(2), (2, 40, 48))
        r = jax.random.normal(jax.random.key(3), (2, 40, 48))
        p = params["blocks"][2]["ffn"]
        whole, rows = jax.jit(lambda p, u, r: ref.experts(p, u, r, CFG))(p, u, r)
        total, counted = jnp.zeros_like(whole), []
        for lo in range(0, 8, 2):
            rank = MoE(48, 8, hidden_dim=24, top_k=3, gated=True, activation="relu", dispatch="sorted",
                       experts_held=range(lo, lo + 2), rows_bound=240)
            mine = {**p, **{n: p[n][lo:lo + 2] for n in ("w1", "w2", "w3")}}
            part, stats = jax.jit(lambda p, u, r, rank=rank: rank.apply_with_stats(p, u, router_input=r))(mine, u, r)
            cut, _ = jax.jit(lambda p, u, r, lo=lo: ref.experts(p, u, r, {**CFG, "experts_held": [lo, lo + 2]}))(mine, u, r)
            close(part, cut)
            assert int(stats["dropped"]) == 0
            total = total + part
            counted.append(stats["rows"])
        close(total, whole)
        np.testing.assert_array_equal(jnp.concatenate(counted), rows)


# ---------------------------------------------------------------------- #
# the whole model
# ---------------------------------------------------------------------- #
_reference_step = jax.jit(lambda params, tokens: ref.loss_and_grads(params, tokens, CFG))


def test_the_programs_tree_is_the_references(setup):
    model, params, _ = setup
    own = model.init(jax.random.key(7))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert shape_of(own) == shape_of(params)
    mask = model.decay_mask(own)
    assert all(bool(m) == ref.decays(path) for path, m in jax.tree_util.tree_flatten_with_path(mask)[0])
    assert mask["head"]["weight"] and not mask["embed"]["weight"] and mask["blocks"][0]["ffn"]["router"]
    assert "expert_bias" not in own["blocks"][0]["ffn"] and "q_norm" not in own["blocks"][0]["operator"]
    assert [b.route_on_input for b in model.blocks] == [True] * 4
    with pytest.raises(ValueError, match="window="):
        PatternLM(8, 8, ["sliding_attention"], num_heads=1, ffn_dim=8)
    with pytest.raises(ValueError, match="sliding_attention"):
        PatternLM(8, 8, ["windowed"], num_heads=1, ffn_dim=8)
    # the arguments leave the accepted layouts as they were: rotated, normalised heads of embed_dim / num_heads
    old = PatternLM(32, 32, ["conv", "full_attention"], num_heads=4, num_kv_heads=2, ffn_dim=48).blocks[1].operator
    assert (old.rope, old.qk_norm, old.head_dim, old.window) == (True, True, 8, None)


def test_logits_loss_and_gradients_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply)(params, tokens)
        close(logits, jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens))
        assert len(stats) == 4 and all(int(s["dropped"]) == 0 for s in stats)

        def loss(p):
            out, routing = model.apply(p, tokens, train=True)
            return ht.nn.losses.next_token_cross_entropy(out, tokens), routing

        (value, routing), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        want, rows, want_grads = _reference_step(params, tokens)
        assert abs(float(value) - float(want)) < 1e-5 * float(want)
        for mine, theirs in zip(routing, rows):
            np.testing.assert_array_equal(mine["rows"], theirs)
        got_norms, want_norms = ref.group_norms(grads), ref.group_norms(want_grads)
        assert set(want_norms) == {"embedding", "head", "norms", "router", "experts",
                                   "operator_0", "operator_1", "operator_2", "operator_3"}
        for name, norm in want_norms.items():
            assert abs(float(got_norms[name]) - float(norm)) <= TOL * float(norm), name
        jax.tree.map(close, grads, want_grads)


def test_three_data_parallel_adamw_steps_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        optimizer = ht.optim.DataParallelOptimizer(ht.optim.AdamW(
            lr=ADAMW["lr"], betas=(ADAMW["b1"], ADAMW["b2"]), eps=ADAMW["eps"],
            weight_decay=ADAMW["weight_decay"], mask=model.decay_mask))
        dp = ht.nn.DataParallel(model, optimizer=optimizer)
        dp.parameters = mine = jax.tree.map(jnp.copy, params)
        step = dp.make_train_step(
            lambda out, t: (ht.nn.losses.next_token_cross_entropy(out[0], t), out[1]),
            stats=lambda grads, aux, *_: (jnp.stack([r["rows"] for r in aux]), sum(r["dropped"] for r in aux)))
        state = optimizer.init_state(mine)
        theirs, adam = params, ref.adamw_init(params)
        reference_update = jax.jit(lambda p, g, a: ref.adamw_step(p, g, a, **ADAMW))
        for i in range(3):
            batch = jnp.roll(tokens, i, axis=1)
            want_loss, want_rows, grads = _reference_step(theirs, batch)
            before = theirs
            theirs, adam = reference_update(theirs, grads, adam)
            start = mine
            moved_from = jax.tree.map(jnp.copy, start)
            mine, state, loss, (rows, dropped) = step(start, state, batch, batch)
            assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss) and int(dropped) == 0
            np.testing.assert_array_equal(rows, jnp.stack(want_rows))
            moved = ref.group_norms(jax.tree.map(jnp.subtract, mine, moved_from))
            for name, norm in ref.group_norms(jax.tree.map(jnp.subtract, theirs, before)).items():
                assert abs(float(moved[name]) - float(norm)) <= 2e-3 * float(norm) + 1e-12, (i, name)
        jax.tree.map(lambda a, b: close(a, b, 1e-4), mine, theirs)


def test_the_controls_are_told_apart(setup):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens)
        for control in ({"product_dtype": jnp.bfloat16}, {"no_window": True}, {"rope_everywhere": True}):
            other = jax.jit(lambda p, t, c=control: ref.logits(p, t, CFG, **c))(params, tokens)
            assert float(jnp.max(jnp.abs(other - want))) > 10 * TOL * float(jnp.max(jnp.abs(want))), control


def test_the_reference_scores_rows_in_blocks_without_changing_a_number(monkeypatch):
    with jax.default_matmul_precision("highest"):
        q, k, v = (jax.random.normal(jax.random.key(i), (40, 16)) for i in range(3))
        whole = ref._attend(q, k, v, 8, None)
        monkeypatch.setattr(ref, "ROWS", 10)
        close(ref._attend(q, k, v, 8, None), whole, 1e-6)
        monkeypatch.setattr(ref, "ROWS", 16)  # does not divide 40: one block
        close(ref._attend(q, k, v, 8, None), whole, 1e-6)


def test_the_reference_is_plain_and_the_benchmarks_copy_is_this_file():
    other = os.path.join(os.path.dirname(HERE), "chipbench", "references", "smallthinker.py")
    assert filecmp.cmp(os.path.join(HERE, "smallthinker_reference.py"), other, shallow=False)
    with open(other, encoding="utf-8") as fh:
        source = fh.read()
    assert "import heat_tpu" not in source and "from heat_tpu" not in source and "pallas" not in source
    assert "jax.nn.relu" in source and "lax.top_k(logits" in source
