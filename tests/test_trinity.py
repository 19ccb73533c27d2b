"""Attention with a gate on its output, the block with a norm after each
sublayer, the scaled embedding and the head's loss in row blocks, each against
the plain reference ``trinity_reference`` at a small size on the CPU; then a
whole ``PatternLM`` in this layout: loss, gradients by group, routed rows,
three ``DataParallel`` AdamW steps through ``forward=model.next_token_loss``,
and the expert-parallel share."""

import filecmp
import gzip
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
import trinity_reference as ref
from heat_tpu.nn.attention import MultiheadAttention
from heat_tpu.nn.losses import next_token_cross_entropy, next_token_cross_entropy_by_rows
from heat_tpu.nn.models import PatternLM
from heat_tpu.nn.moe import MoE
from test_ops_kernels import blocks128  # noqa: F401  (the fixture: 128 x 128 blocks)

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_trinity")

# 4 query heads of 16 (64 wide) on a 48-wide model, 16 experts of which a rank holds 4
CFG = {
    "hidden_size": 48, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 24, "vocab_size": 96,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
    "num_dense_layers": 1, "num_experts": 16, "experts_held": [0, 16], "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826, "sliding_window": 8,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "mup_enabled": True,
}
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
# float32 against float32 at ``highest`` precision: what is left is the order of the sums
TOL = 2e-4


def build(cfg=CFG, **kw):
    return PatternLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["layer_types"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"], qk_norm=True,
        window=cfg["sliding_window"], rope_kinds=("sliding_attention",), rope_base=cfg["rope_theta"],
        ffn_dim=cfg["intermediate_size"], num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"], experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"], experts_held=range(*cfg["experts_held"]),
        routed_scaling=cfg["route_scale"], norm_topk=cfg["route_norm"],
        shared_expert_dim=cfg["moe_intermediate_size"], norm_eps=cfg["rms_norm_eps"], tie_embedding=False,
        attention_gate=True, output_norms=True, embedding_scale=cfg["hidden_size"] ** 0.5, **kw)


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
        # the reference's draw: matrices larger than at the published widths, so that every
        # layer's output has the size of the stream; norm weights off 1, a bias that decides
        params = ref.init_params(jax.random.key(0), CFG, init_std=0.2, bias_std=0.05, embed_std=0.02)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
            if any(str(getattr(k, "key", "")).endswith("norm") for k in path) else a, params)
        tokens = jax.random.randint(jax.random.key(1), (3, 40), 0, CFG["vocab_size"])
        return model, params, tokens


# ---------------------------------------------------------------------- #
# the layers
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("layer", [1, 2], ids=["windowed_rotary", "global_nope"])
def test_gated_attention_matches_the_reference(setup, layer):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 48))
        op, p = model.blocks[layer].operator, params["blocks"][layer]["operator"]
        assert op.gate and op.qk_norm and (op.window, op.rope) == ((8, True), (None, False))[layer - 1]
        assert p["gate_proj"]["weight"].shape == (64, 48) and "bias" not in p["gate_proj"]
        sliding = CFG["layer_types"][layer] == "sliding_attention"
        (got, d_got) = _both(lambda p, z: op.apply(p, z, causal=True), p, z)
        (want, d_want) = _both(lambda p, z: ref.attention(p, z, CFG, sliding, 8 if sliding else None), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        assert float(jnp.max(jnp.abs(d_want[0]["gate_proj"]["weight"]))) > 0
        # the gate is not a no-op at this size
        other, _ = _both(lambda p, z: ref.attention(p, z, CFG, sliding, 8 if sliding else None, gate=False), p, z)
        assert float(jnp.max(jnp.abs(other - want))) > 100 * TOL * float(jnp.max(jnp.abs(want)))


def test_the_gate_runs_under_its_own_scope_inside_the_projections(setup):
    model, params, _ = setup
    block, p = model.blocks[1], params["blocks"][1]
    hlo = jax.jit(lambda p, x: block.apply(p, x)[0]).lower(p, jnp.zeros((1, 16, 48))).as_text(debug_info=True)
    assert "ht.attention.proj/ht.attention.gate" in hlo and "ht.attention.proj/ht.lm.norm" not in hlo
    plain = MultiheadAttention(48, 4, bias=False, head_dim=16)
    hlo = jax.jit(lambda p, x: plain.apply(p, x, causal=True)).lower(
        plain.init(jax.random.key(0)), jnp.zeros((1, 16, 48))).as_text(debug_info=True)
    assert "ht.attention.gate" not in hlo


@pytest.mark.parametrize("window", [None, 5], ids=["global", "windowed"])
def test_decoding_through_the_cache_applies_the_gate(window):
    """A decode step's output is the row of a full causal gated ``apply``,
    and a cross step's the row of a gated cross ``apply``."""
    with jax.default_matmul_precision("highest"):
        op = MultiheadAttention(48, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=2,
                                head_dim=16, window=window, qk_norm=True, gate=True)
        p = op.init(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2, 12, 48))
        want = op.apply(p, x, causal=True)
        cache, rows = op.init_cache(2, 12), []
        for t in range(12):
            y, cache = op.decode_step(p, x[:, t:t + 1], cache)
            rows.append(y)
        close(jnp.concatenate(rows, axis=1), want, 1e-5)
        ungated = MultiheadAttention(48, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=2,
                                     head_dim=16, window=window, qk_norm=True)
        assert float(jnp.max(jnp.abs(ungated.apply(p, x, causal=True) - want))) > 1e-3
    if window is None:
        cross = MultiheadAttention(48, 4, head_dim=16, gate=True)
        p = cross.init(jax.random.key(2))
        assert set(p["gate_proj"]) == {"weight"} and "bias" in p["out_proj"]  # the gate has no bias of its own
        memory = jax.random.normal(jax.random.key(3), (2, 7, 48))
        whole = cross.apply(p, x, kv=memory)
        kh, vh = cross.precompute_kv(p, memory)
        close(cross.cross_step(p, x[:, 3:4], kh, vh), whole[:, 3:4], 1e-5)


def _jaxpr_text(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return re.sub(r" at 0x[0-9a-f]+", "", re.sub(r"/[^\s:]+\.py:\d+", "FILE", text))


# attention modules without a gate, as the accepted cells build them: value and gradients
UNGATED = {
    "mha_bias": (dict(embed_dim=32, num_heads=4), (2, 16, 32)),
    "gqa_qk_norm_rope": (dict(embed_dim=32, num_heads=4, bias=False, rope=True, rope_pairing="half",
                              num_kv_heads=2, qk_norm=True), (2, 16, 32)),
    "gqa_window_head_dim": (dict(embed_dim=48, num_heads=4, bias=False, rope=True, rope_pairing="half",
                                 num_kv_heads=2, head_dim=16, window=8), (2, 16, 48)),
}


def ungated_jaxprs(name):
    """``(apply's value and gradients, a decode step)`` of an attention module
    built without ``gate=``, as text."""
    kw, shape = UNGATED[name]
    op = MultiheadAttention(**kw)
    params = jax.eval_shape(op.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    train = _jaxpr_text(jax.value_and_grad(lambda p, x: jnp.sum(op.apply(p, x, causal=True)), (0, 1)), params, x)
    cache = jax.eval_shape(lambda: op.init_cache(shape[0], shape[1]))
    step = _jaxpr_text(lambda p, x, c: op.decode_step(p, x[:, :1], c), params, x, cache)
    return train + "\n" + step


@pytest.mark.parametrize("name", list(UNGATED))
def test_without_a_gate_attention_lowers_as_before(name):
    """``gate=False`` is a static branch that builds the programs of e393382,
    instruction for instruction: the fixtures are the jaxprs that commit traced
    (source lines and addresses stripped), with the flash forward's residuals
    named (a ``name`` equation each, which lowers to nothing) and the flash
    backward one fused kernel (with the two sweeps, ``_fused_bwd_fits``
    False, they were e393382's with the names when recorded again)."""
    with gzip.open(os.path.join(FIXTURES, f"attention_{name}.jaxpr.txt.gz"), "rt") as f:
        before = f.read()
    assert ungated_jaxprs(name) == before
    kw, shape = UNGATED[name]
    gated = MultiheadAttention(gate=True, **kw)
    text = _jaxpr_text(lambda p, x: gated.apply(p, x, causal=True),
                       jax.eval_shape(gated.init, jax.random.key(0)), jax.ShapeDtypeStruct(shape, jnp.float32))
    assert "logistic" in text and text not in before


def test_windowed_kernels_at_two_blocks_with_normalised_rotated_heads(blocks128):
    """The flash kernels (interpreter) at ``window = 2 x blk``, where a Q
    block's sweep is three blocks and two of them edge blocks, behind QK norm,
    rotation and the gate: against ``_dense_attention(window=)``, which a
    float mask of zeros sends the same module through."""
    fa = blocks128
    assert fa._block_census(512, 512, 128, 128, True, 256) == {"interior": 3, "edge": 6, "dead": 3}
    op = MultiheadAttention(64, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=1,
                            head_dim=16, window=256, qk_norm=True, gate=True)
    p = op.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 512, 64))
    before = dict(fa.path_counts)
    got, d_got = _both(lambda p, x: op.apply(p, x, causal=True), p, x)
    assert fa.path_counts["pallas"] > before["pallas"] and fa.path_counts["dense"] == before["dense"]
    want, d_want = _both(lambda p, x: op.apply(p, x, causal=True, attn_mask=jnp.zeros((512, 512))), p, x)
    close(got, want, 1e-4)
    jax.tree.map(lambda a, b: close(a, b, 1e-4), d_got, d_want)
    unwindowed = MultiheadAttention(64, 4, bias=False, rope=True, rope_pairing="half", num_kv_heads=1,
                                    head_dim=16, qk_norm=True, gate=True)
    assert float(jnp.max(jnp.abs(unwindowed.apply(p, x, causal=True) - got))) > 1e-3


def test_sigmoid_routed_experts_beside_a_shared_one_match_the_reference(setup):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        u = jax.random.normal(jax.random.key(2), (2, 40, 48))
        layer, p = model.blocks[1].ffn, params["blocks"][1]["ffn"]
        assert layer.scoring == "sigmoid" and layer.expert_bias and layer.routed_scaling == 2.826
        (got, d_got) = _both(lambda p, u: layer.apply_with_stats(p, u), p, u)
        (want, d_want) = _both(lambda p, u: ref.experts(p, u, CFG), p, u)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        _, stats = layer.apply_with_stats(p, u)
        assert int(stats["dropped"]) == 0 and int(stats["rows"].sum()) == 2 * 40 * 4
        np.testing.assert_array_equal(stats["rows"], ref.experts(p, u, CFG)[1])
        # the renormalisation's constant is the program's: the published 1e-20 is another number
        assert ref.RENORM_EPS == 1e-6 and "1e-6" in PatternLM.__doc__


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The sixteen ranks' outputs of one expert layer (8 of 128 experts each,
    as in the cell; the shared expert counted once) add up to the uncut
    128-expert reference layer, and their routed rows are its rows."""
    cfg = {**CFG, "num_experts": 128, "experts_held": [0, 128], "num_experts_per_tok": 8}
    with jax.default_matmul_precision("highest"):
        p = ref.init_params(jax.random.key(4), {**cfg, "layer_types": ["sliding_attention"] * 2},
                            init_std=0.2, bias_std=0.05)["blocks"][1]["ffn"]
        u = jax.random.normal(jax.random.key(2), (2, 40, 48))
        whole, rows = jax.jit(lambda p, u: ref.experts(p, u, cfg))(p, u)
        total, counted = jnp.zeros_like(whole), []
        for lo in range(0, 128, 8):
            rank = MoE(48, 128, hidden_dim=24, top_k=8, gated=True, scoring="sigmoid", expert_bias=True,
                       routed_scaling=2.826, dispatch="sorted", experts_held=range(lo, lo + 8),
                       shared_dim=24 if lo == 0 else None, rows_bound=640)
            mine = {**p, **{n: p[n][lo:lo + 8] for n in ("w1", "w2", "w3")}}
            if lo:
                mine.pop("shared")
            part, stats = jax.jit(rank.apply_with_stats)(mine, u)
            cut, _ = jax.jit(lambda p, u, lo=lo: ref.experts(
                p, u, {**cfg, "num_experts_routed": 128, "experts_held": [lo, lo + 8]}, shared=lo == 0))(mine, u)
            close(part, cut)
            assert int(stats["dropped"]) == 0
            total = total + part
            counted.append(stats["rows"])
        close(total, whole)
        np.testing.assert_array_equal(jnp.concatenate(counted), rows)
        assert int(rows.sum()) == 2 * 40 * 8


# ---------------------------------------------------------------------- #
# the head's product and the loss in blocks of rows
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sequences,length,block", [(1, 64, 16), (1, 50, 16), (3, 40, 32), (3, 40, 1000), (2, 8, 16)],
                         ids=["one_seq_multiple", "one_seq_ragged", "several_ragged", "one_block", "block_is_all"])
def test_the_loss_by_rows_is_the_loss_of_the_logits(sequences, length, block, dtype):
    keys = jax.random.split(jax.random.key(length), 4)
    states = jax.random.normal(keys[0], (sequences, length, 24), dtype)
    head = (0.3 * jax.random.normal(keys[1], (50, 24))).astype(dtype)
    weight = 1.0 + 0.1 * jax.random.normal(keys[2], (24,))
    tokens = jax.random.randint(keys[3], (sequences, length), 0, 50)
    norm = lambda w: (lambda rows: ht.nn.modules.rms_normalize(rows, w, 1e-5))  # noqa: E731

    def whole(states, head, weight):
        return next_token_cross_entropy(norm(weight)(states) @ head.T, tokens)

    def by_rows(states, head, weight):
        return next_token_cross_entropy_by_rows(states, head, tokens, norm=norm(weight), block_rows=block)

    with jax.default_matmul_precision("highest"):
        want, d_want = jax.jit(jax.value_and_grad(whole, (0, 1, 2)))(states, head, weight)
        got, d_got = jax.jit(jax.value_and_grad(by_rows, (0, 1, 2)))(states, head, weight)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2  # a bfloat16 cotangent is summed in another order
    assert abs(float(got) - float(want)) <= (1e-6 if dtype == jnp.float32 else 5e-4) * float(want)
    for a, b in zip(d_got, d_want):
        assert a.dtype == b.dtype
        close(a.astype(jnp.float32), b.astype(jnp.float32), tol)
    # no (sequences, length, vocabulary) array: the largest with the vocabulary in it is a block's
    text = str(jax.make_jaxpr(jax.grad(by_rows, (0, 1)))(states, head, weight))
    rows = min(block, sequences * length)
    name = {jnp.float32: "f32", jnp.bfloat16: "bf16"}[dtype]
    assert f"{name}[{rows},50]" in text
    if rows < sequences * length:
        assert f"[{sequences},{length},50]" not in text and f"[{sequences * length},50]" not in text


def test_without_the_norm_the_states_are_taken_as_they_are():
    states = jax.random.normal(jax.random.key(0), (2, 12, 8))
    head = jax.random.normal(jax.random.key(1), (20, 8))
    tokens = jax.random.randint(jax.random.key(2), (2, 12), 0, 20)
    want = next_token_cross_entropy(states @ head.T, tokens)
    assert abs(float(next_token_cross_entropy_by_rows(states, head, tokens, block_rows=5)) - float(want)) < 1e-5
    assert "next_token_cross_entropy_by_rows" in next_token_cross_entropy.__doc__
    assert "saves" in next_token_cross_entropy.__doc__ and "nothing" in next_token_cross_entropy.__doc__


# ---------------------------------------------------------------------- #
# the whole model
# ---------------------------------------------------------------------- #
_reference_step = jax.jit(lambda params, tokens: ref.loss_and_grads(params, tokens, CFG))
GROUPS = {"embedding", "head", "norms", "router", "selection_bias", "experts", "shared_expert", "dense_ffn",
          "operator_0", "operator_1", "operator_2", "operator_3"}


def test_the_programs_tree_is_the_references(setup):
    model, params, _ = setup
    own = model.init(jax.random.key(7))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert shape_of(own) == shape_of(params)
    mask = model.decay_mask(own)
    assert all(bool(m) == ref.decays(path) for path, m in jax.tree_util.tree_flatten_with_path(mask)[0])
    block = own["blocks"][1]
    assert set(block) == {"operator_norm", "operator", "operator_out_norm", "ffn_norm", "ffn", "ffn_out_norm"}
    assert mask["blocks"][1]["operator"]["gate_proj"]["weight"] and not mask["blocks"][1]["ffn_out_norm"]["weight"]
    assert float(own["blocks"][1]["operator_out_norm"]["weight"].min()) == 1.0
    assert [b.operator.rope for b in model.blocks] == [True, True, False, True]
    assert [b.operator.window for b in model.blocks] == [8, 8, None, 8]
    # the arguments leave the accepted layouts as they were: two norms a block, no gate, no scale
    old = PatternLM(32, 32, ["conv", "full_attention"], num_heads=4, num_kv_heads=2, ffn_dim=48)
    assert old.embedding_scale is None and not old.blocks[1].operator.gate
    assert set(old.init(jax.random.key(0))["blocks"][1]) == {"operator_norm", "operator", "ffn_norm", "ffn"}


def test_logits_loss_and_gradients_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply)(params, tokens)
        close(logits, jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens))
        assert len(stats) == 3 and all(int(s["dropped"]) == 0 for s in stats)
        loss = lambda p: model.next_token_loss(p, tokens, train=True, block_rows=32)  # noqa: E731
        (value, routing), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        want, rows, want_grads = _reference_step(params, tokens)
        assert abs(float(value) - float(want)) < 1e-5 * float(want)
        assert abs(float(ht.nn.losses.next_token_cross_entropy(logits, tokens)) - float(want)) < 1e-5 * float(want)
        for mine, theirs in zip(routing, rows):
            np.testing.assert_array_equal(mine["rows"], theirs)
        got_norms, want_norms = ref.group_norms(grads), ref.group_norms(want_grads)
        assert set(want_norms) == GROUPS
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
            lambda out, t: out, forward=model.next_token_loss,
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
    with pytest.raises(ValueError, match="forward="):
        dp.make_train_step(lambda out, t: out, forward=model.next_token_loss, overlap_sync=True)


def test_the_controls_are_told_apart(setup):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens)
        for control in ({"product_dtype": jnp.bfloat16}, {"no_gate": True}, {"no_window": True},
                        {"no_embedding_scale": True}):
            other = jax.jit(lambda p, t, c=control: ref.logits(p, t, CFG, **c))(params, tokens)
            assert float(jnp.max(jnp.abs(other - want))) > 10 * TOL * float(jnp.max(jnp.abs(want))), control


def test_the_reference_works_in_blocks_without_changing_a_number(setup, monkeypatch):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        q, k, v = (jax.random.normal(jax.random.key(i), (40, 16)) for i in range(3))
        for window in (8, None):
            whole = ref._attend(q, k, v, window, None)
            monkeypatch.setattr(ref, "ROWS", 10)  # a windowed block scores 18 of the 40 keys
            close(ref._attend(q, k, v, window, None), whole, 1e-6)
            monkeypatch.setattr(ref, "ROWS", 16)  # does not divide 40: one block
            close(ref._attend(q, k, v, window, None), whole, 1e-6)
            monkeypatch.setattr(ref, "ROWS", 2048)
        whole = ref.loss(params, tokens, CFG)[0]
        monkeypatch.setattr(ref, "HEAD_ROWS", 8)
        assert abs(float(ref.loss(params, tokens, CFG)[0]) - float(whole)) < 1e-6 * float(whole)


def test_the_reference_is_plain_and_the_benchmarks_copy_is_this_file():
    other = os.path.join(os.path.dirname(HERE), "chipbench", "references", "trinity.py")
    assert filecmp.cmp(os.path.join(HERE, "trinity_reference.py"), other, shallow=False)
    with open(other, encoding="utf-8") as fh:
        source = fh.read()
    assert "import heat_tpu" not in source and "from heat_tpu" not in source and "pallas" not in source
    assert "jax.nn.sigmoid(_mm(z, w_g.T" in source and "Departures" in source and "1e-20" in source
