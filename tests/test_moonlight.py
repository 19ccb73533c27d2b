"""Latent attention with rotary positions on its shared key part, against the
plain reference ``moonlight_reference`` at a small size on the CPU: the layer,
the rotation's relative-position property, a layer without positions that
traces as it did, the expert-parallel share, then a whole ``PatternLM`` of
latent-attention layers: loss, gradients by group, routed rows and three
``DataParallel`` AdamW steps through ``forward=model.next_token_loss``."""

import filecmp
import gzip
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
import moonlight_reference as ref
from heat_tpu.nn import attention as attention_module
from heat_tpu.nn.attention import LatentAttention, apply_rope
from heat_tpu.nn.models import PatternLM
from heat_tpu.nn.moe import MoE

HERE = os.path.dirname(os.path.abspath(__file__))

# 4 heads of 16 + 8 on a 48-wide model over a latent of 24, 16 experts of which a rank holds all
CFG = {
    "hidden_size": 48, "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "kv_lora_rank": 24, "intermediate_size": 96, "moe_intermediate_size": 24,
    "vocab_size": 96, "num_hidden_layers": 3, "first_k_dense_replace": 1, "n_routed_experts": 16,
    "num_experts_per_tok": 6, "n_shared_experts": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "rope_theta": 50000, "rms_norm_eps": 1e-5, "kv_a_layernorm_eps": 1e-6,
}
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
# float32 against float32 at ``highest`` precision: what is left is the order of the sums
TOL = 2e-4


def build(cfg=CFG, rope_kinds=("mla",)):
    return PatternLM(
        cfg["vocab_size"], cfg["hidden_size"], ["mla"] * cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], kv_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_shared_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"], rope_kinds=rope_kinds,
        rope_base=cfg["rope_theta"], kv_norm_eps=cfg["kv_a_layernorm_eps"], ffn_dim=cfg["intermediate_size"],
        num_dense_layers=cfg["first_k_dense_replace"], num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"], expert_dim=cfg["moe_intermediate_size"],
        routed_scaling=cfg["routed_scaling_factor"], norm_topk=cfg["norm_topk_prob"],
        shared_expert_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        tie_embedding=False)


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
        # the reference's draw: matrices larger than at the published widths, so that the
        # scores and every layer's output have the size of the stream; norm weights off 1
        params = ref.init_params(jax.random.key(0), CFG, init_std=0.2, bias_std=0.05)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
            if any(str(getattr(k, "key", "")).endswith("norm") for k in path) else a, params)
        tokens = jax.random.randint(jax.random.key(1), (3, 40), 0, CFG["vocab_size"])
        return model, params, tokens


# ---------------------------------------------------------------------- #
# the layer
# ---------------------------------------------------------------------- #
def test_rotated_latent_attention_matches_the_reference(setup, monkeypatch):
    model, params, _ = setup
    op, p = model.blocks[1].operator, params["blocks"][1]["operator"]
    assert isinstance(op, LatentAttention) and op.rope and op.rope_base == 50000 and op.eps == 1e-6
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 48))
        got, d_got = _both(lambda p, z: op.apply(p, z, causal=True), p, z)
        want, d_want = _both(lambda p, z: ref.attention(p, z, CFG), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        # the rotation is no no-op at this size
        plain, _ = _both(lambda p, z: ref.attention(p, z, CFG, rotary=False), p, z)
        assert float(jnp.max(jnp.abs(plain - want))) > 100 * TOL * float(jnp.max(jnp.abs(want)))
        # the control: channel i paired with i + d/2 of the layout the weights store
        # (rotate-half without the published de-interleaving) is another model
        monkeypatch.setattr(attention_module, "_pairs_to_halves", lambda w, width, groups: w)
        halves, _ = _both(lambda p, z: op.apply(p, z, causal=True), p, z)
        assert float(jnp.max(jnp.abs(halves - want))) > 100 * TOL * float(jnp.max(jnp.abs(want)))


def test_the_shared_key_rotates_by_relative_position():
    """A query part and a shared key part the same at every position: after
    the rotation their products depend on the distance alone, and the
    published de-interleave-then-rotate-halves gives the products of the
    rotation of consecutive pairs."""
    with jax.default_matmul_precision("highest"):
        q, k = jax.random.normal(jax.random.key(3), (2, 64))
        positions = jnp.arange(48)
        rq, rk = (apply_rope(jnp.broadcast_to(v, (48, 64)), positions, 50000.0) for v in (q, k))
        scores = rq @ rk.T
        for shift in (1, 5, 17):
            close(scores[shift:, shift:], scores[:-shift, :-shift], 1e-5)
        assert float(jnp.std(jnp.diagonal(scores, -7))) < 1e-4 * float(jnp.max(jnp.abs(scores)))
        assert float(jnp.std(scores[:, 0])) > 1e-2 * float(jnp.max(jnp.abs(scores)))
        pq, pk = (ref.rotate(jnp.broadcast_to(v, (48, 64)), positions, 50000.0) for v in (q, k))
        close(pq @ pk.T, scores, 1e-5)
        # the rotation keeps the norm of every vector
        close(jnp.linalg.norm(rk, axis=-1), jnp.full((48,), jnp.linalg.norm(k)), 1e-6)


def test_the_rotation_runs_under_its_own_scope_inside_the_projections(setup):
    model, params, _ = setup
    block, p = model.blocks[1], params["blocks"][1]
    hlo = jax.jit(lambda p, x: block.apply(p, x)[0]).lower(p, jnp.zeros((1, 16, 48))).as_text(debug_info=True)
    assert "ht.attention.proj/ht.attention.rope" in hlo
    plain = LatentAttention(48, 4, kv_rank=24, qk_nope_dim=16, qk_shared_dim=8, v_dim=12)
    hlo = jax.jit(lambda p, x: plain.apply(p, x)).lower(
        plain.init(jax.random.key(0)), jnp.zeros((1, 16, 48))).as_text(debug_info=True)
    assert "ht.attention.rope" not in hlo
    # the pairs are reordered on the weights' rows: no strided slice of the activations,
    # which would lower to a gather forward and a scatter-add backward
    rotated = LatentAttention(48, 4, kv_rank=24, qk_nope_dim=16, qk_shared_dim=8, v_dim=12, rope=True)
    grad = jax.grad(lambda p, x: jnp.sum(rotated.apply(p, x)), argnums=(0, 1))
    text = jax.jit(grad).lower(rotated.init(jax.random.key(0)), jnp.zeros((1, 16, 48))).as_text()
    assert "gather" not in text and "scatter" not in text
    with pytest.raises(ValueError, match="even"):
        LatentAttention(48, 4, kv_rank=24, qk_nope_dim=16, qk_shared_dim=7, v_dim=12, rope=True)


def test_without_rope_the_kimi_step_traces_as_it_did():
    """``rope=False`` (the default, what ``PatternLM`` builds where
    ``rope_kinds`` does not name ``"mla"``) leaves latent attention as it was:
    the Kimi cell's step at its toy shapes traces to the jaxpr its fixture
    holds, instruction for instruction."""
    sys.path.insert(0, os.path.join(HERE, "benchmark"))
    import test_chipbench_kimi_linear as kimi_tests
    from test_chipbench_trinity import BENCH, step_jaxpr

    with gzip.open(os.path.join(HERE, "fixtures_trinity", "step_kimi_linear_48b_a3b_train_2x8k.jaxpr.txt.gz"),
                   "rt") as f:
        before = f.read()
    toy = dict(config=kimi_tests.TINY, traffic=kimi_tests.TINY_TRAFFIC)
    assert step_jaxpr(BENCH, toy) == before
    assert not any(b.operator.rope for b in BENCH.job("kimi_linear_train_step").model(kimi_tests.TINY).blocks
                   if isinstance(b.operator, LatentAttention))


def test_eight_shares_add_up_to_the_uncut_layer():
    """The eight ranks' outputs of one expert layer (8 of 64 experts each, as
    in the cell; the shared experts counted once) add up to the uncut
    64-expert reference layer, and their routed rows are its rows."""
    cfg = {**CFG, "n_routed_experts": 64, "num_hidden_layers": 2}
    with jax.default_matmul_precision("highest"):
        p = ref.init_params(jax.random.key(4), cfg, init_std=0.2, bias_std=0.05)["blocks"][1]["ffn"]
        u = jax.random.normal(jax.random.key(2), (2, 40, 48))
        whole, rows = jax.jit(lambda p, u: ref.experts(p, u, cfg))(p, u)
        total, counted = jnp.zeros_like(whole), []
        for lo in range(0, 64, 8):
            rank = MoE(48, 64, hidden_dim=24, top_k=6, gated=True, scoring="sigmoid", expert_bias=True,
                       routed_scaling=2.446, dispatch="sorted", experts_held=range(lo, lo + 8),
                       shared_dim=48 if lo == 0 else None, rows_bound=480)
            mine = {**p, **{n: p[n][lo:lo + 8] for n in ("w1", "w2", "w3")}}
            if lo:
                mine.pop("shared")
            part, stats = jax.jit(rank.apply_with_stats)(mine, u)
            cut, _ = jax.jit(lambda p, u, lo=lo: ref.experts(
                p, u, {**cfg, "num_experts_routed": 64, "experts_held": [lo, lo + 8]}, shared=lo == 0))(mine, u)
            close(part, cut)
            assert int(stats["dropped"]) == 0
            total = total + part
            counted.append(stats["rows"])
        close(total, whole)
        np.testing.assert_array_equal(jnp.concatenate(counted), rows)
        assert int(rows.sum()) == 2 * 40 * 6


# ---------------------------------------------------------------------- #
# the whole model
# ---------------------------------------------------------------------- #
_reference_step = jax.jit(lambda params, tokens: ref.loss_and_grads(params, tokens, CFG))
GROUPS = {"embedding", "head", "norms", "router", "selection_bias", "experts", "shared_expert", "dense_ffn",
          "operator_0", "operator_1", "operator_2"}


def test_the_programs_tree_is_the_references(setup):
    model, params, _ = setup
    own = model.init(jax.random.key(7))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert shape_of(own) == shape_of(params)
    mask = model.decay_mask(own)
    assert all(bool(m) == ref.decays(path) for path, m in jax.tree_util.tree_flatten_with_path(mask)[0])
    assert [b.operator.rope for b in model.blocks] == [True] * 3
    assert not any(b.operator.rope for b in build(rope_kinds=()).blocks)
    # the default leaves latent attention without positions, as Kimi's layers are
    kimi = PatternLM(32, 32, ["mla"], num_heads=2, kv_rank=8, qk_nope_dim=8, qk_shared_dim=4, v_dim=8, ffn_dim=48)
    assert not kimi.blocks[0].operator.rope and kimi.blocks[0].operator.eps == 1e-5


def test_logits_loss_and_gradients_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply)(params, tokens)
        close(logits, jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens))
        assert len(stats) == 2 and all(int(s["dropped"]) == 0 for s in stats)
        loss = lambda p: model.next_token_loss(p, tokens, train=True, block_rows=32)  # noqa: E731
        (value, routing), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        want, rows, want_grads = _reference_step(params, tokens)
        assert abs(float(value) - float(want)) < 1e-5 * float(want)
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
            moved_from = jax.tree.map(jnp.copy, mine)
            mine, state, loss, (rows, dropped) = step(mine, state, batch, batch)
            assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss) and int(dropped) == 0
            np.testing.assert_array_equal(rows, jnp.stack(want_rows))
            moved = ref.group_norms(jax.tree.map(jnp.subtract, mine, moved_from))
            for name, norm in ref.group_norms(jax.tree.map(jnp.subtract, theirs, before)).items():
                assert abs(float(moved[name]) - float(norm)) <= 2e-3 * float(norm) + 1e-12, (i, name)
        jax.tree.map(lambda a, b: close(a, b, 1e-4), mine, theirs)


@pytest.mark.parametrize("control", ["bfloat16", "no_rope", "program_no_rope"])
def test_the_controls_are_told_apart(setup, control):
    """The reference's products in bfloat16, the reference without its
    rotation, and the program built with ``rope=False`` on its ``"mla"``
    layers: each is another model than the reference's, by far more than the
    tolerance."""
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens)
        if control == "program_no_rope":
            other = jax.jit(build(rope_kinds=()).apply)(params, tokens)[0]
        else:
            lower = {"bfloat16": {"product_dtype": jnp.bfloat16}, "no_rope": {"no_rope": True}}[control]
            other = jax.jit(lambda p, t: ref.logits(p, t, CFG, **lower))(params, tokens)
        assert float(jnp.max(jnp.abs(other - want))) > 10 * TOL * float(jnp.max(jnp.abs(want)))


def test_the_reference_works_in_blocks_without_changing_a_number(setup, monkeypatch):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        q, k = (jax.random.normal(jax.random.key(i), (40, 24)) for i in range(2))
        v = jax.random.normal(jax.random.key(2), (40, 12))
        whole = ref._attend(q, k, v, None)
        monkeypatch.setattr(ref, "ROWS", 10)
        close(ref._attend(q, k, v, None), whole, 1e-6)
        monkeypatch.setattr(ref, "ROWS", 16)  # does not divide 40: one block
        close(ref._attend(q, k, v, None), whole, 1e-6)
        monkeypatch.setattr(ref, "ROWS", 2048)
        whole = ref.loss(params, tokens, CFG)[0]
        monkeypatch.setattr(ref, "HEAD_ROWS", 8)
        assert abs(float(ref.loss(params, tokens, CFG)[0]) - float(whole)) < 1e-6 * float(whole)


def test_the_reference_is_plain_and_the_benchmarks_copy_is_this_file():
    other = os.path.join(os.path.dirname(HERE), "chipbench", "references", "moonlight.py")
    assert filecmp.cmp(os.path.join(HERE, "moonlight_reference.py"), other, shallow=False)
    with open(other, encoding="utf-8") as fh:
        source = fh.read()
    assert "import heat_tpu" not in source and "from heat_tpu" not in source and "pallas" not in source
    assert "x[..., 0::2], x[..., 1::2]" in source and "Departures" in source and "1e-20" in source
