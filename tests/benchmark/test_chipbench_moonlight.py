"""The ``moonlight_16b_a3b_train_4x8k`` cell: its job kind end to end at a toy
size on the CPU (the same test as the other cells'), ``work()`` against
arithmetic by hand, the configuration against the catalog row of the published
``config.json``, a check that refuses a lower-precision control, the reference
without its rotation, a dropped row and a step that trains on half of its
sequences, and the two readers this cell adds with
the identity of its ``*_ms`` metrics on a step written out by hand.

The cell's toy sizes enter ``test_chipbench_jobs.CELLS`` here, at import, as
``test_chipbench_trinity.py`` enters its own."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import test_chipbench_jobs as jobs_tests  # noqa: E402  (beside this file)
import test_chipbench_smallthinker  # noqa: E402  (beside this file)
import test_chipbench_trinity  # noqa: E402  (enters its cell's toy sizes, and the two it imports theirs)
from chipbench.harness import manifest, runner  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402
from heat_tpu.core.communication import Communication  # noqa: E402

BENCH = manifest.Manifest(REPO)
CELL = "moonlight_16b_a3b_train_4x8k"
JOB = BENCH.job("moonlight_train_step")
CONFIG, TRAFFIC = BENCH.config(BENCH.cell(CELL)), BENCH.traffic(BENCH.cell(CELL))
E = tr.Event
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY = {
    "hidden_size": 48, "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "kv_lora_rank": 24, "intermediate_size": 96, "moe_intermediate_size": 24,
    "vocab_size": 128, "num_hidden_layers": 3, "layer_types": ["mla"] * 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "num_experts": 4, "num_experts_routed": 16, "experts_held": [0, 4],
    "num_experts_per_tok": 6, "n_shared_experts": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "rope_theta": 50000, "rms_norm_eps": 1e-5, "kv_a_layernorm_eps": 1e-6, "tie_word_embeddings": False,
    "expert_rows_bound": 384, "loss_block_rows": 24, "activation_dtype": "float32", "init_std": 0.02,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "warmup_steps": 10},
}
TINY_TRAFFIC = {"job": "moonlight_train_step", "sequences": 2, "sequence_length": 32,
                "zipf_exponent": 1.0, "check_steps": 2, "warmup_jobs": 1, "traced_jobs": 1}
jobs_tests.CELLS[CELL] = dict(config=TINY, traffic=TINY_TRAFFIC)

# moonshotai/Moonlight-16B-A3B's config.json as the catalog row has it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 11264, "kv_lora_rank": 512, "max_position_embeddings": 8192, "model_type": "deepseek_v3",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_tiny_end_to_end(trace):
    jobs_tests.test_cell_tiny_end_to_end(CELL, trace)


def test_the_configuration_keeps_every_published_width():
    entry = BENCH._named("configs", "moonlight_16b_a3b_ep8")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"] == "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value and CONFIG["reduced"][key], key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    # the dense layer and four with experts: the period is one layer
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 5 and set(CONFIG["layer_types"]) == {"mla"}
    lo, hi = CONFIG["experts_held"]
    assert hi - lo == CONFIG["num_experts"] == CONFIG["n_routed_experts"] == 8
    assert CONFIG["num_experts_routed"] == PUBLISHED["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"] and "8 chips" in CONFIG["deployment"]
    assert set(CONFIG["aliases"]) == {"why", "num_experts", "num_experts_routed", "experts_held", "layer_types"}
    assert {"loss", "routing", "expert_bias", "initialisation", "kv_a_layernorm_eps", "optimizer",
            "activation_dtype", "documents", "expert_rows_bound", "batch", "parameters"} <= set(CONFIG["assumed"])
    assert "1e-20" in CONFIG["assumed"]["routing"] and "seq_aux" in CONFIG["assumed"]["loss"]
    assert "no row of a held expert is dropped" in CONFIG["guarantees"]
    for key in ("source", "deployment", "guarantees", "published", "reduced", "assumed"):
        assert CONFIG[key], key
    # a buffer row for every token-slot: no routing, however uneven, can drop a row
    assert CONFIG["expert_rows_bound"] == 4 * 8192 * 6 and CONFIG["loss_block_rows"] == 8192
    assert TRAFFIC["check_steps"] == TRAFFIC["warmup_jobs"] + 1
    assert (TRAFFIC["sequences"], TRAFFIC["sequence_length"]) == (4, PUBLISHED["max_position_embeddings"])
    for wrong in ({"num_experts": 16}, {"experts_held": [0, 16]}, {"layer_types": ["mla"] * 4}):
        with pytest.raises(ValueError, match="alias"):
            JOB.model({**CONFIG, **wrong})


def test_work_is_the_published_arithmetic():
    work = JOB.work(CONFIG, TRAFFIC, 1)
    p = JOB.matmul_parameters(CONFIG)
    assert p == {"attention": 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048,
                 "dense_ffn": 3 * 2048 * 11264, "expert": 3 * 2048 * 1408, "shared": 3 * 2048 * 2816,
                 "router": 2048 * 64, "head": 20480 * 2048}
    assert p["attention"] == 13_762_560 and p["expert"] == 8_650_752
    tokens = 4 * 8192
    per_token = 5 * p["attention"] + p["dense_ffn"] + 4 * (p["router"] + p["shared"]) + p["head"]
    experts = 6 * (tokens * 6 * 8 // 64) * p["expert"] * 4            # 3,072 rows an expert expected
    pairs = sum(i + 1 for i in range(8192))                            # a head's causal pairs: j <= i
    attention = 6 * (192 + 128) * pairs * 16 * 4 * 5
    assert work["flop"] == 6 * tokens * per_token + experts + attention
    assert work["flop"] == pytest.approx(75.1e12, rel=5e-3)
    assert attention == pytest.approx(20.6e12, rel=5e-3)
    # latent attention's projections and its sweeps: the largest part of the step
    assert (6 * tokens * 5 * p["attention"] + attention) / work["flop"] == pytest.approx(0.46, abs=0.01)
    kernels = work["kernels"]
    assert kernels["moe_experts"]["flop"] == experts == pytest.approx(5.10e12, rel=1e-2)
    assert kernels["flash_attention"]["flop"] == attention
    assert kernels["flash_attention"]["bytes"] == 5 * tokens * 16 * 2 * (2 * 192 + 2 * 128) * 2
    assert work["bytes"] == 28 * 568_484_608
    assert work["derived"] == {"tokens_per_job": tokens, "steps_per_job": 1}
    assert {k: v["scope"] for k, v in kernels.items()} == {"moe_experts": "ht.moe.experts",
                                                           "flash_attention": "ht.attention"}


def test_the_model_at_the_published_widths_has_the_stated_parameters():
    lm = JOB.model(CONFIG)
    shapes = jax.eval_shape(lm.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == JOB.parameters(CONFIG) == 568_484_608
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    blocks = shapes["blocks"]
    assert len(blocks) == 5 and all(count(b["operator"]) == 13_762_560 + 512 for b in blocks)
    assert count(blocks[0]["ffn"]) == 69_206_016
    assert all(count(b["ffn"]) == 8 * 8_650_752 + 17_301_504 + 131_072 + 64 for b in blocks[1:])
    op = blocks[1]["operator"]
    assert op["q_proj"]["weight"].shape == (16 * 192, 2048) and op["kv_a_proj"]["weight"].shape == (576, 2048)
    assert op["kv_b_proj"]["weight"].shape == (16 * 256, 512) and op["out_proj"]["weight"].shape == (2048, 16 * 128)
    ffn = blocks[1]["ffn"]
    assert ffn["w1"].shape == (8, 2048, 1408) and ffn["shared"]["w1"]["weight"].shape == (2816, 2048)
    assert shapes["head"]["weight"].shape == shapes["embed"]["weight"].shape == (20480, 2048)
    want = jax.eval_shape(lambda key: JOB.reference.init_params(key, CONFIG), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, want)
    assert all(b.operator.rope and b.operator.rope_base == 50000 and b.operator.eps == 1e-6 for b in lm.blocks)
    assert not lm.blocks[0].routed and all(
        b.ffn.scoring == "sigmoid" and b.ffn.routed_scaling == 2.446 and b.ffn.top_k == 6 for b in lm.blocks[1:])


def _run(steps, config=TINY):
    comm = Communication(Mesh(np.asarray(jax.devices()[:1]), ("x",)), "x")
    state = JOB.setup(config, TINY_TRAFFIC, 3, comm)
    out = None
    for _ in range(steps):
        out = jax.block_until_ready(JOB.job(state))
    return state, out


def test_the_check_passes_and_the_controls_fail():
    state, out = _run(2)
    ok, facts = JOB.check(state, out)
    assert ok and facts["steps_compared"] == 2 and state.params is None
    assert facts["loss_err"] < 1e-5 and facts["grad_norm_err"] < 1e-3 and facts["routed_rows_err"] == 0
    assert facts["update_err"] < 1e-3 and facts["moment_err"] < 1e-3 and facts["decay_err"] < 1e-2
    assert set(facts["grad_norms_step0"]) == {
        "embedding", "head", "norms", "router", "selection_bias", "experts", "shared_expert", "dense_ffn",
        "operator_0", "operator_1", "operator_2"}
    # the control of the chip runs: the reference's products one format below bfloat16;
    # the same state replays again, as the chip runs' controls do after the sound check
    ok, lowered = JOB.compare(state, out, product_dtype=jnp.float8_e4m3fn)
    assert not ok and lowered["grad_norm_err"] > max(JOB.LIMITS["grad_norm_err"], 100 * facts["grad_norm_err"])


def test_the_check_refuses_the_reference_without_its_rotation():
    """Drawn so that the scores have the unit variance they have at the
    published widths (48 x 0.15^2 is about 2048 x 0.02^2)."""
    state, out = _run(2, {**TINY, "init_std": 0.15})
    ok, facts = JOB.compare(state, out, no_rope=True)
    failed = [k for k, limit in JOB.LIMITS.items() if facts[k] > limit]
    assert not ok and "loss_err" in failed, failed


def test_the_check_refuses_a_dropped_row(monkeypatch):
    """A bound too small for the rows routed: the run is not correct, by
    ``dropped_rows`` alone (the replay is stood in for by the program's own
    readings, so every other number compared reads 0)."""
    state, out = _run(2, {**TINY, "expert_rows_bound": 8})
    assert JOB.counters(state)["moe_dropped_rows"] > 0

    def own_readings(s, steps, **lower):
        return [{"loss": loss, **{k: v for k, v in stats.items() if k != "dropped"},
                 "params_squared": {name: 1.0 for name in stats["grad_norms"]}}
                for loss, stats in jax.device_get(s.log[:steps])]

    monkeypatch.setattr(JOB, "replay", own_readings)
    ok, facts = JOB.check(state, out)
    assert not ok and facts["dropped_rows"] > 0 == JOB.LIMITS["dropped_rows"]
    assert [k for k, limit in JOB.LIMITS.items() if facts[k] > limit] == ["dropped_rows"]


def _half_loss(config, model=JOB.model):
    """The job's model, but its loss covers the first half of the sequences;
    its routing (rows, drops) is still the whole batch's."""
    lm = model(config)
    whole = lm.next_token_loss

    def half(params, tokens, **kw):
        loss, _ = whole(params, tokens[: tokens.shape[0] // 2], **kw)
        return loss, whole(jax.lax.stop_gradient(params), tokens, **kw)[1]

    object.__setattr__(lm, "next_token_loss", half)
    return lm


@pytest.mark.parametrize("form", ["loss", "batch"])
def test_the_check_refuses_a_step_that_trains_on_half_the_sequences(monkeypatch, form):
    """Two planted faults of a batch of several sequences: the loss over half
    of them with the routing of all (no count of rows can tell), and the step
    handed half of the batch (the rows routed halve too)."""
    if form == "loss":
        monkeypatch.setattr(JOB, "model", _half_loss)
    state = JOB.setup(TINY, TINY_TRAFFIC, 3, Communication(Mesh(np.asarray(jax.devices()[:1]), ("x",)), "x"))
    whole_batch = state.batch
    if form == "batch":
        state.batch = lambda i: whole_batch(i)[: TINY_TRAFFIC["sequences"] // 2]
    out = None
    for _ in range(2):
        out = jax.block_until_ready(JOB.job(state))
    state.batch = whole_batch
    ok, facts = JOB.check(state, out)
    failed = {k for k, limit in JOB.LIMITS.items() if facts[k] > limit}
    assert not ok and {"grad_norm_err", "moment_err", "decay_err"} <= failed, failed
    assert ("routed_rows_err" in failed) == (form == "batch"), failed


def test_the_check_starts_from_the_references_own_draw():
    """The reference's draw, each expert layer's selection bias evened out on
    the seed's batch that no step trains on."""
    state, _ = _run(0)
    drawn = jax.jit(lambda key: JOB.reference.init_params(key, TINY, TINY["init_std"]))(jax.random.key(3))
    tokens = state.batch(JOB.EVEN_OUT_BATCH)
    want = jax.jit(lambda p: JOB.reference.even_out_bias(p, tokens, TINY))(drawn)
    got = jax.tree_util.tree_flatten_with_path(state.params)[0]
    for (path, a), b, c in zip(got, jax.tree.leaves(want), jax.tree.leaves(drawn)):
        np.testing.assert_array_equal(a, b)
        if "expert_bias" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(a, c)
    assert all(float(jnp.abs(b["ffn"]["expert_bias"]).max()) > 0 for b in state.params["blocks"][1:])


def _loads(ref, params, tokens, cfg):
    """Each expert layer's rows of every expert, by the reference's routing."""
    x, loads = params["embed"]["weight"][tokens], []
    for layer, p in enumerate(params["blocks"]):
        h = ref._attention_sublayer(p, x, cfg, None, False)
        if layer >= cfg["first_k_dense_replace"]:
            u = ref.rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"]).reshape(-1, h.shape[-1])
            loads.append(np.bincount(np.asarray(ref.route(p["ffn"], u, cfg)[1]).ravel(), minlength=64))
        x, _ = ref._ffn_sublayer(p, h, layer, cfg, None)
    return loads


def test_the_evened_out_biases_even_out_the_loads():
    """64 experts, 8 held, 256 wide, 512 tokens a batch: with the published
    bias of 0 a few experts take most rows and the draw decides how many the
    held ones get; after ``even_out_bias`` on another batch of the seed, the
    fullest expert holds less and the held experts get near their share."""
    cfg = {**TINY, "hidden_size": 256, "num_experts_routed": 64, "n_routed_experts": 8, "num_experts": 8,
           "experts_held": [0, 8], "expert_rows_bound": 4096}
    batch = JOB._batches(cfg, {**TINY_TRAFFIC, "sequences": 4, "sequence_length": 128}, 3)
    share = 4 * 128 * 6 * 8 // 64
    for seed in range(3):
        drawn = JOB.reference.init_params(jax.random.key(seed), cfg, init_std=cfg["init_std"])
        plain = _loads(JOB.reference, drawn, batch(0), cfg)
        even = _loads(JOB.reference, JOB._draw(cfg, batch)(jax.random.key(seed)), batch(0), cfg)
        for a, b in zip(plain, even):
            assert b.max() / b.mean() < min(2.6, a.max() / a.mean()), (a, b)
            assert abs(b[:8].sum() - share) < 0.2 * share, b


# ---------------------------------------------------------------------- #
# the two readers, and the identity of the cell's ``*_ms`` metrics
# ---------------------------------------------------------------------- #
FWD, BWD = "jvp(ht.lm.block)/jit(run)", "transpose(jvp(ht.lm.block))/jit(run)/checkpoint"
AGAIN = BWD + "/rematted_computation"
# (operation, nanoseconds, scope) of one job of this model; a second program runs beside the step
STEP = [
    ("fusion.1", 2, "jvp(ht.lm.cast)"), ("fusion.2", 4, "jvp(ht.lm.embed)"),
    ("fusion.3", 1, FWD + "/ht.lm.cast"), ("fusion.4", 3, FWD + "/ht.lm.norm"),
    ("fusion.5", 10, FWD + "/ht.attention.proj"),
    ("fusion.6", 6, FWD + "/ht.attention.proj/ht.attention.rope"),
    ("_flash_kernel", 20, FWD + "/ht.attention.proj/ht.attention/_flash_kernel"),
    ("fusion.7", 2, FWD + "/ht.lm.norm"),
    ("fusion.8", 6, FWD + "/ht.mlp"),
    ("fusion.10", 3, FWD + "/ht.moe.route"), ("fusion.11", 4, FWD + "/while/body/jit(_sorted_rows)/ht.moe.dispatch"),
    ("ragged-dot-none.3", 15, FWD + "/while/body/jit(_sorted_rows)"), ("fusion.12", 5, FWD + "/ht.moe.shared"),
    ("convert.1", 2, FWD),
    ("fusion.13", 4, AGAIN + "/ht.attention.proj/ht.attention.rope"),
    ("fusion.14", 9, BWD + "/ht.attention.proj/ht.attention.rope"),
    ("fusion.15", 12, "jvp(ht.lm.head_loss)/while/body/checkpoint"),
    ("fusion.16", 13, "transpose(jvp(ht.lm.head_loss))/while/body/checkpoint/rematted_computation"),
    ("fusion.17", 5, "ht.optim.update"), ("copy-done.4", 2, ""),
]
BATCH = ("fusion.1", 3, "jit(searchsorted)/while/body")
WANT = {"mla_rope_ms": 19, "attention_proj_ms": 10, "attention_ms": 20, "norm_ms": 5, "mlp_ms": 6,
        "moe_dispatch_ms": 7, "moe_experts_ms": 15, "moe_shared_ms": 5, "block_other_ms": 2, "head_loss_ms": 25,
        "optimizer_ms": 5, "embed_ms": 4, "cast_ms": 3, "unscoped_ms": 5, "recompute_ms": 17}


def _step_trace():
    ops, modules, jobs = [], [], []
    for start in (0, 1000):
        t = start
        modules.append(E("jit_batch(1)", t, t + BATCH[1]))
        for name, ns, scope in [BATCH] + STEP:
            ops.append(E(name, t, t + ns, scope))
            t += ns
        modules.append(E("jit_step(2)", start + BATCH[1], t))
        jobs.append(E("bench.job", start, start + 1000))
    return tr.Trace([tr.DeviceTrace(0, modules, ops)], jobs)


def _read(name, trace, **kw):
    return BENCH.reader(name)(runner.Context({}, {}, {}, 1, {}, [], trace=trace, **kw))


@pytest.mark.parametrize("name", list(WANT))
def test_the_cells_readers_on_a_step_written_out_by_hand(name):
    assert _read(name, _step_trace()) == pytest.approx(WANT[name] * 1e-6)


def test_the_cells_metrics_by_innermost_scope_and_the_unscoped_rest_are_the_busy_time():
    """``test_chipbench_scope_coverage.py``'s identity on this cell's metric
    list: every ``*_ms`` metric the manifest lists for the cell but the one
    that cuts across the layers; each operation counted once, none left out."""
    trace = _step_trace()
    parts = [m["name"] for m in BENCH.metrics("per_layer", CELL)
             if m["unit"] == "ms" and m["name"] not in {"recompute_ms", "collective_ms_per_job"}]
    assert sorted(parts) == sorted(set(WANT) - {"recompute_ms"}) and len(parts) == 14
    total = sum(_read(name, trace) for name in parts)
    busy_s, _ = tr.busy_seconds(trace)
    assert total * 1e-3 == pytest.approx(busy_s / 2)
    # the rotation is the projections' no longer: with it the projections' reader would count it twice
    assert _read("mla_rope_ms", trace) + _read("attention_proj_ms", trace) == pytest.approx(29e-6)


def test_the_new_readers_find_nothing_where_there_is_nothing():
    expected, counted = 6 * 3 * 8 * 4 * 500, 6 * 3 * 8 * 4 * 1000
    trace = _step_trace()
    busy = sum(ns for _, ns, _ in [BATCH] + STEP)
    work = {"flop": 197e12 * 40e-9 + expected, "bytes": 1, "derived": {}, "kernels": {
        "moe_experts": {"flop": expected, "bytes": 1, "scope": "ht.moe.experts"}}}
    ctx = runner.Context({}, {"hidden_size": 8, "moe_intermediate_size": 4, "num_experts": 2}, {}, 1,
                         work, [0.1, 0.1], trace=trace, peaks=PEAKS, counters={"moe_rows": 2000})
    assert BENCH.reader(f"{CELL}_mfu")(ctx) == pytest.approx(100 * (40e-9 + counted / 197e12) / (busy * 1e-9))
    empty = runner.Context({}, {}, {}, 1, {"flop": 1, "bytes": 1, "derived": {}}, [0.1], trace=None, peaks=PEAKS)
    for name in ("mla_rope_ms", f"{CELL}_mfu"):
        assert BENCH.reader(name)(empty) is None  # nothing to read: no number, no error
    # a program without the rotation's scope (another model's step, Kimi's NoPE latent attention among them)
    for other in (test_chipbench_trinity._step_trace(), test_chipbench_smallthinker._step_trace()):
        assert _read("mla_rope_ms", other) is None
    listed = {m["name"] for m in BENCH.metrics("per_layer", CELL)}
    assert {"mla_rope_ms", f"{CELL}_mfu", "attention_ms", "flash_attention_roofline", "attention_proj_ms",
            "moe_experts_ms", "moe_experts_roofline", "moe_dispatch_ms", "moe_shared_ms", "mlp_ms", "head_loss_ms",
            "optimizer_ms", "embed_ms", "norm_ms", "cast_ms", "block_other_ms", "unscoped_ms", "recompute_ms",
            "moe_rows_per_job", "moe_dropped_rows_per_job", "moe_load_max_over_mean"} <= listed
    assert not {"shortconv_ms", "kda_ms", "window_attention_ms", "attention_gate_ms",
                "trinity_mini_26b_a3b_train_1x32k_mfu"} & listed
    for other_cell in test_chipbench_trinity.ACCEPTED + ("trinity_mini_26b_a3b_train_1x32k",):
        assert not {"mla_rope_ms", f"{CELL}_mfu"} & {m["name"] for m in BENCH.metrics("per_layer", other_cell)}


def test_the_traffic_and_configuration_files_are_json_the_harness_finds():
    assert BENCH.traffic(BENCH.cell(CELL))["job"] == "moonlight_train_step"
    assert BENCH.cell(CELL)["chips"] == 1 and json.dumps(CONFIG)
