"""The ``kimi_linear_48b_a3b_train_2x8k`` cell: its job kind end to end at a toy
size on the CPU (the same test as the other cells'), ``work()`` against
arithmetic by hand, the configuration against the catalog row of the published
``config.json``, a check that refuses a lower-precision control and a dropped
row, and the readers this cell adds on a small synthetic trace.

The cell's toy sizes enter ``test_chipbench_jobs.CELLS`` here, at import: every
test module is imported at collection, in every worker, before any test runs,
and that table is read only when a test runs."""

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
from chipbench.harness import manifest, runner  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402
from heat_tpu.core.communication import Communication  # noqa: E402

BENCH = manifest.Manifest(REPO)
CELL = "kimi_linear_48b_a3b_train_2x8k"
JOB = BENCH.job("kimi_linear_train_step")
CONFIG, TRAFFIC = BENCH.config(BENCH.cell(CELL)), BENCH.traffic(BENCH.cell(CELL))
E = tr.Event
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "vocab_size": 128,
    "num_attention_heads": 2, "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "linear_attn_config": {"head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4,
                           "kda_layers": [1], "full_attn_layers": [2]},
    "layer_types": ["kda", "mla"], "first_k_dense_replace": 1, "kda_gate_rank": 8, "kda_chunk": 16,
    "num_experts": 2, "num_experts_routed": 8, "experts_held": [0, 2], "num_experts_per_token": 2,
    "num_shared_experts": 1, "expert_rows_bound": 128, "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "activation_dtype": "float32", "init_std": 0.02,
    "expert_bias_std": 0.1,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "warmup_steps": 10},
}
TINY_TRAFFIC = {"job": "kimi_linear_train_step", "sequences": 2, "sequence_length": 32,
                "zipf_exponent": 1.0, "check_steps": 2, "warmup_jobs": 1, "traced_jobs": 1}
jobs_tests.CELLS[CELL] = dict(config=TINY, traffic=TINY_TRAFFIC)

# moonshotai/Kimi-Linear-48B-A3B-Instruct's config.json as the catalog row has it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_tiny_end_to_end(trace):
    jobs_tests.test_cell_tiny_end_to_end(CELL, trace)


def test_the_configuration_keeps_every_published_width():
    entry = BENCH._named("configs", "kimi_linear_48b_a3b_ep32")
    assert entry["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value and CONFIG["reduced"][key], key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    # inside the one nested group that changed, only the two lists of layers did
    lin = CONFIG["linear_attn_config"]
    assert {k: lin[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")} == {
        "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
    kept = sorted(lin["kda_layers"] + lin["full_attn_layers"])
    assert kept == [1, 2, 3, 4, 5] and len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 5
    assert CONFIG["layer_types"] == ["kda" if n in lin["kda_layers"] else "mla" for n in kept]
    assert set(lin["kda_layers"]) <= set(PUBLISHED["linear_attn_config"]["kda_layers"])
    assert set(lin["full_attn_layers"]) <= set(PUBLISHED["linear_attn_config"]["full_attn_layers"])
    assert CONFIG["layer_types"][1:].count("kda") == 3 * CONFIG["layer_types"][1:].count("mla")
    lo, hi = CONFIG["experts_held"]
    assert hi - lo == CONFIG["num_experts"] == 8 and CONFIG["num_experts_routed"] == 256
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["expert_rows_bound"] == 4 * (2 * 8192 * 8 * 8 // 256) and "32 chips" in CONFIG["deployment"]
    assert "no row of a held expert is dropped" in CONFIG["guarantees"]
    assert TRAFFIC["check_steps"] == TRAFFIC["warmup_jobs"] + 1
    assert (TRAFFIC["sequences"], TRAFFIC["sequence_length"]) == (2, 8192)


def test_work_is_the_published_arithmetic():
    work = JOB.work(CONFIG, TRAFFIC, 1)
    p = JOB.matmul_parameters(CONFIG)
    assert p == {
        "kda": 3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32,
        "mla": 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304,
        "dense_ffn": 63_700_992, "expert": 7_077_888, "router": 589_824, "head": 47_185_920}
    tokens = 2 * 8192
    per_token = 4 * p["kda"] + p["mla"] + p["dense_ffn"] + p["head"] + 4 * (p["router"] + p["expert"])
    experts = 6 * (tokens * 8 * 8 // 256) * p["expert"] * 4   # 4,096 rows a layer expected
    attention = 3 * 8192 ** 2 * (192 + 128) * 32 * 2          # forward S^2 (d_qk + d_v) a head and sequence
    kda = 21 * 128 ** 2 * 32 * tokens * 4
    assert work["flop"] == 6 * tokens * per_token + experts + attention + kda
    assert work["flop"] == pytest.approx(37.8e12, rel=2e-3)
    assert 6 * tokens * 4 * p["kda"] == pytest.approx(15.5e12, rel=5e-3)
    kernels = work["kernels"]
    assert kernels["moe_experts"]["flop"] == experts == pytest.approx(0.7e12, rel=1e-2)
    assert kernels["flash_attention"]["flop"] == attention == pytest.approx(4.1e12, rel=1e-2)
    assert kernels["kda"]["flop"] == kda == pytest.approx(0.72e12, rel=1e-2)
    # q, k, v, o bfloat16 and g, beta float32, forward and their cotangents: memory-bound at the v5e's peaks
    assert kernels["kda"]["bytes"] == 4 * tokens * 32 * 2 * (4 * 128 * 2 + 129 * 4)
    assert kernels["kda"]["bytes"] / 819e9 > 2 * kernels["kda"]["flop"] / 197e12
    assert kernels["flash_attention"]["bytes"] == tokens * 32 * 2 * (2 * 192 + 2 * 128) * 2
    assert work["bytes"] == 28 * 602_434_432
    assert work["derived"] == {"tokens_per_job": tokens, "steps_per_job": 1}
    assert {k["scope"] for k in kernels.values()} == {"ht.moe.experts", "ht.attention", "ht.kda"}


def test_the_model_at_the_published_widths_has_the_stated_parameters():
    shapes = jax.eval_shape(JOB.model(CONFIG).init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == JOB.parameters(CONFIG) == 602_434_432
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    blocks = shapes["blocks"]
    assert count(blocks[0]["operator"]) == pytest.approx(39.51e6, rel=1e-3)
    assert count(blocks[3]["operator"]) == pytest.approx(29.11e6, rel=1e-3)
    assert count(blocks[0]["ffn"]) == 63_700_992 and count(blocks[1]["ffn"]) == pytest.approx(64.29e6, rel=1e-3)
    ffn = blocks[1]["ffn"]
    assert ffn["router"].shape == (2304, 256) and ffn["expert_bias"].shape == (256,)
    assert ffn["w1"].shape == (8, 2304, 1024) and ffn["shared"]["w2"]["weight"].shape == (2304, 1024)
    kda, mla = blocks[0]["operator"], blocks[3]["operator"]
    assert kda["in_proj"]["weight"].shape == (3 * 4096, 2304) and kda["conv"]["weight"].shape == (3 * 4096, 4)
    assert kda["A_log"].shape == (32,) and kda["dt_bias"].shape == (4096,) and kda["o_norm"]["weight"].shape == (128,)
    assert kda["f_a"]["weight"].shape == (128, 2304) and kda["g_b"]["weight"].shape == (4096, 128)
    assert mla["q_proj"]["weight"].shape == (32 * 192, 2304) and mla["kv_a_proj"]["weight"].shape == (576, 2304)
    assert mla["kv_b_proj"]["weight"].shape == (32 * 256, 512) and mla["out_proj"]["weight"].shape == (2304, 4096)
    assert shapes["head"]["weight"].shape == shapes["embed"]["weight"].shape == (20480, 2304)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(shapes))
    want = jax.eval_shape(lambda key: JOB.reference.init_params(key, JOB.reference_config(CONFIG)), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, want)


def _run(steps, config=TINY):
    comm = Communication(Mesh(np.asarray(jax.devices()[:1]), ("x",)), "x")
    state = JOB.setup(config, TINY_TRAFFIC, 3, comm)
    out = None
    for _ in range(steps):
        out = jax.block_until_ready(JOB.job(state))
    return state, out


def test_the_check_passes_and_a_lower_precision_control_fails():
    state, out = _run(2)
    ok, facts = JOB.check(state, out)
    assert ok and facts["steps_compared"] == 2 and state.params is None
    assert facts["loss_err"] < 1e-5 and facts["grad_norm_err"] < 1e-3 and facts["routed_rows_err"] == 0
    assert facts["update_err"] < 1e-3 and facts["moment_err"] < 1e-3 and facts["decay_err"] < 1e-2
    assert facts["update_norms_step0"]["selection_bias"] == 0 < facts["update_norms_step0"]["router"]
    assert set(facts["grad_norms_step0"]) == {
        "embedding", "head", "norms", "router", "experts", "shared_expert", "dense_ffn", "selection_bias",
        "operator_0", "operator_1"}
    # the control of the chip runs: the reference's products one format below bfloat16
    state, out = _run(2)
    ok, lowered = JOB.compare(state, out, product_dtype=jnp.float8_e4m3fn)
    assert not ok and lowered["grad_norm_err"] > max(JOB.LIMITS["grad_norm_err"], 100 * facts["grad_norm_err"])


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


def test_the_check_starts_from_the_references_own_draw():
    state, _ = _run(0)
    want = jax.jit(lambda key: JOB.reference.init_params(
        key, JOB.reference_config(TINY), TINY["init_std"], TINY["expert_bias_std"]))(jax.random.key(3))
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    a_log = state.params["blocks"][0]["operator"]["A_log"]
    assert 0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16.0)


def _step_trace():
    """Two traced jobs of 100 ns: the delta rule forward, recomputed and
    backward, its projections around it, convolution, gate, shared expert."""
    fwd, bwd = "jvp(jit(run))/ht.kda.proj", "transpose(jvp(jit(run)))/checkpoint/rematted_computation/ht.kda.proj"
    ops = []
    for t in (0, 100):
        ops += [E("fusion.1", t, t + 10, fwd),
                E("fusion.2", t + 10, t + 15, fwd + "/ht.kda.conv"),
                E("fusion.3", t + 15, t + 20, fwd + "/ht.kda.gate"),
                E("fusion.4", t + 20, t + 40, fwd + "/ht.kda/while/body"),
                E("fusion.5", t + 40, t + 50, bwd + "/ht.kda/while/body"),
                E("fusion.6", t + 50, t + 55, bwd),
                E("fusion.7", t + 55, t + 70, "transpose(jvp(jit(run)))/ht.moe.shared"),
                E("fusion.8", t + 70, t + 80, "ht.kdax")]
    chip = tr.DeviceTrace(0, [E("jit_step(1)", 0, 80), E("jit_step(1)", 100, 180)], ops)
    return tr.Trace([chip], [E("bench.job", 0, 100), E("bench.job", 100, 200)])


def test_the_new_readers_on_a_step_trace():
    trace = _step_trace()
    expected, counted = 6 * 3 * 8 * 4 * 500, 6 * 3 * 8 * 4 * 1000
    work = {"flop": 197e12 * 40e-9 + expected, "bytes": 1, "derived": {}, "kernels": {
        "moe_experts": {"flop": expected, "bytes": 1, "scope": "ht.moe.experts"},
        "kda": {"flop": 1, "bytes": 819e9 * 3e-9, "scope": "ht.kda"}}}
    ctx = runner.Context({}, {"hidden_size": 8, "moe_intermediate_size": 4, "num_experts": 2}, {}, 1,
                         work, [0.1, 0.1], trace=trace, peaks=PEAKS, counters={"moe_rows": 2000})
    read = lambda name: BENCH.reader(name)(ctx)  # noqa: E731
    assert read("kda_ms") == pytest.approx(30e-6)          # forward, recomputed and backward
    assert read("kda_proj_ms") == pytest.approx(15e-6)     # what the scope holds itself, not what lies further in
    assert read("kda_conv_ms") == pytest.approx(5e-6) and read("kda_gate_ms") == pytest.approx(5e-6)
    assert read("moe_shared_ms") == pytest.approx(15e-6)
    assert read("kda_roofline") == pytest.approx(100 * 3e-9 / 30e-9)   # memory-bound: the bytes decide
    assert read(f"{CELL}_mfu") == pytest.approx(100 * (40e-9 + counted / 197e12) / 80e-9)
    empty = runner.Context({}, {}, {}, 1, {"flop": 1, "bytes": 1, "derived": {}}, [0.1], trace=None, peaks=PEAKS)
    for name in ("kda_ms", "kda_roofline", "kda_proj_ms", "kda_conv_ms", "kda_gate_ms", "moe_shared_ms", f"{CELL}_mfu"):
        assert BENCH.reader(name)(empty) is None  # nothing to read: no number, no error
    listed = {m["name"] for m in BENCH.metrics("per_layer", CELL)}
    assert {"kda_ms", "kda_roofline", "kda_proj_ms", "kda_conv_ms", "kda_gate_ms", "moe_shared_ms", f"{CELL}_mfu",
            "attention_ms", "flash_attention_roofline", "moe_experts_ms", "moe_experts_roofline", "moe_dispatch_ms",
            "head_loss_ms", "optimizer_ms", "moe_rows_per_job", "moe_dropped_rows_per_job",
            "moe_load_max_over_mean"} <= listed
    assert not {"shortconv_ms", "shortconv_roofline", "lfm2_8b_a1b_train_4x8k_mfu"} & listed


def test_the_traffic_and_configuration_files_are_json_the_harness_finds():
    assert BENCH.traffic(BENCH.cell(CELL))["job"] == "kimi_linear_train_step"
    assert BENCH.cell(CELL)["chips"] == 1 and json.dumps(CONFIG)
