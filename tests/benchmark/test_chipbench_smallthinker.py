"""The ``smallthinker_21b_a3b_train_1x16k`` cell: its job kind end to end at a toy
size on the CPU (the same test as the other cells'), ``work()`` against
arithmetic by hand, the configuration against the catalog row of the published
``config.json``, a check that refuses a lower-precision control, both controls
of this model's own and a dropped row, and the readers this cell adds on a
small synthetic trace.

The cell's toy sizes enter ``test_chipbench_jobs.CELLS`` here, at import, as
``test_chipbench_kimi_linear.py`` enters its own."""

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
CELL = "smallthinker_21b_a3b_train_1x16k"
JOB = BENCH.job("smallthinker_train_step")
CONFIG, TRAFFIC = BENCH.config(BENCH.cell(CELL)), BENCH.traffic(BENCH.cell(CELL))
E = tr.Event
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY = {
    "hidden_size": 48, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "moe_ffn_hidden_size": 24, "moe_intermediate_size": 24, "vocab_size": 128,
    "moe_num_primary_experts": 4, "num_experts": 4, "num_experts_routed": 8, "experts_held": [0, 4],
    "moe_num_active_primary_experts": 3, "norm_topk_prob": True, "moe_primary_router_apply_softmax": True,
    "rope_layout": [0, 1, 1], "sliding_window_layout": [0, 1, 1], "sliding_window_size": 8,
    "layer_types": ["global_attention", "sliding_attention", "sliding_attention"],
    "rope_theta": 1500000, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "expert_rows_bound": 192, "activation_dtype": "float32", "init_std": 0.02,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "warmup_steps": 10},
}
TINY_TRAFFIC = {"job": "smallthinker_train_step", "sequences": 2, "sequence_length": 32,
                "zipf_exponent": 1.0, "check_steps": 2, "warmup_jobs": 1, "traced_jobs": 1}
jobs_tests.CELLS[CELL] = dict(config=TINY, traffic=TINY_TRAFFIC)

# PowerInfer/SmallThinker-21BA3B-Instruct's config.json as the catalog row has it
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_tiny_end_to_end(trace):
    jobs_tests.test_cell_tiny_end_to_end(CELL, trace)


def test_the_configuration_keeps_every_published_width():
    entry = BENCH._named("configs", "smallthinker_21b_a3b_ep4")
    assert entry["reduced"] == ["num_hidden_layers", "rope_layout", "sliding_window_layout",
                                "moe_num_primary_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value and CONFIG["reduced"][key], key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    # one whole period, the first four published layers
    assert CONFIG["rope_layout"] == PUBLISHED["rope_layout"][:4] == [0, 1, 1, 1]
    assert CONFIG["sliding_window_layout"] == PUBLISHED["sliding_window_layout"][:4]
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 4
    # every alias carries its published key's value and says whose it is
    assert CONFIG["moe_intermediate_size"] == CONFIG["moe_ffn_hidden_size"] == 768
    lo, hi = CONFIG["experts_held"]
    assert hi - lo == CONFIG["num_experts"] == CONFIG["moe_num_primary_experts"] == 16
    assert CONFIG["num_experts_routed"] == PUBLISHED["moe_num_primary_experts"] == 64
    assert CONFIG["layer_types"] == [JOB.KINDS[w] for w in CONFIG["sliding_window_layout"]]
    assert set(CONFIG["aliases"]) == {"why", "moe_intermediate_size", "num_experts", "num_experts_routed",
                                      "experts_held", "layer_types"}
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # a buffer row for every token-slot: no routing, however uneven, can drop a row
    assert CONFIG["expert_rows_bound"] == 16384 * 6 and "4 chips" in CONFIG["deployment"]
    assert CONFIG["embedding_std"] == 1.0 and "embedding" in CONFIG["assumed"]["initialisation"]
    assert "no row of a held expert is dropped" in CONFIG["guarantees"]
    assert "router_input" in CONFIG["assumed"] and "before the input norm" in CONFIG["assumed"]["router_input"]
    assert TRAFFIC["check_steps"] == TRAFFIC["warmup_jobs"] + 1
    assert (TRAFFIC["sequences"], TRAFFIC["sequence_length"]) == (1, PUBLISHED["max_position_embeddings"])
    with pytest.raises(ValueError, match="alias"):
        JOB.model({**CONFIG, "moe_intermediate_size": 1024})


def test_work_is_the_published_arithmetic():
    work = JOB.work(CONFIG, TRAFFIC, 1)
    p = JOB.matmul_parameters(CONFIG)
    assert p == {"attention": 2 * 2560 * 3584 + 2 * 2560 * 512, "expert": 3 * 2560 * 768,
                 "router": 2560 * 64, "head": 18992 * 2560}
    tokens = 16384
    per_token = 4 * (p["attention"] + p["router"]) + p["head"]
    experts = 6 * (tokens * 6 * 16 // 64) * p["expert"] * 4          # 24,576 rows a layer expected
    causal = sum(i + 1 for i in range(tokens))                        # pairs a head: j <= i
    inside = sum(min(i + 1, 4096) for i in range(tokens))             # and i - j < 4096
    assert (causal, inside) == (134_225_920, 58_722_304) and inside / causal == pytest.approx(0.4375, abs=2e-4)
    assert JOB.attended_pairs(tokens) == causal and JOB.attended_pairs(tokens, 4096) == inside
    assert JOB.attended_pairs(100, 100) == JOB.attended_pairs(100, 500) == JOB.attended_pairs(100) == 5050
    global_flop, window_flop = 6 * causal * 256 * 28, 6 * inside * 256 * 28 * 3
    assert work["flop"] == 6 * tokens * per_token + experts + global_flop + window_flop
    assert work["flop"] == pytest.approx(29.9e12, rel=5e-3)
    assert 6 * tokens * per_token == pytest.approx(13.1e12, rel=5e-3)
    kernels = work["kernels"]
    assert kernels["moe_experts"]["flop"] == experts == pytest.approx(3.5e12, rel=1e-2)
    assert kernels["flash_attention"]["flop"] == global_flop == pytest.approx(5.8e12, rel=1e-2)
    assert kernels["window_attention"]["flop"] == window_flop == pytest.approx(7.6e12, rel=1e-2)
    assert (global_flop + window_flop) / work["flop"] == pytest.approx(0.45, abs=0.01)
    assert kernels["flash_attention"]["bytes"] * 3 == kernels["window_attention"]["bytes"]
    assert kernels["flash_attention"]["bytes"] == tokens * 2 * 2 * 128 * (28 + 4) * 2
    assert work["bytes"] == 28 * 559_290_880
    assert work["derived"] == {"tokens_per_job": tokens, "steps_per_job": 1}
    assert {k: v["scope"] for k, v in kernels.items()} == {
        "moe_experts": "ht.moe.experts", "flash_attention": "ht.attention",
        "window_attention": "ht.attention.window"}


def test_the_model_at_the_published_widths_has_the_stated_parameters():
    shapes = jax.eval_shape(JOB.model(CONFIG).init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == JOB.parameters(CONFIG) == 559_290_880
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    blocks = shapes["blocks"]
    assert len(blocks) == 4 and all(count(b["operator"]) == 20_971_520 for b in blocks)
    assert all(count(b["ffn"]) == 16 * 5_898_240 + 163_840 for b in blocks)
    ffn, op = blocks[1]["ffn"], blocks[1]["operator"]
    assert set(ffn) == {"router", "w1", "w2", "w3"} and ffn["router"].shape == (2560, 64)
    assert ffn["w1"].shape == ffn["w3"].shape == (16, 2560, 768) and ffn["w2"].shape == (16, 768, 2560)
    assert set(op) == {"in_proj_weight", "out_proj"}  # no normalisation of queries or keys
    assert op["in_proj_weight"].shape == (3584 + 2 * 512, 2560) and op["out_proj"]["weight"].shape == (2560, 3584)
    assert shapes["head"]["weight"].shape == shapes["embed"]["weight"].shape == (18992, 2560)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(shapes))
    want = jax.eval_shape(lambda key: JOB.reference.init_params(key, CONFIG), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, want)
    lm = JOB.model(CONFIG)
    windows = [b.operator.window for b in lm.blocks]
    assert windows == [None, 4096, 4096, 4096] and [b.operator.rope for b in lm.blocks] == [False, True, True, True]
    assert all(b.route_on_input and b.ffn.activation == "relu" and b.ffn.scoring == "softmax" for b in lm.blocks)


def test_the_census_of_the_cells_flash_blocks():
    """A head's forward sweep at the cell's shapes: 16 Q blocks of 1,024; the
    global layer walks all 16 K/V blocks of each, a windowed layer 5."""
    blocks = JOB.flash_blocks(CONFIG, TRAFFIC)
    assert blocks["global_attention"] == {"interior": 120, "edge": 16, "dead": 120}
    assert blocks["sliding_attention"] == {"interior": 42, "edge": 28, "dead": 10}
    assert JOB.flash_blocks(TINY, TINY_TRAFFIC) == {
        "global_attention": {"interior": 0, "edge": 1, "dead": 0},
        "sliding_attention": {"interior": 0, "edge": 1, "dead": 0}}


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
    assert set(facts["grad_norms_step0"]) == {
        "embedding", "head", "norms", "router", "experts", "operator_0", "operator_1", "operator_2"}
    assert set(facts["flash_blocks"]) == {"global_attention", "sliding_attention"}
    # the control of the chip runs: the reference's products one format below bfloat16
    state, out = _run(2)
    ok, lowered = JOB.compare(state, out, product_dtype=jnp.float8_e4m3fn)
    assert not ok and lowered["grad_norm_err"] > max(JOB.LIMITS["grad_norm_err"], 100 * facts["grad_norm_err"])


@pytest.mark.parametrize("control", ["no_window", "rope_everywhere"])
def test_the_check_refuses_the_models_own_controls(control):
    """The reference without its window, or with rotary positions on its
    global layer, is another model: the comparison must say so.  Drawn so
    that the scores have the unit variance they have at the published widths
    (48 x 0.15^2 is about 2560 x 0.02^2): with near-zero scores attention
    averages whatever the positions are."""
    state, out = _run(2, {**TINY, "init_std": 0.15})
    ok, facts = JOB.compare(state, out, **{control: True})
    assert not ok and facts["grad_norm_err"] > JOB.LIMITS["grad_norm_err"]


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
    want = jax.jit(lambda key: JOB.reference.init_params(key, TINY, TINY["init_std"]))(jax.random.key(3))
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    # the embedding's own scale, where the configuration states one: that leaf alone differs
    state, _ = _run(0, {**TINY, "embedding_std": 1.0})
    assert float(jnp.std(state.params["embed"]["weight"])) == pytest.approx(1.0, rel=0.05)
    for name in ("head", "blocks", "norm"):
        for a, b in zip(jax.tree.leaves(state.params[name]), jax.tree.leaves(want[name])):
            np.testing.assert_array_equal(a, b)


def _step_trace():
    """Two traced jobs of 100 ns: a global layer's and a windowed layer's
    attention, forward, recomputed and backward, inside their projections."""
    fwd, bwd = "jvp(jit(run))/ht.attention.proj", "transpose(jvp(jit(run)))/checkpoint/rematted_computation/ht.attention.proj"
    ops = []
    for t in (0, 100):
        ops += [E("fusion.1", t, t + 10, fwd),
                E("_flash_kernel", t + 10, t + 20, fwd + "/ht.attention/_flash_kernel"),
                E("_flash_kernel", t + 20, t + 25, fwd + "/ht.attention.window/_flash_kernel"),
                E("_flash_bwd_dq_kernel", t + 25, t + 40, bwd + "/ht.attention.window/_flash_bwd_dq_kernel"),
                E("_flash_bwd_dq_kernel", t + 40, t + 70, bwd + "/ht.attention/_flash_bwd_dq_kernel"),
                E("fusion.2", t + 70, t + 80, "ht.attention.windowed")]
    chip = tr.DeviceTrace(0, [E("jit_step(1)", 0, 80), E("jit_step(1)", 100, 180)], ops)
    return tr.Trace([chip], [E("bench.job", 0, 100), E("bench.job", 100, 200)])


def test_the_new_readers_on_a_step_trace():
    trace = _step_trace()
    expected, counted = 6 * 3 * 8 * 4 * 500, 6 * 3 * 8 * 4 * 1000
    work = {"flop": 197e12 * 40e-9 + expected, "bytes": 1, "derived": {}, "kernels": {
        "moe_experts": {"flop": expected, "bytes": 1, "scope": "ht.moe.experts"},
        "flash_attention": {"flop": 197e12 * 10e-9, "bytes": 1, "scope": "ht.attention"},
        "window_attention": {"flop": 197e12 * 5e-9, "bytes": 1, "scope": "ht.attention.window"}}}
    ctx = runner.Context({}, {"hidden_size": 8, "moe_intermediate_size": 4, "num_experts": 2}, {}, 1,
                         work, [0.1, 0.1], trace=trace, peaks=PEAKS, counters={"moe_rows": 2000})
    read = lambda name: BENCH.reader(name)(ctx)  # noqa: E731
    # a scope is matched as a whole component: the window's kernels are not the global layer's
    assert read("window_attention_ms") == pytest.approx(20e-6)
    assert read("attention_ms") == pytest.approx(40e-6)
    assert read("window_attention_roofline") == pytest.approx(100 * 5e-9 / 20e-9)
    assert read("flash_attention_roofline") == pytest.approx(100 * 10e-9 / 40e-9)
    assert read(f"{CELL}_mfu") == pytest.approx(100 * (40e-9 + counted / 197e12) / 80e-9)
    empty = runner.Context({}, {}, {}, 1, {"flop": 1, "bytes": 1, "derived": {}}, [0.1], trace=None, peaks=PEAKS)
    for name in ("window_attention_ms", "window_attention_roofline", f"{CELL}_mfu"):
        assert BENCH.reader(name)(empty) is None  # nothing to read: no number, no error
    # a program without the window's scope (the parent's): the readers find nothing and do not raise
    parent = runner.Context({}, {}, {}, 1, {**work, "kernels": {}}, [0.1], trace=trace, peaks=PEAKS)
    assert BENCH.reader("window_attention_roofline")(parent) is None
    listed = {m["name"] for m in BENCH.metrics("per_layer", CELL)}
    assert {"window_attention_ms", "window_attention_roofline", f"{CELL}_mfu", "attention_ms",
            "flash_attention_roofline", "moe_experts_ms", "moe_experts_roofline", "moe_dispatch_ms",
            "head_loss_ms", "optimizer_ms", "moe_rows_per_job", "moe_dropped_rows_per_job",
            "moe_load_max_over_mean"} <= listed
    assert not {"shortconv_ms", "kda_ms", "moe_shared_ms", "lfm2_8b_a1b_train_4x8k_mfu",
                "kimi_linear_48b_a3b_train_2x8k_mfu"} & listed
    for other in ("lfm2_8b_a1b_train_4x8k", "kimi_linear_48b_a3b_train_2x8k"):
        assert not {"window_attention_ms", "window_attention_roofline", f"{CELL}_mfu"} & {
            m["name"] for m in BENCH.metrics("per_layer", other)}


def test_the_traffic_and_configuration_files_are_json_the_harness_finds():
    assert BENCH.traffic(BENCH.cell(CELL))["job"] == "smallthinker_train_step"
    assert BENCH.cell(CELL)["chips"] == 1 and json.dumps(CONFIG)
