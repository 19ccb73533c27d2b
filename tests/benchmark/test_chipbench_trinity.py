"""The ``trinity_mini_26b_a3b_train_1x32k`` cell: its job kind end to end at a toy
size on the CPU (the same test as the other cells'), ``work()`` against
arithmetic by hand, the configuration against the catalog row of the published
``config.json``, a check that refuses a lower-precision control, the three
controls of this model's own and a dropped row, the two readers this cell adds
and the identity of its ``*_ms`` metrics on a step written out by hand, and the
three accepted model cells' programs at their toy shapes against the jaxprs
e393382 traced.

The cell's toy sizes enter ``test_chipbench_jobs.CELLS`` here, at import, as
``test_chipbench_smallthinker.py`` enters its own."""

import gzip
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import test_chipbench_jobs as jobs_tests  # noqa: E402  (beside this file)
import test_chipbench_kimi_linear  # noqa: E402, F401  (enters its cell's toy sizes)
import test_chipbench_smallthinker  # noqa: E402, F401  (enters its cell's toy sizes)
from chipbench.harness import manifest, runner  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402
from heat_tpu.core.communication import Communication  # noqa: E402

BENCH = manifest.Manifest(REPO)
CELL = "trinity_mini_26b_a3b_train_1x32k"
JOB = BENCH.job("trinity_train_step")
CONFIG, TRAFFIC = BENCH.config(BENCH.cell(CELL)), BENCH.traffic(BENCH.cell(CELL))
FIXTURES = os.path.join(REPO, "tests", "fixtures_trinity")
E = tr.Event
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY = {
    "hidden_size": 48, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 24, "vocab_size": 128, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
    "num_dense_layers": 1, "num_experts": 4, "num_experts_routed": 16, "experts_held": [0, 4],
    "num_experts_per_tok": 4, "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "mup_enabled": True, "tie_word_embeddings": False, "expert_rows_bound": 256, "loss_block_rows": 24,
    "activation_dtype": "float32", "init_std": 0.02, "expert_bias_std": 0.05,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "warmup_steps": 10},
}
TINY_TRAFFIC = {"job": "trinity_train_step", "sequences": 2, "sequence_length": 32,
                "zipf_exponent": 1.0, "check_steps": 2, "warmup_jobs": 1, "traced_jobs": 1}
jobs_tests.CELLS[CELL] = dict(config=TINY, traffic=TINY_TRAFFIC)

# arcee-ai/Trinity-Mini's config.json as the catalog row has it
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_tiny_end_to_end(trace):
    jobs_tests.test_cell_tiny_end_to_end(CELL, trace)


def test_the_configuration_keeps_every_published_width():
    entry = BENCH._named("configs", "trinity_mini_26b_a3b_ep16")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value and CONFIG["reduced"][key], key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    # the second dense layer and one whole period: entries 1 to 5 of the published list
    assert CONFIG["layer_types"] == PUBLISHED["layer_types"][1:6]
    assert CONFIG["layer_types"][1:].count("full_attention") * 3 == CONFIG["layer_types"][1:].count("sliding_attention")
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 5 and CONFIG["num_dense_layers"] == 1
    lo, hi = CONFIG["experts_held"]
    assert hi - lo == CONFIG["num_experts"] == 8 and CONFIG["num_experts_routed"] == PUBLISHED["num_experts"]
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"] and "16 chips" in CONFIG["deployment"]
    assert {"attention_gate", "qk_norm", "positions", "norms", "embedding_scale", "loss", "routing",
            "expert_bias", "expert_rows_bound"} <= set(CONFIG["assumed"])
    assert "1e-20" in CONFIG["assumed"]["routing"] and "auxiliary" in CONFIG["assumed"]["loss"]
    assert not any("PLACEHOLDER" in str(v) for v in CONFIG["assumed"].values())
    assert "no row of a held expert is dropped" in CONFIG["guarantees"]
    for key in ("source", "deployment", "guarantees", "published", "reduced", "assumed"):
        assert CONFIG[key], key
    # a buffer row for every token-slot: no routing, however uneven, can drop a row
    assert CONFIG["expert_rows_bound"] == 32768 * 8 and CONFIG["loss_block_rows"] == 8192
    assert TRAFFIC["check_steps"] == TRAFFIC["warmup_jobs"] + 1
    assert (TRAFFIC["sequences"], TRAFFIC["sequence_length"]) == (1, 32768)
    with pytest.raises(ValueError, match="experts held"):
        JOB.model({**CONFIG, "num_experts": 16})


def test_work_is_the_published_arithmetic():
    work = JOB.work(CONFIG, TRAFFIC, 1)
    p = JOB.matmul_parameters(CONFIG)
    assert p == {"attention": 2 * 2048 * 4096 + 2 * 2048 * 512, "gate": 2048 * 4096, "dense_ffn": 3 * 2048 * 6144,
                 "expert": 3 * 2048 * 1024, "router": 2048 * 128, "head": 25024 * 2048}
    tokens = 32768
    per_token = 5 * (p["attention"] + p["gate"]) + p["dense_ffn"] + 4 * (p["router"] + p["expert"]) + p["head"]
    assert per_token == pytest.approx(251.5e6, rel=1e-3)
    experts = 6 * (tokens * 8 * 8 // 128) * p["expert"] * 4          # 16,384 rows a layer expected
    causal = sum(i + 1 for i in range(tokens))                        # pairs a head: j <= i
    inside = sum(min(i + 1, 2048) for i in range(tokens))             # and i - j < 2048
    assert (causal, inside) == (536_887_296, 65_012_736) and inside / causal == pytest.approx(0.121, abs=1e-3)
    assert JOB.attended_pairs(tokens) == causal and JOB.attended_pairs(tokens, 2048) == inside
    global_flop, window_flop = 6 * causal * 256 * 32, 6 * inside * 256 * 32 * 4  # four sliding layers, the dense one among them
    assert work["flop"] == 6 * tokens * per_token + experts + global_flop + window_flop
    assert work["flop"] == pytest.approx(91.1e12, rel=5e-3)
    assert 6 * tokens * per_token == pytest.approx(49.4e12, rel=5e-3)
    kernels = work["kernels"]
    assert kernels["moe_experts"]["flop"] == experts == pytest.approx(2.47e12, rel=1e-2)
    assert kernels["flash_attention"]["flop"] == global_flop == pytest.approx(26.4e12, rel=1e-2)
    assert kernels["window_attention"]["flop"] == window_flop == pytest.approx(12.8e12, rel=1e-2)
    assert (global_flop + window_flop) / work["flop"] == pytest.approx(0.43, abs=0.01)
    assert kernels["flash_attention"]["bytes"] * 4 == kernels["window_attention"]["bytes"]
    assert kernels["flash_attention"]["bytes"] == tokens * 2 * 2 * 128 * (32 + 4) * 2
    assert work["bytes"] == 28 * 504_147_712
    assert work["derived"] == {"tokens_per_job": tokens, "steps_per_job": 1}
    assert {k: v["scope"] for k, v in kernels.items()} == {
        "moe_experts": "ht.moe.experts", "flash_attention": "ht.attention",
        "window_attention": "ht.attention.window"}


def test_the_model_at_the_published_widths_has_the_stated_parameters():
    lm = JOB.model(CONFIG)
    shapes = jax.eval_shape(lm.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == JOB.parameters(CONFIG) == 504_147_712
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    blocks = shapes["blocks"]
    assert len(blocks) == 5 and all(count(b["operator"]) == 27_262_976 + 256 for b in blocks)
    assert count(blocks[0]["ffn"]) == 37_748_736
    assert all(count(b["ffn"]) == 9 * 6_291_456 + 262_144 + 128 for b in blocks[1:])
    ffn, op = blocks[1]["ffn"], blocks[1]["operator"]
    assert set(ffn) == {"router", "expert_bias", "shared", "w1", "w2", "w3"} and ffn["router"].shape == (2048, 128)
    assert ffn["w1"].shape == ffn["w3"].shape == (8, 2048, 1024) and ffn["w2"].shape == (8, 1024, 2048)
    assert set(op) == {"in_proj_weight", "out_proj", "q_norm", "k_norm", "gate_proj"}
    assert op["in_proj_weight"].shape == (4096 + 2 * 512, 2048) and op["gate_proj"]["weight"].shape == (4096, 2048)
    assert shapes["head"]["weight"].shape == shapes["embed"]["weight"].shape == (25024, 2048)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(shapes))
    want = jax.eval_shape(lambda key: JOB.reference.init_params(key, CONFIG), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, want)
    assert [b.operator.window for b in lm.blocks] == [2048, 2048, None, 2048, 2048]
    assert [b.operator.rope for b in lm.blocks] == [True, True, False, True, True]
    assert all(b.operator.gate and b.operator.qk_norm and b.operator_out_norm is not None for b in lm.blocks)
    assert lm.embedding_scale == 2048 ** 0.5 and not lm.blocks[0].routed
    assert all(b.ffn.scoring == "sigmoid" and b.ffn.routed_scaling == 2.826 and b.ffn.shared is not None
               for b in lm.blocks[1:])


def test_the_census_of_the_cells_flash_blocks():
    """A head's forward sweep at the cell's shapes: 32 Q blocks of 1,024; the
    global layer walks all 32 K/V blocks of each, a windowed layer 3, of which
    the one the window's lower edge crosses and the diagonal one are edge blocks."""
    blocks = JOB.flash_blocks(CONFIG, TRAFFIC)
    assert blocks["full_attention"] == {"interior": 496, "edge": 32, "dead": 496}
    assert blocks["sliding_attention"] == {"interior": 31, "edge": 62, "dead": 3}
    assert JOB.head_blocks(CONFIG, TRAFFIC) == 4 and JOB.head_blocks(TINY, TINY_TRAFFIC) == 3
    assert JOB.flash_blocks(TINY, TINY_TRAFFIC) == {
        "full_attention": {"interior": 0, "edge": 1, "dead": 0},
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
        "embedding", "head", "norms", "router", "selection_bias", "experts", "shared_expert", "dense_ffn",
        "operator_0", "operator_1", "operator_2", "operator_3"}
    assert set(facts["flash_blocks"]) == {"full_attention", "sliding_attention"} and facts["head_blocks"] == 3
    # the control of the chip runs: the reference's products one format below bfloat16
    state, out = _run(2)
    ok, lowered = JOB.compare(state, out, product_dtype=jnp.float8_e4m3fn)
    assert not ok and lowered["grad_norm_err"] > max(JOB.LIMITS["grad_norm_err"], 100 * facts["grad_norm_err"])


@pytest.mark.parametrize("control", ["no_gate", "no_window", "no_embedding_scale"])
def test_the_check_refuses_the_models_own_controls(control):
    """The reference without its gate, without its window or with its
    embedding unscaled is another model: the comparison must say so.  Drawn so
    that the scores have the unit variance they have at the published widths
    (48 x 0.15^2 is about 2048 x 0.02^2)."""
    state, out = _run(2, {**TINY, "init_std": 0.15, "embedding_std": 0.02})
    ok, facts = JOB.compare(state, out, **{control: True})
    failed = [k for k, limit in JOB.LIMITS.items() if facts[k] > limit]
    assert not ok and len(failed) >= 2 and "loss_err" in failed, failed


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
        key, TINY, TINY["init_std"], TINY["expert_bias_std"]))(jax.random.key(3))
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    assert float(jnp.std(state.params["blocks"][1]["ffn"]["expert_bias"])) == pytest.approx(0.05, rel=0.5)


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
    ("_flash_kernel", 9, FWD + "/ht.attention.proj/ht.attention.window/_flash_kernel"),
    ("_flash_kernel", 20, FWD + "/ht.attention.proj/ht.attention/_flash_kernel"),
    ("fusion.6", 7, FWD + "/ht.attention.proj/ht.attention.gate"),
    ("fusion.7", 2, FWD + "/ht.lm.norm"),
    ("fusion.8", 6, FWD + "/ht.mlp"), ("fusion.9", 1, FWD + "/ht.lm.norm"),
    ("fusion.10", 3, FWD + "/ht.moe.route"), ("fusion.11", 4, FWD + "/while/body/jit(_sorted_rows)/ht.moe.dispatch"),
    ("ragged-dot-none.3", 15, FWD + "/while/body/jit(_sorted_rows)"), ("fusion.12", 5, FWD + "/ht.moe.shared"),
    ("convert.1", 2, FWD),
    ("fusion.13", 8, AGAIN + "/ht.attention.proj/ht.attention.gate"),
    ("fusion.14", 11, BWD + "/ht.attention.proj/ht.attention.gate"),
    ("fusion.15", 12, "jvp(ht.lm.head_loss)/while/body/checkpoint"),
    ("fusion.16", 13, "transpose(jvp(ht.lm.head_loss))/while/body/checkpoint/rematted_computation"),
    ("fusion.17", 5, "ht.optim.update"), ("copy-done.4", 2, ""),
]
BATCH = ("fusion.1", 3, "jit(searchsorted)/while/body")
WANT = {"attention_gate_ms": 26, "attention_proj_ms": 10, "attention_ms": 20, "window_attention_ms": 9,
        "norm_ms": 6, "mlp_ms": 6, "moe_dispatch_ms": 7, "moe_experts_ms": 15, "moe_shared_ms": 5,
        "block_other_ms": 2, "head_loss_ms": 25, "optimizer_ms": 5, "embed_ms": 4, "cast_ms": 3,
        "unscoped_ms": 5, "recompute_ms": 21}


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
    assert sorted(parts) == sorted(set(WANT) - {"recompute_ms"}) and len(parts) == 15
    total = sum(_read(name, trace) for name in parts)
    busy_s, _ = tr.busy_seconds(trace)
    assert total * 1e-3 == pytest.approx(busy_s / 2)
    # the gate is the projections' no longer: with it the projections' reader would count it twice
    assert _read("attention_gate_ms", trace) + _read("attention_proj_ms", trace) == pytest.approx(36e-6)


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
    for name in ("attention_gate_ms", f"{CELL}_mfu"):
        assert BENCH.reader(name)(empty) is None  # nothing to read: no number, no error
    # a program without the gate's scope (another model's step): nothing, and no error
    other = test_chipbench_smallthinker._step_trace()
    assert _read("attention_gate_ms", other) is None
    listed = {m["name"] for m in BENCH.metrics("per_layer", CELL)}
    assert {"attention_gate_ms", f"{CELL}_mfu", "window_attention_ms", "window_attention_roofline", "attention_ms",
            "flash_attention_roofline", "moe_experts_ms", "moe_experts_roofline", "moe_dispatch_ms", "moe_shared_ms",
            "mlp_ms", "head_loss_ms", "optimizer_ms", "moe_rows_per_job", "moe_dropped_rows_per_job",
            "moe_load_max_over_mean"} <= listed
    assert not {"shortconv_ms", "kda_ms", "lfm2_8b_a1b_train_4x8k_mfu", "kimi_linear_48b_a3b_train_2x8k_mfu",
                "smallthinker_21b_a3b_train_1x16k_mfu"} & listed
    for other_cell in ("lfm2_8b_a1b_train_4x8k", "kimi_linear_48b_a3b_train_2x8k", "smallthinker_21b_a3b_train_1x16k"):
        assert not {"attention_gate_ms", f"{CELL}_mfu"} & {m["name"] for m in BENCH.metrics("per_layer", other_cell)}


def test_the_traffic_and_configuration_files_are_json_the_harness_finds():
    assert BENCH.traffic(BENCH.cell(CELL))["job"] == "trinity_train_step"
    assert BENCH.cell(CELL)["chips"] == 1 and json.dumps(CONFIG)


# ---------------------------------------------------------------------- #
# the three accepted model cells' programs
# ---------------------------------------------------------------------- #
ACCEPTED = ("lfm2_8b_a1b_train_4x8k", "kimi_linear_48b_a3b_train_2x8k", "smallthinker_21b_a3b_train_1x16k")


def step_jaxpr(bench, toy) -> str:
    """Value and gradients of a model cell's loss at its toy shapes, as text
    (source lines and addresses stripped)."""
    job = bench.job(toy["traffic"]["job"])
    lm = job.model(toy["config"])
    params = jax.eval_shape(lm.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((toy["traffic"]["sequences"], toy["traffic"]["sequence_length"]), jnp.int32)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: job._loss(lm.apply(p, t, train=True), t), has_aux=True))(params, tokens))
    return re.sub(r" at 0x[0-9a-f]+", "", re.sub(r"/[^\s:]+\.py:\d+", "FILE", text))


@pytest.mark.parametrize("cell", ACCEPTED)
def test_the_accepted_cells_programs_are_the_ones_they_were(cell):
    """With the gate, the output norms, the embedding's scale and ``forward=``
    at their defaults the three accepted cells' jobs trace to the jaxprs of
    value and gradients that e393382 traced at these toy shapes."""
    with gzip.open(os.path.join(FIXTURES, f"step_{cell}.jaxpr.txt.gz"), "rt") as f:
        before = f.read()
    assert step_jaxpr(BENCH, jobs_tests.CELLS[cell]) == before
