"""The ``minicpm_sala_9b_train_1x32k`` cell: its job kind end to end at a toy
size on the CPU (the same test as the other cells'), ``work()`` against
arithmetic by hand, the configuration against the catalog row of the published
``config.json`` and the model built from it, a check that refuses a broken
selection, a lower-precision control and the reference without its decay, and
the readers this cell adds on a step written out by hand.

The cell's toy sizes enter ``test_chipbench_jobs.CELLS`` here, at import, as
``test_chipbench_moonlight.py`` enters its own."""

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
import test_chipbench_moonlight  # noqa: E402  (enters its cell's toy sizes, and those it imports theirs)
from chipbench.harness import manifest, runner  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402
from heat_tpu.core.communication import Communication  # noqa: E402

BENCH = manifest.Manifest(REPO)
CELL = "minicpm_sala_9b_train_1x32k"
JOB = BENCH.job("minicpm_sala_train_step")
CONFIG, TRAFFIC = BENCH.config(BENCH.cell(CELL)), BENCH.traffic(BENCH.cell(CELL))
E = tr.Event
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# the published configuration at a toy size: widths, heads and the selection's sizes cut,
# the pattern and every switch as published; 128 tokens are past dense_len
TINY = {**CONFIG, "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16, "intermediate_size": 96,
        "vocab_size": 101, "heads_held": [0, 2], "kv_heads_held": [0, 1], "lightning_heads_held": [0, 2],
        "ffn_held": [0, 48], "lightning_chunk": 16, "activation_dtype": "float32", "loss_block_rows": 48,
        "init_std": 0.1, "dim_model_base": 4,
        "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "init_blocks": 1, "block_size": 16,
                          "window_size": 32, "topk": 5, "dense_len": 64}}
TINY_TRAFFIC = {"job": "minicpm_sala_train_step", "sequences": 2, "sequence_length": 128,
                "zipf_exponent": 1.0, "check_steps": 2, "warmup_jobs": 1, "traced_jobs": 1}
jobs_tests.CELLS[CELL] = dict(config=TINY, traffic=TINY_TRAFFIC)

# openbmb/MiniCPM-SALA's config.json as the catalog row has it (mixer_types aside)
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True, "max_position_embeddings": 524288,
    "model_type": "minicpm_sala", "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2,
    "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
}
MIXERS = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + \
    ["lightning-attn"] * 4 + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_new_cell_tiny_end_to_end(trace):
    jobs_tests.test_cell_tiny_end_to_end(CELL, trace)


def test_the_configuration_keeps_every_published_width():
    entry = BENCH._named("configs", "minicpm_sala_9b_tp2")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types", "vocab_size"]
    assert entry["source"] == CONFIG["source"] == "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
    assert len(MIXERS) == 32 and MIXERS.count("minicpm4") == 8
    for key, value in {**PUBLISHED, "mixer_types": MIXERS}.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value and CONFIG["reduced"][key], key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    # published layers 0 to 3: one sparse layer, then three lightning layers
    assert CONFIG["mixer_types"] == MIXERS[:4] and CONFIG["num_hidden_layers"] == 4
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"] and "2 chips" in CONFIG["deployment"]
    # the share held: one whole selection group, half of every other width
    assert (CONFIG["heads_held"], CONFIG["kv_heads_held"]) == ([0, 16], [0, 1])
    assert CONFIG["lightning_heads_held"] == [0, 16] and CONFIG["ffn_held"] == [0, 8192]
    assert set(CONFIG["held"]) == {"heads_held", "kv_heads_held", "lightning_heads_held", "ffn_held"}
    # MiniCPM4-8B's published sparse_config
    assert CONFIG["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1, "block_size": 64,
                                       "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert {"sparse_config", "selection", "decay", "feature_map", "qk_norm", "output_norm", "gates",
            "mup_denominator", "initialisation", "optimizer", "documents"} <= set(CONFIG["assumed"])
    for key in ("source", "deployment", "guarantees", "published", "reduced", "assumed", "parameters"):
        assert CONFIG[key], key
    assert "629,951,488" in CONFIG["parameters"] and CONFIG["loss_block_rows"] == 8192
    assert (TRAFFIC["sequences"], TRAFFIC["sequence_length"]) == (1, 32768)
    assert TRAFFIC["check_steps"] == TRAFFIC["warmup_jobs"] + 1 == 3 and TRAFFIC["traced_jobs"] == 3


def test_the_model_at_the_published_widths_has_the_stated_parameters():
    lm = JOB.model(CONFIG)
    shapes = jax.eval_shape(lm.init, jax.random.key(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == JOB.parameters(CONFIG) == 629_951_488
    blocks = shapes["blocks"]
    assert count(blocks[0]) == 126_886_144 and all(count(b) == 142_616_832 for b in blocks[1:])
    assert count(shapes["embed"]) == count(shapes["head"]) == 9181 * 4096 == 37_605_376
    op = blocks[0]["operator"]
    assert op["in_proj_weight"].shape == ((16 + 2) * 128, 4096) and op["out_proj"]["weight"].shape == (4096, 2048)
    assert op["gate_proj"]["weight"].shape == (2048, 4096)
    light = blocks[1]["operator"]
    assert light["in_proj"]["weight"].shape == (3 * 2048, 4096) and light["o_norm"]["weight"].shape == (2048,)
    assert blocks[1]["ffn"]["w1"]["weight"].shape == (8192, 4096) and blocks[1]["ffn"]["w2"]["weight"].shape == (4096, 8192)
    want = jax.eval_shape(lambda key: JOB.reference.init_params(key, CONFIG), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, want)
    # 16 bytes a parameter: 10.08 GB, about 63% of a v5e's 16 GB before any activation
    assert 16 * count(shapes) == pytest.approx(10.08e9, rel=1e-3)
    # the scales as published: 1.4 / sqrt(32) on every branch, 256 / 4096 on the head's input
    assert lm.blocks[0].residual_scale == pytest.approx(1.4 / 32 ** 0.5) and lm.head_input_scale == 1 / 16
    assert lm.embedding_scale == 12 and lm.blocks[0].operator.sparse.topk == 64
    # the decays of heads 0 to 15 of 32 at published layer 1
    assert lm.blocks[1].operator.slopes[0] == pytest.approx(2 ** -0.25 * (1 - 1 / 31 + 1e-5))
    assert len(lm.blocks[3].operator.slopes) == 16 and lm.blocks[3].operator.rope and lm.blocks[3].operator.qk_norm


def test_work_is_the_published_arithmetic():
    work = JOB.work(CONFIG, TRAFFIC, 1)
    p = JOB.matmul_parameters(CONFIG)
    assert p == {"sparse": 4096 * 18 * 128 + 2 * 2048 * 4096, "lightning": 5 * 4096 * 2048,
                 "swiglu": 3 * 4096 * 8192, "head": 9181 * 4096}
    tokens = 32768
    # a token past 4,096 keeps 63 whole blocks and its own up to itself; before, every earlier key
    pairs = sum(i + 1 for i in range(4096)) + sum(63 * 64 + i % 64 + 1 for i in range(4096, tokens))
    assert pairs == JOB.kept_pairs(tokens, CONFIG["sparse_config"]) == 124_928_000
    assert pairs / (tokens * (tokens + 1) // 2) == pytest.approx(0.2327, abs=1e-4)
    sparse = 6 * (128 + 128) * pairs * 16
    light = 6 * 2 * 128 * 128 * 16 * tokens * 3
    per_token = p["sparse"] + 3 * p["lightning"] + 4 * p["swiglu"] + p["head"]
    assert work["flop"] == 6 * tokens * per_token + sparse + light
    assert work["flop"] == pytest.approx(119.8e12, rel=1e-3)
    assert sparse == pytest.approx(3.07e12, rel=1e-2) and light == pytest.approx(0.31e12, rel=1e-2)
    kernels = work["kernels"]
    assert kernels["sparse_attention"] == {"flop": sparse, "scope": "ht.sparse_attention",
                                           "bytes": tokens * 2 * 2 * 128 * (16 + 1) * 2}
    assert kernels["lightning"] == {"flop": light, "scope": "ht.lightning", "bytes": 3 * tokens * 2 * 4 * 128 * 16 * 2}
    # lightning is memory-bound at the chip's peaks, the sparse kernel compute-bound
    assert light / PEAKS["bf16_flops_per_s"] < kernels["lightning"]["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert sparse / PEAKS["bf16_flops_per_s"] > kernels["sparse_attention"]["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert work["bytes"] == 28 * 629_951_488 and work["derived"] == {"tokens_per_job": tokens, "steps_per_job": 1}
    # at dense_len and below the triangle is kept whole
    assert JOB.kept_pairs(8192, CONFIG["sparse_config"]) == 8192 * 8193 // 2


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
    # float32 on both sides: the same blocks, the same numbers
    assert facts["select_same_share"] == [1.0, 1.0] and facts["select_margin"] == 0
    assert facts["rows_short"] == facts["kept_pairs_off"] == facts["forced_missing"] == 0
    assert facts["loss_err"] < 1e-5 and facts["grad_norm_err"] < 1e-3
    assert facts["update_err"] < 1e-3 and facts["moment_err"] < 1e-3 and facts["decay_err"] < 1e-2
    assert set(facts["grad_norms_step0"]) == {"embedding", "head", "norms"} | {
        f"{kind}_{i}" for kind in ("operator", "ffn") for i in range(4)}
    assert 0 < facts["kept_share_step0"] < 1
    # the control of the chip runs: the reference's products one format below bfloat16
    ok, lowered = JOB.compare(state, out, product_dtype=jnp.float8_e4m3fn)
    failed = [k for k, limit in JOB.LIMITS.items() if lowered[k] > limit]
    assert not ok and "grad_norm_err" in failed, failed
    # the reference without its decay: a lightning layer that never forgets
    ok, plain = JOB.compare(state, out, no_decay=True)
    assert not ok and "grad_norm_err" in [k for k, limit in JOB.LIMITS.items() if plain[k] > limit]


# the limit each broken selection must fail
BROKEN = {"shifted": "select_margin", "no_window": "forced_missing", "thinned": "rows_short",
          "repeated": "rows_short"}


@pytest.mark.parametrize("fault", list(BROKEN))
def test_the_check_refuses_a_broken_selection(fault):
    """The steps' block choices replaced after the fact: every block one later
    (capped at the row's own), the row's own block left out, every block that
    is not forced left out (-1), or each of those replaced by the row's own."""
    state, out = _run(2)
    sc = TINY["sparse_config"]
    positions = jnp.arange(TINY_TRAFFIC["sequence_length"])[:, None]
    rows = positions // sc["block_size"]
    low = jnp.maximum(positions - sc["window_size"] + 1, 0) // sc["block_size"]
    for _, stats in state.log:
        broken = []
        for sel in stats["selection"]:
            free = (sel >= sc["init_blocks"]) & (sel < low)
            assert jnp.any(free), "no row keeps a block it was not forced to"
            broken.append({"shifted": jnp.where(sel >= 0, jnp.minimum(sel + 1, rows), sel),
                           "no_window": jnp.where(sel == rows, -1, sel),
                           "thinned": jnp.where(free, -1, sel),
                           "repeated": jnp.where(free, rows, sel)}[fault])
        stats["selection"] = broken
    ok, facts = JOB.check(state, out)
    failed = [k for k, limit in JOB.LIMITS.items() if facts[k] > limit]
    assert not ok and BROKEN[fault] in failed, failed
    if fault == "shifted":
        assert "forced_missing" in failed, failed


def test_the_check_refuses_a_kept_count_that_is_not_the_shapes():
    """The counter ``sparse_kept_share`` reads, one pair short on a step."""
    state, out = _run(2)
    stats = state.log[1][1]
    stats["kept_pairs"] = stats["kept_pairs"] - 1
    ok, facts = JOB.check(state, out)
    assert not ok and facts["kept_pairs_off"] == 1 and facts["rows_short"] == 0


# ---------------------------------------------------------------------- #
# the readers this cell adds
# ---------------------------------------------------------------------- #
FWD, BWD = "jvp(ht.lm.block)/jit(run)", "transpose(jvp(ht.lm.block))/jit(run)/checkpoint"
AGAIN = BWD + "/rematted_computation"
# (operation, nanoseconds, scope) of one job of this model; a second program runs beside the step
STEP = [
    ("fusion.1", 2, "jvp(ht.lm.cast)"), ("fusion.2", 4, "jvp(ht.lm.embed)"),
    ("fusion.3", 1, FWD + "/ht.lm.cast"), ("fusion.4", 3, FWD + "/ht.lm.norm"),
    ("fusion.5", 10, FWD + "/ht.attention.proj"),
    ("sort.1", 7, FWD + "/ht.attention.proj/ht.attention/ht.sparse_attention.select"),
    ("_fwd_kernel", 20, FWD + "/ht.attention.proj/ht.attention/ht.sparse_attention/_sparse/_fwd_call"),
    ("fusion.6", 5, FWD + "/ht.attention.proj/ht.attention.gate"),
    ("fusion.7", 2, FWD + "/ht.lm.norm"), ("fusion.8", 6, FWD + "/ht.mlp"),
    ("fusion.9", 8, FWD + "/ht.attention.proj"),
    ("_forward_kernel", 11, FWD + "/ht.attention.proj/ht.lightning/_lightning/_grid_call"),
    ("while.1", 9, FWD + "/ht.attention.proj/ht.lightning/_lightning/while/body"),
    ("fusion.10", 4, FWD + "/ht.attention.proj/ht.lightning.gate"),
    ("convert.1", 2, FWD),
    ("fusion.11", 3, AGAIN + "/ht.attention.proj"),
    ("_bwd_kernel", 40, BWD + "/ht.attention.proj/ht.attention/ht.sparse_attention/_bwd_call"),
    ("fusion.12", 12, "jvp(ht.lm.head_loss)/while/body/checkpoint"),
    ("fusion.13", 5, "ht.optim.update"), ("copy-done.4", 2, ""),
]
BATCH = ("fusion.1", 3, "jit(searchsorted)/while/body")
WANT = {"sparse_attention_ms": 60, "sparse_select_ms": 7, "lightning_ms": 20, "lightning_gate_ms": 4,
        "attention_ms": 67,
        "attention_proj_ms": 21, "attention_gate_ms": 5, "norm_ms": 5, "mlp_ms": 6, "block_other_ms": 2,
        "head_loss_ms": 12, "optimizer_ms": 5, "embed_ms": 4, "cast_ms": 3, "unscoped_ms": 5, "recompute_ms": 3}


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


def test_the_rooflines_share_and_mfu_from_their_counts():
    trace = _step_trace()
    work = JOB.work(CONFIG, TRAFFIC, 1)
    ctx = runner.Context({}, CONFIG, TRAFFIC, 1, work, [0.1, 0.1], trace=trace, peaks=PEAKS,
                         counters={"sparse_kept_pairs": 25.0, "sparse_causal_pairs": 100.0})
    sparse = work["kernels"]["sparse_attention"]["flop"] / PEAKS["bf16_flops_per_s"]
    assert BENCH.reader("sparse_attention_roofline")(ctx) == pytest.approx(100 * sparse / 60e-9)
    light = work["kernels"]["lightning"]["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert BENCH.reader("lightning_roofline")(ctx) == pytest.approx(100 * light / 20e-9)
    busy = sum(ns for _, ns, _ in [BATCH] + STEP) * 1e-9
    assert BENCH.reader(f"{CELL}_mfu")(ctx) == pytest.approx(100 * work["flop"] / PEAKS["bf16_flops_per_s"] / busy)
    assert BENCH.reader("sparse_kept_share")(ctx) == 25.0


def test_the_new_readers_find_nothing_where_there_is_nothing():
    """On another model's step (no sparse or lightning scope), with no trace
    and without the counters (the parent's program): no number, no error."""
    new = ("sparse_attention_ms", "sparse_select_ms", "lightning_ms", "sparse_attention_roofline",
           "lightning_roofline", f"{CELL}_mfu", "sparse_kept_share", "lightning_gate_ms")
    empty = runner.Context({}, {}, {}, 1, {"flop": 1, "bytes": 1, "derived": {}}, [0.1], trace=None, peaks=PEAKS)
    other = test_chipbench_moonlight._step_trace()
    for name in new:
        assert BENCH.reader(name)(empty) is None
        if name.endswith("_ms"):
            assert _read(name, other) is None
    listed = {m["name"] for m in BENCH.metrics("per_layer", CELL)}
    assert set(new) <= listed and not {"flash_attention_roofline", "kda_ms", "moe_experts_ms"} & listed
    for cell in BENCH.data["workloads"]:
        if cell["name"] != CELL:
            assert not set(new) & {m["name"] for m in BENCH.metrics("per_layer", cell["name"])}


def test_the_traffic_and_configuration_files_are_json_the_harness_finds():
    assert BENCH.traffic(BENCH.cell(CELL))["job"] == "minicpm_sala_train_step"
    assert BENCH.cell(CELL)["chips"] == 1 and json.dumps(CONFIG)
