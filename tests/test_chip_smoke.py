"""``chip_smoke.py`` off the chip: its phase functions at toy sizes on the
8-device CPU mesh (kernels in interpret mode), its refusal to run without a
TPU, and the compile-cache placement rule.

The whole script at toy sizes (``chip_smoke.run``: seventeen phases) is a
minute of XLA:CPU compiles, more than the quick lane can spare (ROADMAP D9),
so it carries the ``slow`` marker; the quick lane keeps the refusal, the
cache rule and the agreement of the toy and full size tables.  Run the slow
one before spending chip time: ``pytest tests/test_chip_smoke.py -m slow``.
"""

import os
import subprocess
import sys

import jax
import pytest

import heat_tpu as ht

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(
    matmul_n=256, resplit_n=128, qr_shape=(4096, 16), kmeans=(4096, 8, 4), fft_n=1024,
    mlp_batch_per_chip=8,
    daso_model=lambda: ht.nn.models.resnet((1, 1), width=8, num_classes=10),
    daso_image=(3, 16, 16), daso_classes=10, daso_batch_per_chip=2,
    lm=dict(vocab_size=64, embed_dim=32, num_heads=4, depth=2, max_len=64),
    lm_batch=8, lm_seq=32, lm_prompt=4, lm_new=4,
    attn=(8, 4, 128, 16), attn_kv_heads=2, attn_long=(2, 8, 256, 16),
    kda=(8, 128, 128), kda_conv=(1, 64, 2, 128),
    gated_window=dict(embed=32, heads=4, kv_heads=2, head_dim=16, seq=128, window=32, prefix=48),
    ring=(2, 2, 16, 8),
    moe=dict(embed=16, hidden=32, experts_per_chip=2, tokens_per_chip=8),
    pipe=dict(embed=16, heads=2, seq=8, batch_per_chip=1),
)


def test_full_sizes_name_the_same_phases():
    assert set(TOY) == set(chip_smoke.FULL)


@pytest.mark.slow
def test_every_phase_toy(capsys):
    chip_smoke.run(TOY)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("PHASE ")]
    # one line per phase, naming the device, its seconds labelled as
    # including compilation
    for line in lines:
        assert " ok wall_s_incl_compile=" in line
        assert f"platform=cpu device_kind='cpu' devices={len(jax.devices())}" in line
    assert [l.split()[1] for l in lines] == [
        "array.matmul", "array.resplit", "array.qr", "array.kmeans", "array.ragged",
        "array.fft", "train.mlp_dataparallel", "train.daso", "model.transformer_lm",
        "model.flash_attention", "model.gated_window_attention", "model.kda", "model.kda_conv", "multi.dryrun_tiers",
        "multi.ring_attention", "multi.moe_expert_parallel", "multi.pipeline",
        "multi.daso_two_tier",
    ]


def test_no_tpu_no_result():
    """Without a chip the entry point prints no success marker and no metric
    line, and exits non-zero (no CPU stand-in under a device's name)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0, proc.stdout[-300:]
    assert "CHIP_SMOKE OK" not in proc.stdout and "{" not in proc.stdout, proc.stdout[-300:]
    assert "Unable to initialize backend 'tpu'" in proc.stderr, proc.stderr[-500:]


def test_compile_cache_rule(monkeypatch):
    from heat_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    # placed from outside: the code sets no directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.configure() == "/elsewhere/cache"
    assert [k for k, _ in calls] == ["jax_persistent_cache_min_compile_time_secs"]
    # not placed: <checkout>/.jax_cache, and every compile is kept
    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    here = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == here
    assert calls == [("jax_compilation_cache_dir", here),
                     ("jax_persistent_cache_min_compile_time_secs", 0.0)]


def test_only_the_helper_places_the_cache():
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d != "chiprun_out"]
        for f in files:
            path = os.path.join(root, f)
            if f.endswith(".py") and path != os.path.abspath(__file__):
                text = open(path, encoding="utf-8").read()
                if "jax_compilation_cache_dir" in text or "HEAT_TPU_JAX_CACHE" in text:
                    offenders.append(os.path.relpath(path, REPO))
    assert offenders == [os.path.join("heat_tpu", "utils", "compile_cache.py")]
