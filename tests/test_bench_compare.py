"""scripts/bench_compare.py — the perun-CB regression-comparator analogue
(SURVEY §2.6, VERDICT r4 item 7): payload loading (driver wrapper + direct
manual captures), direction inference, threshold flagging, and the
rows_expected/rows_captured manifest."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import bench_compare  # noqa: E402


def _payload(value, extra):
    return {"metric": "dist_matmul_16384_bf16_tflops_per_chip", "value": value,
            "unit": "TFLOPS/chip", "vs_baseline": None, "extra": extra}


class TestUnits:
    def test_flatten_recurses_and_skips_bools(self):
        rows = bench_compare.flatten(_payload(100.0, {
            "mfu_bf16": 0.8, "flag": True,
            "summa_vs_gspmd_cpu8dev": {"summa_over_gspmd": 0.7},
        }))
        assert rows["dist_matmul_16384_bf16_tflops_per_chip"] == 100.0
        assert rows["summa_vs_gspmd_cpu8dev.summa_over_gspmd"] == 0.7
        assert "flag" not in rows

    def test_direction(self):
        d = bench_compare.direction
        assert d("matmul_4096_bf16_tflops_per_chip") > 0
        assert d("lm_decode_b8_tok_per_s") > 0
        assert d("mfu_f32") > 0
        assert d("flash_attention_speedup") > 0  # "_s" substring must not win
        assert d("kmeans_kernel_speedup") > 0
        assert d("matmul_4096_dispatch_overhead_s") < 0
        assert d("qr_tsqr_1e6x256_f32_s") < 0
        assert d("summa_vs_gspmd_cpu8dev.summa_over_gspmd") < 0
        # bookkeeping rows are never flagged
        assert d("n_chips") == 0
        assert d("kmeans_rows") == 0
        assert d("bf16_peak_tflops_per_chip") == 0

    def test_wrapper_and_direct_forms_load(self, tmp_path):
        direct = tmp_path / "direct.json"
        direct.write_text(json.dumps(_payload(10.0, {})))
        wrapper = tmp_path / "wrapper.json"
        wrapper.write_text(json.dumps({"n": 5, "rc": 0, "tail": "…",
                                       "parsed": _payload(11.0, {})}))
        assert bench_compare.load(str(direct))["value"] == 10.0
        assert bench_compare.load(str(wrapper))["value"] == 11.0
        bogus = tmp_path / "bogus.json"
        bogus.write_text("{}")
        with pytest.raises(ValueError, match="metric"):
            bench_compare.load(str(bogus))


class TestEndToEnd:
    def _run(self, tmp_path, a, b, *flags):
        fa, fb = tmp_path / "a.json", tmp_path / "b.json"
        fa.write_text(json.dumps(a))
        fb.write_text(json.dumps(b))
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "bench_compare.py"),
             str(fa), str(fb), *flags],
            capture_output=True, text=True, timeout=120)

    def test_clean_pair_exits_zero(self, tmp_path):
        a = _payload(100.0, {"mfu_bf16": 0.80})
        b = _payload(98.0, {"mfu_bf16": 0.79})
        r = self._run(tmp_path, a, b)
        assert r.returncode == 0, r.stdout
        assert "no regressions" in r.stdout

    def test_regression_flagged_both_directions(self, tmp_path):
        a = _payload(100.0, {"step_wallclock_s": 1.0})
        b = _payload(80.0, {"step_wallclock_s": 1.5})  # ↓thr/chip AND ↑time
        r = self._run(tmp_path, a, b)
        assert r.returncode == 2
        assert r.stdout.count("REGRESSION") >= 2

    def test_threshold_flag(self, tmp_path):
        a = _payload(100.0, {})
        b = _payload(85.0, {})  # -15%: flagged at 10%, clean at 20%
        assert self._run(tmp_path, a, b).returncode == 2
        assert self._run(tmp_path, a, b, "--threshold", "0.20").returncode == 0

    def test_manifest_reported(self, tmp_path):
        a = _payload(100.0, {"rows_expected": ["headline", "flash_ab"],
                             "rows_captured": ["headline"],
                             "platform": "tpu"})
        b = _payload(99.0, {})
        r = self._run(tmp_path, a, b)
        assert "1/2 expected rows captured" in r.stdout
        assert "MISSING: flash_ab" in r.stdout
