"""Bench regression comparator — the perun-CB analogue (SURVEY §2.6: the
reference tracks per-PR benchmark regressions; VERDICT r4 item 7).

    python scripts/bench_compare.py BENCH_rA.json BENCH_rB.json [--threshold 0.10]

Loads two bench payloads (either the driver wrapper ``{n, cmd, rc, tail,
parsed}`` or a direct ``{metric, value, unit, vs_baseline, extra}`` object
as ``benchmarks/dispatch.py`` writes it), flattens every numeric row
(top-level value + ``extra`` recursively), prints a per-row delta table,
and flags regressions beyond the threshold.  Direction (higher/lower is
better) is inferred from the metric name; rows with unknown direction are
reported but never flagged.  Understands the ``rows_expected`` /
``rows_captured`` manifest (a payload whose rows failed says which) and
prints each payload's platform/provenance so a number is never read
without the device it came from.

Exit code: 0 clean, 2 if any regression was flagged (CI-friendly), 1 on
unusable input.
"""

from __future__ import annotations

import json
import sys

# name fragments that decide comparison direction
# checked BEFORE LOWER_BETTER: "speedup" must win over a trailing "_s"
HIGHER_BETTER = ("tflops", "gflops", "iter_per_s", "tok_per_s", "mfu",
                 "throughput", "bandwidth", "_per_s", "speedup")
# time units match as SUFFIXES only; qualitative words match anywhere
LOWER_BETTER_SUFFIX = ("_s", "_ms", "_seconds")
LOWER_BETTER_SUB = ("overhead", "wallclock", "_over_gspmd", "latency")
# bookkeeping rows that are not performance measurements at all —
# fragments matched as substrings, plus exact names for the short tokens
# (a bare "n" fragment would match nearly every metric name)
NOT_PERF = ("_rows", "_gib", "n_chips", "peak", "count", "bytes",
            "vs_baseline", "ratio_vs_torch", "torch_cpu")
NOT_PERF_EXACT = ("n", "rc", "kmeans_rows", "kmeans_bf16_rows")


def load(path: str) -> dict:
    with open(path) as f:
        d = json.load(f)
    if "parsed" in d and isinstance(d["parsed"], dict):
        d = d["parsed"]  # driver wrapper
    if "metric" not in d:
        raise ValueError(f"{path}: not a bench payload (no 'metric' key)")
    return d


def flatten(d: dict) -> dict:
    """metric-name -> float for every numeric row in the payload."""
    rows = {}
    if isinstance(d.get("value"), (int, float)):
        rows[d["metric"]] = float(d["value"])

    def walk(prefix, obj):
        for k, v in obj.items():
            name = f"{prefix}{k}"
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                rows[name] = float(v)
            elif isinstance(v, dict):
                walk(f"{name}.", v)

    walk("", d.get("extra") or {})
    return rows


def direction(name: str) -> int:
    """+1 higher-better, -1 lower-better, 0 unknown/not-perf."""
    low = name.lower()
    if low in NOT_PERF_EXACT or any(f in low for f in NOT_PERF):
        return 0
    if any(f in low for f in HIGHER_BETTER):
        return +1
    if any(low.endswith(f) for f in LOWER_BETTER_SUFFIX) or any(
        f in low for f in LOWER_BETTER_SUB
    ):
        return -1
    return 0


def provenance(d: dict) -> str:
    e = d.get("extra") or {}
    bits = [str(e.get("platform", "?"))]
    for k in ("provenance", "note"):
        if e.get(k):
            bits.append(str(e[k])[:140])
    return " | ".join(bits)


def manifest(d: dict) -> tuple[list, list]:
    e = d.get("extra") or {}
    return list(e.get("rows_expected") or []), list(e.get("rows_captured") or [])


def main(argv) -> int:
    args, thr, i = [], 0.10, 1
    while i < len(argv):
        tok = argv[i]
        if tok.startswith("--threshold"):
            if "=" in tok:
                thr = float(tok.split("=", 1)[1])
            else:
                i += 1
                thr = float(argv[i])
        elif not tok.startswith("--"):
            args.append(tok)
        i += 1
    if len(args) != 2:
        print(__doc__)
        return 1
    try:
        a, b = load(args[0]), load(args[1])
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"error: {exc}")
        return 1

    print(f"A = {args[0]}: {provenance(a)}")
    print(f"B = {args[1]}: {provenance(b)}")
    for tag, d in (("A", a), ("B", b)):
        exp, cap = manifest(d)
        if exp:
            missing = [r for r in exp if r not in cap]
            print(f"{tag} manifest: {len(cap)}/{len(exp)} expected rows captured"
                  + (f"; MISSING: {', '.join(missing)}" if missing else ""))

    ra, rb = flatten(a), flatten(b)
    shared = sorted(set(ra) & set(rb))
    only_a = sorted(set(ra) - set(rb))
    only_b = sorted(set(rb) - set(ra))

    regressions = []
    print(f"\n{'row':58s} {'A':>12s} {'B':>12s} {'Δ%':>8s}  flag")
    for name in shared:
        va, vb = ra[name], rb[name]
        pct = (vb - va) / abs(va) * 100.0 if va else float("inf") if vb else 0.0
        d = direction(name)
        flag = ""
        if d > 0 and pct < -thr * 100:
            flag = "REGRESSION"
        elif d < 0 and pct > thr * 100:
            flag = "REGRESSION"
        elif d == 0:
            flag = "(untracked)"
        if flag == "REGRESSION":
            regressions.append((name, va, vb, pct))
        print(f"{name:58s} {va:12.4g} {vb:12.4g} {pct:+8.1f}  {flag}")
    if only_a:
        print(f"\nonly in A ({len(only_a)}): {', '.join(only_a)}")
    if only_b:
        print(f"only in B ({len(only_b)}): {', '.join(only_b)}")

    if regressions:
        print(f"\n{len(regressions)} regression(s) beyond {thr:.0%}:")
        for name, va, vb, pct in regressions:
            print(f"  {name}: {va:.4g} -> {vb:.4g} ({pct:+.1f}%)")
        return 2
    print(f"\nno regressions beyond {thr:.0%} on {len(shared)} shared rows")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
