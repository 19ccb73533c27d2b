"""Merge per-rank telemetry JSONL exports into one timeline + summary.

    python scripts/telemetry_report.py DIR_OR_FILE... [--json OUT]
                                       [--top N] [--timeline N]

Each rank of a run writes ``rank<k>.jsonl`` (``heat_tpu.utils.telemetry
.flush``; the multiprocess lane arms this via ``HEAT_TPU_TELEMETRY_DIR``).  This CLI reads any mix of directories
(``rank*.jsonl`` inside) and explicit files and prints:

- a cross-rank **span summary** aggregated by name, sorted by self-time —
  where the wall-clock went, per site, over all ranks;
- **counters** summed over ranks (``comm.*`` byte accounting, ``cache.*``
  hit/miss, ``retry.*``, ``io.*``, ``daso.*``) — the per-rank LAST counters
  record wins (counters are cumulative within a rank);
- merged **histograms** (log-spaced bins sum exactly across ranks; the
  percentiles are recomputed from the merged bins);
- a merged **timeline**: the first N spans of all ranks on one wall-clock
  axis (span timestamps are exported in epoch seconds for this reason);
- when a target directory also holds flight-recorder rings
  (``flight_rank*.ring``, written crash-durably by
  ``heat_tpu.utils.flightrec``), a per-rank **collective timeline** — the
  seq × rank fingerprint grid centered on the first divergence or the
  straggler's stuck sequence, plus the one-line post-mortem verdict
  (``scripts/postmortem.py`` does the merge; this CLI just folds its view
  into the report so one command reads a whole run's artifacts);
- when serving artifacts are present — ``sched.job`` telemetry spans in
  the rank files and/or a scheduler journal (``sched_journal*.jsonl``,
  ``heat_tpu.parallel.scheduler``) — a per-tenant **SLO table**: job
  counts by outcome plus p50/p99 queue wait and execution latency (span
  durations when exported; journal record timestamps otherwise, so a
  journal-only dir — all a SIGKILLed rank leaves behind — still yields
  the full table).

Deliberately stdlib-only (no jax, no heat_tpu import): it must run
instantly on a login node against artifacts scp'd from a pod.

Exit code: 0 on success, 1 when no rank files were found/readable.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
from typing import Dict, List, Optional


def find_rank_files(target: str) -> List[str]:
    """Rank files under a directory, or the file itself."""
    if os.path.isdir(target):
        return sorted(glob.glob(os.path.join(target, "rank*.jsonl")))
    return [target] if os.path.exists(target) else []


def _read_records(path: str) -> List[dict]:
    records = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue  # a torn tail line must not sink the whole report
    return records


def _merge_hist(agg: dict, rec: dict) -> None:
    """Histograms merge exactly: same fixed bin grid on every rank, so bin
    counts sum; min/max/total/count fold."""
    for i, c in rec.get("bins", {}).items():
        agg["bins"][i] = agg["bins"].get(i, 0) + int(c)
    agg["count"] += int(rec.get("count", 0))
    agg["total_s"] += float(rec.get("total_s", 0.0))
    agg["min_s"] = min(agg["min_s"], float(rec.get("min_s", math.inf) or math.inf))
    agg["max_s"] = max(agg["max_s"], float(rec.get("max_s", 0.0)))
    agg["lo"] = float(rec.get("lo", 1e-6))
    agg["per_decade"] = int(rec.get("per_decade", 5))


def _hist_quantile(agg: dict, q: float) -> float:
    if not agg["count"]:
        return 0.0
    target = q * agg["count"]
    seen = 0
    for i in sorted(agg["bins"], key=int):
        n = agg["bins"][i]
        seen += n
        if n and seen >= target:
            idx = int(i)
            if idx == 0:
                return 0.0 if agg["min_s"] is math.inf else agg["min_s"]
            return min(agg["lo"] * 10 ** (idx / agg["per_decade"]), agg["max_s"])
    return agg["max_s"]


def merge_files(paths: List[str]) -> dict:
    """Fold every rank file into one merged structure (see module docstring
    for the merge rules)."""
    spans: List[dict] = []
    counters_by_rank: Dict[int, dict] = {}
    hists_by_rank: Dict[int, Dict[str, dict]] = {}
    ranks = set()
    for path in paths:
        for rec in _read_records(path):
            kind = rec.get("type")
            rank = int(rec.get("rank", 0))
            ranks.add(rank)
            if kind == "span":
                spans.append(rec)
            elif kind == "counters":
                counters_by_rank[rank] = rec.get("values", {})  # last wins
            elif kind == "hist":
                # hist records are CUMULATIVE snapshots (like counters): a
                # rank that flushes twice writes the same observations twice,
                # so within a rank the LAST snapshot wins; only across ranks
                # do bins sum
                hists_by_rank.setdefault(rank, {})[rec["name"]] = rec
    spans.sort(key=lambda r: r.get("ts", 0.0))

    hists: Dict[str, dict] = {}
    for per_rank in hists_by_rank.values():
        for name, rec in per_rank.items():
            agg = hists.get(name)
            if agg is None:
                agg = hists[name] = {
                    "bins": {}, "count": 0, "total_s": 0.0,
                    "min_s": math.inf, "max_s": 0.0,
                    "lo": 1e-6, "per_decade": 5,
                }
            _merge_hist(agg, rec)

    by_name: Dict[str, list] = {}
    for s in spans:
        row = by_name.setdefault(s["name"], [0, 0.0, 0.0, 0.0, set()])
        row[0] += 1
        row[1] += float(s.get("dur_s", 0.0))
        row[2] += float(s.get("self_s", 0.0))
        row[3] = max(row[3], float(s.get("dur_s", 0.0)))
        row[4].add(int(s.get("rank", 0)))
    span_summary = sorted(
        (
            {
                "name": name,
                "count": c,
                "total_s": round(total, 6),
                "self_s": round(self_s, 6),
                "max_ms": round(mx * 1e3, 3),
                "ranks": sorted(rks),
            }
            for name, (c, total, self_s, mx, rks) in by_name.items()
        ),
        key=lambda r: -r["self_s"],
    )

    counters: Dict[str, int] = {}
    for vals in counters_by_rank.values():
        for k, v in vals.items():
            counters[k] = counters.get(k, 0) + int(v)

    hist_summary = {}
    for name, agg in sorted(hists.items()):
        if not agg["count"]:
            hist_summary[name] = {"count": 0}
            continue
        hist_summary[name] = {
            "count": agg["count"],
            "mean_s": round(agg["total_s"] / agg["count"], 9),
            "p50_s": round(_hist_quantile(agg, 0.50), 9),
            "p90_s": round(_hist_quantile(agg, 0.90), 9),
            "p99_s": round(_hist_quantile(agg, 0.99), 9),
            # bin-resolution caveat applies (telemetry.Histogram docstring):
            # the deep tail is exact about the BIN, upper-edge within it
            "p999_s": round(_hist_quantile(agg, 0.999), 9),
            "max_s": round(agg["max_s"], 9),
        }

    return {
        "ranks": sorted(ranks),
        "files": paths,
        "n_spans": len(spans),
        "span_summary": span_summary,
        "counters": dict(sorted(counters.items())),
        "counters_per_rank": {str(r): v for r, v in sorted(counters_by_rank.items())},
        "histograms": hist_summary,
        "timeline": spans,
    }


def _fmt_table(rows: List[List[str]], header: List[str]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows) for i in range(len(header))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*header), fmt.format(*("-" * w for w in widths))]
    lines += [fmt.format(*(str(c) for c in r)) for r in rows]
    return "\n".join(lines)


def render(merged: dict, top: int = 20, timeline: int = 25) -> str:
    out = []
    ranks = merged["ranks"]
    out.append(
        f"telemetry report: {len(merged['files'])} rank file(s), "
        f"ranks={ranks}, {merged['n_spans']} spans"
    )
    if merged["span_summary"]:
        out.append("\n-- span summary (by total self-time, all ranks) --")
        rows = [
            [r["name"], r["count"], f"{r['total_s'] * 1e3:.3f}",
             f"{r['self_s'] * 1e3:.3f}", f"{r['max_ms']:.3f}",
             ",".join(str(x) for x in r["ranks"])]
            for r in merged["span_summary"][:top]
        ]
        out.append(_fmt_table(rows, ["span", "calls", "total_ms", "self_ms", "max_ms", "ranks"]))
    if merged["counters"]:
        out.append("\n-- counters (summed over ranks) --")
        rows = [[k, v] for k, v in merged["counters"].items()]
        out.append(_fmt_table(rows, ["counter", "value"]))
    if merged["histograms"]:
        out.append("\n-- histograms (merged bins) --")
        rows = []
        for name, h in merged["histograms"].items():
            if not h["count"]:
                continue
            rows.append([
                name, h["count"], f"{h['mean_s'] * 1e3:.3f}",
                f"{h['p50_s'] * 1e3:.3f}", f"{h['p90_s'] * 1e3:.3f}",
                f"{h['p99_s'] * 1e3:.3f}",
                f"{h.get('p999_s', h['p99_s']) * 1e3:.3f}",
                f"{h['max_s'] * 1e3:.3f}",
            ])
        if rows:
            out.append(_fmt_table(
                rows, ["histogram", "n", "mean_ms", "p50_ms", "p90_ms",
                       "p99_ms", "p99.9_ms", "max_ms"]
            ))
    if merged["timeline"] and timeline > 0:
        out.append(f"\n-- timeline (first {min(timeline, len(merged['timeline']))} spans, all ranks) --")
        t0 = merged["timeline"][0].get("ts", 0.0)
        rows = []
        for s in merged["timeline"][:timeline]:
            rows.append([
                f"+{(s.get('ts', 0.0) - t0) * 1e3:.3f}ms",
                s.get("rank", 0),
                "  " * int(s.get("depth", 0)) + s["name"],
                f"{float(s.get('dur_s', 0.0)) * 1e3:.3f}",
            ])
        out.append(_fmt_table(rows, ["t", "rank", "span", "dur_ms"]))
    return "\n".join(out)


_postmortem = None


def _postmortem_mod():
    """``scripts/postmortem.py`` loaded standalone (it lives next to this
    file; both are stdlib-only) — the ONE implementation of ring loading,
    verdict analysis and the seq × rank grid.  None when the file is
    missing (a stripped install): the report then simply has no
    collective-timeline section."""
    global _postmortem
    if _postmortem is None:
        import importlib.util

        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "postmortem.py")
        if not os.path.exists(path):
            return None
        spec = importlib.util.spec_from_file_location("telemetry_report_postmortem", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _postmortem = mod
    return _postmortem


def _jsonl_ranks(d: str) -> List[int]:
    """Rank numbers of the ``rank<k>.jsonl`` files in ``d`` — every rank
    that exported telemetry there was part of the world, so they double as
    the analyzer's expected-ranks hint (a rank with telemetry but no ring
    lost its black box, and must not hide inside a clean verdict)."""
    out = []
    for path in find_rank_files(d):
        base = os.path.basename(path)
        try:
            out.append(int(base[len("rank") : -len(".jsonl")]))
        except ValueError:
            continue
    return sorted(set(out))


def flightrec_section(dirs: List[str], context: int = 5) -> str:
    """The collective-timeline section for every target directory holding
    ``flight_rank*.ring`` files; '' when none do (the common telemetry-only
    invocation prints nothing extra).  The verdict gets the same evidence
    the supervisor's analyzer gets: the dir's own telemetry jsonl for wait
    attribution, and its jsonl rank set as expected ranks."""
    pm = _postmortem_mod()
    if pm is None:
        return ""
    out = []
    for d in dirs:
        rings = pm.load_rings(d)
        if not rings:
            continue
        verdict = pm.analyze(
            rings,
            waits=pm.load_wait_hists(d),
            expected_ranks=_jsonl_ranks(d) or None,
        )
        around = verdict.get("first_divergent_seq")
        if around is None and verdict.get("straggler"):
            around = verdict["straggler"].get("seq")
        out.append(f"\n-- collective timeline (seq × rank) from {d} --")
        out.append(pm.summary_line(verdict))
        if verdict.get("missing_ranks"):
            out.append(
                "rank(s) with telemetry but NO ring file: "
                + ", ".join(str(r) for r in verdict["missing_ranks"])
            )
        out.append(pm.render_grid(rings, around=around, context=context))
    return "\n".join(out)


def memory_section(dirs: List[str], timeline: int = 12) -> str:
    """The device-memory section: per-rank watermark timeline + a
    top-buffers table, both read from the flight-ring ``mem``/``membuf``
    records the memory ledger writes (``heat_tpu/utils/memledger.py``);
    '' when no target dir holds rings with memory records.  An ``oom=1``
    record is called out explicitly with the failed request size — the
    same evidence ``scripts/postmortem.py`` turns into its ``oom``
    verdict."""
    pm = _postmortem_mod()
    if pm is None:
        return ""
    out: List[str] = []
    for d in dirs:
        rings = pm.load_rings(d)
        if not rings:
            continue
        per_rank: Dict[int, List[dict]] = {}
        bufs: List[dict] = []
        for r, ring in sorted(rings.items()):
            # a ring may hold several dumps (per-step attestations + an OOM
            # dump); keep only each rank's LAST membuf burst — the freshest
            # view — so stale rows from earlier dumps never interleave as
            # "top live buffers" (the same per-dump scoping postmortem.py's
            # collector applies)
            burst: List[dict] = []
            last_burst: List[dict] = []
            for rec in ring.get("records", []):
                if rec.get("k") == "mem":
                    per_rank.setdefault(r, []).append(rec)
                    if burst:
                        last_burst = burst
                    burst = []
                elif rec.get("k") == "membuf":
                    burst.append(dict(rec, rank=r))
            bufs.extend(burst or last_burst)
        if not per_rank and not bufs:
            continue
        out.append(f"\n-- device memory (ledger watermarks) from {d} --")
        for r, recs in sorted(per_rank.items()):
            peak = max((rec.get("peak") or 0) for rec in recs)
            out.append(f"MEM-PEAK rank={r} bytes={peak}")
            t0 = recs[0].get("t", 0.0)
            for rec in recs[-timeline:]:
                by = rec.get("by") or {}
                cats = " ".join(f"{c}={v}" for c, v in sorted(by.items()))
                flag = (
                    f"  OOM req={rec.get('req')} where={rec.get('where')}"
                    if rec.get("oom")
                    else ""
                )
                out.append(
                    f"  rank {r} t+{rec.get('t', 0.0) - t0:7.3f}s  "
                    f"live={rec.get('live', 0):>12}  "
                    f"peak={rec.get('peak', 0):>12}  {cats}{flag}"
                )
        if bufs:
            bufs.sort(key=lambda b: -(b.get("nb") or 0))
            rows = [
                [
                    str(b.get("rank")),
                    str(b.get("nb")),
                    str(b.get("op")),
                    str(b.get("cat")),
                    str(b.get("span") or "-"),
                    str(b.get("tid") or "-"),
                ]
                for b in bufs[:10]
            ]
            out.append("top live buffers (from ledger dumps):")
            out.append(
                _fmt_table(rows, ["rank", "bytes", "op", "category", "span",
                                  "trace"])
            )
    return "\n".join(out)


_stepprof = None


def _stepprof_mod():
    """``scripts/stepprof.py`` loaded standalone (next to this file,
    stdlib-only) — the ONE implementation of the step-time breakdown.
    None when missing (a stripped install): no overlap section."""
    global _stepprof
    if _stepprof is None:
        import importlib.util

        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "stepprof.py")
        if not os.path.exists(path):
            return None
        spec = importlib.util.spec_from_file_location("telemetry_report_stepprof", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _stepprof = mod
    return _stepprof


def overlap_section(spans: List[dict]) -> str:
    """The step-time compute/comm-overlap breakdown (``scripts/
    stepprof.py``) over the already-merged spans; '' when no step spans
    exist or stepprof is missing."""
    sp = _stepprof_mod()
    if sp is None or not spans:
        return ""
    return sp.overlap_section(spans)


_tl = None


def _timeline_mod():
    """``heat_tpu/analysis/timeline.py`` loaded standalone (stdlib-only)
    — the ONE implementation of clock alignment, Chrome-trace export and
    critical-path blame.  None when missing (a stripped install)."""
    mod = sys.modules.get("heat_tpu.analysis.timeline")
    if mod is not None:
        return mod
    global _tl
    if _tl is None:
        import importlib.util

        path = os.path.normpath(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            os.pardir, "heat_tpu", "analysis", "timeline.py",
        ))
        if not os.path.exists(path):
            return None
        spec = importlib.util.spec_from_file_location("telemetry_report_timeline", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _tl = mod
    return _tl


def critical_path_section(targets: List[str], trace_out: Optional[str] = None) -> str:
    """CLOCK-ALIGN + CRITICAL-PATH attribution over the target dirs'
    merged artifacts (``heat_tpu/analysis/timeline.py``); '' when nothing
    is attributable.  With ``trace_out``, additionally writes the
    schema-checked Chrome trace-event JSON there."""
    tl = _timeline_mod()
    if tl is None:
        return ""
    dirs = [t for t in targets if os.path.isdir(t)]
    if not dirs:
        return ""
    bundle = tl.assemble(dirs)
    if not bundle["ranks"]:
        return ""
    out = []
    clock = tl.clock_report(bundle)
    if clock:
        out.append(clock)
    report = tl.critical_path_report(bundle)
    if report:
        out.append(report)
    if trace_out:
        trace = tl.to_chrome_trace(bundle)
        problems = tl.validate_chrome_trace(trace)
        with open(trace_out, "w") as fh:
            json.dump(trace, fh)
        out.append(
            f"TRACE-EXPORT events={len(trace['traceEvents'])} "
            f"ranks={len(bundle['ranks'])} out={trace_out}"
        )
        for p in problems:
            out.append(f"INVALID: {p}")
    return "\n".join(out)


def trace_section(targets: List[str], trace_id: str,
                  spans: Optional[List[dict]] = None) -> str:
    """The assembled causal timeline of ONE trace id across every artifact
    the targets hold: telemetry spans whose attrs carry the id, scheduler
    journal records (``tid``), and flight-recorder ring records (``tid``
    on collective stamps and job events).  This is the end-to-end payoff
    of trace propagation: one command renders a single job's path —
    submit, dispatches, per-collective seq stamps, retries, terminal state
    — across ranks, processes and supervisor restarts, merged on the
    epoch-seconds axis the exports share."""
    events: List[dict] = []  # {t, rank, source, what}
    if spans is None:
        spans = []
        for t in targets:
            for p in find_rank_files(t):
                spans.extend(
                    r for r in _read_records(p) if r.get("type") == "span"
                )
    for s in spans:
        at = s.get("attrs") or {}
        if at.get("trace_id") != trace_id:
            continue
        what = f"span {s.get('name')} ({float(s.get('dur_s', 0.0)) * 1e3:.3f}ms"
        extra = ", ".join(
            f"{k}={at[k]}" for k in ("kind", "outcome", "op", "attempts")
            if k in at
        )
        what += f"; {extra})" if extra else ")"
        events.append({
            "t": float(s.get("ts", 0.0)),
            "rank": s.get("rank", "?"),
            "source": "telemetry",
            "what": what,
        })
    sched = _scheduler_mod()
    for t in targets:
        for jp in find_journals(t):
            if sched is None:
                break
            try:
                replay = sched.replay_journal(jp)
            except Exception:
                continue
            for rec in replay["records"]:
                if rec.get("tid") != trace_id:
                    continue
                bits = [str(rec.get("type"))]
                for k in ("id", "seq", "attempt", "reason", "epoch"):
                    if rec.get(k) is not None:
                        bits.append(f"{k}={rec[k]}")
                events.append({
                    "t": float(rec.get("t", 0.0)),
                    "rank": "journal",
                    "source": "journal",
                    "what": " ".join(bits),
                })
    pm = _postmortem_mod()
    if pm is not None:
        for t in targets:
            if not os.path.isdir(t):
                continue
            for rank, ring in sorted(pm.load_rings(t).items()):
                for rec in ring.get("records", []):
                    if rec.get("tid") != trace_id:
                        continue
                    kind = rec.get("k")
                    if kind == "coll":
                        what = (
                            f"collective seq={rec.get('seq')} "
                            f"op={rec.get('op')} wire={rec.get('wire')}B"
                        )
                    else:
                        bits = [str(kind)]
                        for k in ("id", "state", "attempt"):
                            if rec.get(k) is not None:
                                bits.append(f"{k}={rec[k]}")
                        what = " ".join(bits)
                    events.append({
                        "t": float(rec.get("t", 0.0)),
                        "rank": rank,
                        "source": "flightrec",
                        "what": what,
                    })
    if not events:
        return f"trace {trace_id}: no records found under {targets}"
    events.sort(key=lambda e: e["t"])
    t0 = events[0]["t"]
    out = [f"-- causal timeline for trace {trace_id} "
           f"({len(events)} records, all sources) --"]
    rows = [
        [f"+{(e['t'] - t0) * 1e3:.3f}ms", str(e["rank"]), e["source"], e["what"]]
        for e in events
    ]
    out.append(_fmt_table(rows, ["t", "rank", "source", "event"]))
    return "\n".join(out)


_scheduler = None


def _scheduler_mod():
    """``heat_tpu/parallel/scheduler.py`` loaded standalone (stdlib-only,
    like this CLI) — the ONE implementation of journal replay.  None when
    the file is missing (a stripped install): the report then has no SLO
    section from journals (spans still render)."""
    global _scheduler
    if _scheduler is None:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "heat_tpu", "parallel", "scheduler.py",
        )
        if not os.path.exists(path):
            return None
        spec = importlib.util.spec_from_file_location("telemetry_report_scheduler", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _scheduler = mod
    return _scheduler


def find_journals(target: str) -> List[str]:
    """Scheduler journal files under a directory, or the file itself."""
    if os.path.isdir(target):
        return sorted(glob.glob(os.path.join(target, "sched_journal*.jsonl")))
    base = os.path.basename(target)
    if base.startswith("sched_journal") and os.path.exists(target):
        return [target]
    return []


def _pctl(values: List[float], q: float) -> float:
    """Exact upper percentile of a small sample (serving job counts are
    human-scale; no need for the histogram approximation here)."""
    if not values:
        return 0.0
    vs = sorted(values)
    idx = min(len(vs) - 1, max(0, int(math.ceil(q * len(vs))) - 1))
    return vs[idx]


def slo_section(targets: List[str], spans: Optional[List[dict]] = None) -> str:
    """The per-tenant serving SLO table, from whichever artifacts exist:

    - ``sched.job`` spans in the rank files — the high-fidelity latency
      source (queue wait rides the span attrs, execution latency is the
      span duration);
    - scheduler journals (``sched_journal*.jsonl``) — the complete outcome
      accounting (incl. shed jobs, which never execute and so never span),
      with record-timestamp latencies as the fallback when no spans were
      exported (a SIGKILLed rank flushes no telemetry; its journal
      survives).

    '' when neither is present (the common non-serving invocation prints
    nothing extra).  ``spans`` lets a caller that already parsed the rank
    files (``main`` passes ``merged['timeline']``) skip the second read —
    the rank files are otherwise re-parsed here."""
    if spans is None:
        spans = []
        for t in targets:
            for p in find_rank_files(t):
                for rec in _read_records(p):
                    if rec.get("type") == "span" and rec.get("name") == "sched.job":
                        spans.append(rec)
    else:
        spans = [s for s in spans
                 if s.get("type") == "span" and s.get("name") == "sched.job"]
    # every rank of an SPMD serve world emits an identical span per job —
    # dedup by job id or the per-rank copies would multiply the job
    # counts and skew the percentiles
    seen_jobs = set()
    deduped = []
    for rec in spans:
        jid = (rec.get("attrs") or {}).get("id")
        if jid is not None:
            if jid in seen_jobs:
                continue
            seen_jobs.add(jid)
        deduped.append(rec)
    spans = deduped
    views: Dict[str, dict] = {}
    notes: List[str] = []
    for t in targets:
        for jp in find_journals(t):
            sched = _scheduler_mod()
            if sched is None:
                break
            try:
                views.update(sched.replay_journal(jp)["jobs"])
            except Exception as e:  # a bad journal must not sink the report
                notes.append(f"journal {jp}: unreadable ({e})")
    if not spans and not views and not notes:
        return ""
    tenants: Dict[str, dict] = {}

    def row(tenant: str) -> dict:
        return tenants.setdefault(tenant, {
            "jobs": 0, "done": 0, "failed": 0, "shed": 0,
            "waits": [], "execs": [],
        })

    for v in views.values():
        r = row(str(v.get("tenant", "default")))
        r["jobs"] += 1
        state = v.get("state")
        if state == "done":
            r["done"] += 1
        elif state == "failed":
            r["failed"] += 1
        elif state == "shed":
            r["shed"] += 1
        if v.get("dispatch_t") and v.get("submit_t"):
            r["waits"].append(max(0.0, v["dispatch_t"] - v["submit_t"]))
        if v.get("exec_s") is not None:
            r["execs"].append(float(v["exec_s"]))
        elif v.get("finish_t") and v.get("dispatch_t"):
            r["execs"].append(max(0.0, v["finish_t"] - v["dispatch_t"]))
    by_tenant_spans: Dict[str, dict] = {}
    for s in spans:
        at = s.get("attrs") or {}
        d = by_tenant_spans.setdefault(str(at.get("tenant", "default")),
                                       {"waits": [], "execs": [], "outcomes": {}})
        d["waits"].append(float(at.get("queue_wait_s", 0.0)))
        d["execs"].append(float(s.get("dur_s", 0.0)))
        oc = str(at.get("outcome", "?"))
        d["outcomes"][oc] = d["outcomes"].get(oc, 0) + 1
    for tenant, d in by_tenant_spans.items():
        r = row(tenant)
        # spans are the higher-fidelity latency source when both exist
        r["waits"], r["execs"] = d["waits"], d["execs"]
        if not views:  # spans-only dir: outcome counts from the spans too
            r["jobs"] = sum(d["outcomes"].values())
            r["done"] = d["outcomes"].get("done", 0)
            r["failed"] = r["jobs"] - r["done"]
    out = ["\n-- per-tenant serving SLO (sched.job spans + scheduler journal) --"]
    out.extend(notes)
    if tenants:
        rows = []
        for tenant in sorted(tenants):
            r = tenants[tenant]
            rows.append([
                tenant, r["jobs"], r["done"], r["failed"], r["shed"],
                f"{_pctl(r['waits'], 0.5) * 1e3:.1f}",
                f"{_pctl(r['waits'], 0.99) * 1e3:.1f}",
                f"{_pctl(r['execs'], 0.5) * 1e3:.1f}",
                f"{_pctl(r['execs'], 0.99) * 1e3:.1f}",
            ])
        out.append(_fmt_table(rows, [
            "tenant", "jobs", "done", "failed", "shed",
            "wait_p50_ms", "wait_p99_ms", "exec_p50_ms", "exec_p99_ms",
        ]))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("targets", nargs="+", help="telemetry dirs and/or rank*.jsonl files")
    ap.add_argument("--json", default=None, help="also write the merged structure here")
    ap.add_argument("--top", type=int, default=20, help="span-summary rows to print")
    ap.add_argument("--timeline", type=int, default=25,
                    help="timeline rows to print (0 disables)")
    ap.add_argument("--context", type=int, default=5,
                    help="collective-grid rows either side of the divergence")
    ap.add_argument("--trace", default=None, metavar="TRACE_ID",
                    help="render the assembled causal timeline of ONE trace "
                         "id across spans, scheduler journals and flight "
                         "rings, instead of the full report")
    ap.add_argument("--trace-out", default=None, metavar="TRACE_JSON",
                    help="also export the cross-rank Chrome trace-event "
                         "JSON (clock-aligned; scripts/traceviz.py is the "
                         "standalone form)")
    args = ap.parse_args(argv)

    paths = []
    for t in args.targets:
        paths.extend(find_rank_files(t))
    paths = sorted(dict.fromkeys(paths))  # de-dup, stable order
    if args.trace:
        merged = merge_files(paths) if paths else None
        print(trace_section(
            list(args.targets), args.trace,
            spans=merged["timeline"] if merged is not None else None,
        ))
        return 0
    section = flightrec_section(
        [t for t in args.targets if os.path.isdir(t)], context=args.context
    )
    mem = memory_section([t for t in args.targets if os.path.isdir(t)])
    merged = merge_files(paths) if paths else None
    # reuse the merge's already-parsed spans instead of re-reading every
    # rank file just to pick out the sched.job records
    slo = slo_section(
        list(args.targets),
        spans=merged["timeline"] if merged is not None else None,
    )
    if not paths:
        # a dir holding ONLY flight-recorder rings or a scheduler journal
        # is a legitimate target: the supervisor's harvested epoch dirs
        # contain rings but no telemetry jsonl, and a SIGKILLed serving
        # rank leaves a journal and nothing else — the timeline / SLO
        # table is exactly what a post-mortem reader comes for
        if section or slo or mem:
            print(f"no rank*.jsonl telemetry files under {args.targets}; "
                  "rendering the journal/ring artifacts only")
            if section:
                print(section)
            if mem:
                print(mem)
            if slo:
                print(slo)
            # rings alone still align and attribute (the harvested
            # epoch-dir case: collective stamps are the anchors)
            cp = critical_path_section(
                list(args.targets), trace_out=args.trace_out
            )
            if cp:
                print(cp)
            return 0
        print(
            f"no rank*.jsonl files (nor flight_rank*.ring / "
            f"sched_journal*.jsonl files) found under {args.targets}",
            file=sys.stderr,
        )
        return 1
    print(render(merged, top=args.top, timeline=args.timeline))
    if section:
        print(section)
    if mem:
        print(mem)
    if slo:
        print(slo)
    overlap = overlap_section(merged["timeline"])
    if overlap:
        print(overlap)
    # cross-rank clock alignment + critical-path blame (and optionally
    # the Chrome trace artifact) — which rank/op/seq gated each step,
    # not just how much time each class took
    cp = critical_path_section(list(args.targets), trace_out=args.trace_out)
    if cp:
        print(cp)
    if args.json:
        # the timeline can be huge; the JSON artifact keeps it whole (the
        # text rendering is the bounded view)
        with open(args.json, "w") as fh:
            json.dump(merged, fh, indent=1)
        print(f"\nmerged JSON written to {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
