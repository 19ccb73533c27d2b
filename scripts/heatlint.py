#!/usr/bin/env python
"""heatlint CLI — static analysis of heat_tpu's distributed invariants.

Usage:
    python scripts/heatlint.py heat_tpu/ benchmarks/ tutorials/
    python scripts/heatlint.py heat_tpu/ --json out.json    # machine output
    python scripts/heatlint.py heat_tpu/ --sarif out.sarif  # PR annotations
    python scripts/heatlint.py heat_tpu/ --write-baseline   # regenerate
    python scripts/heatlint.py heat_tpu/ --select HT3*      # prefix wildcard
    python scripts/heatlint.py heat_tpu/ --fix              # proof-carrying autofix
    python scripts/heatlint.py heat_tpu/ --fix --dry-run-diff
    python scripts/heatlint.py heat_tpu/ --fix-check        # CI: no autofixable news
    python scripts/heatlint.py --list-rules                 # severity + fixable

Exit codes: 0 = clean (no ERROR findings beyond the committed baseline),
1 = new error findings (after fixes, under ``--fix``; any autofixable new
finding, under ``--fix-check``), 2 = usage error.  ``info``-severity
findings (the interprocedural rules' unresolved-call downgrades) never
gate — they are counted in the summary, listed with ``--show-info``, and
carried in the JSON/SARIF output at note level.

Autofix (``--fix``): each fixable finding is rewritten ONLY when its
safety proof holds (0-d + untraced for host syncs, literal seed for
entropy, no-caller-armed-deadline for waits — see analysis/fixes.py);
unprovable sites are left byte-identical with a per-site refusal reason
in the summary and ``--json``.  Every run asserts the engine's contract
before writing: fixed files re-lint clean for their fingerprints, and
fix ∘ fix = fix (a second pass plans zero edits).  ``--dry-run-diff``
prints the unified diffs instead of writing.  SARIF output carries the
planned patches as ``fixes`` objects.

Suppressions: ``# heatlint: disable=HT101`` on the offending line,
``# heatlint: disable-file=HT101`` anywhere for the whole file.  A line
suppression that suppresses nothing is itself a finding (HT110) with a
fixer that deletes it.
The baseline (default: .heatlint-baseline.json next to the repo root)
grandfathers pre-existing findings by fingerprint — line drift does not
invalidate it, and ``--write-baseline`` regenerates it after intentional
changes.  The interprocedural passes cache per-file effect summaries in
``.heatlint-summaries.json`` (keyed by content hash; ``--no-cache``
disables, ``--summaries-cache`` relocates).
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_analysis():
    """Import ``heat_tpu.analysis`` WITHOUT importing ``heat_tpu`` itself:
    the linter is pure stdlib, and the CI lint lane (like any pre-commit
    hook) must not need jax/numpy installed just to parse source files.
    A synthetic parent package keeps the relative imports working."""
    name = "_heatlint_analysis"
    if name in sys.modules:
        # a second loader in the same process (two test modules both
        # importing the CLI) must get the FRAMEWORK back, not the synthetic
        # parent package
        return sys.modules[name + ".framework"]
    pkg_dir = os.path.join(REPO, "heat_tpu", "analysis")
    pkg = types.ModuleType(name)
    pkg.__path__ = [pkg_dir]
    sys.modules[name] = pkg
    spec = importlib.util.spec_from_file_location(
        name + ".framework", os.path.join(pkg_dir, "framework.py")
    )
    framework = importlib.util.module_from_spec(spec)
    sys.modules[name + ".framework"] = framework
    spec.loader.exec_module(framework)
    pkg.framework = framework
    rules = importlib.import_module(name + ".rules")
    pkg.rules = rules
    pkg.fixes = importlib.import_module(name + ".fixes")
    return framework


_fw = _load_analysis()
_fixes = sys.modules["_heatlint_analysis.fixes"]
all_rules = _fw.all_rules
lint_paths = _fw.lint_paths
load_baseline = _fw.load_baseline
render_json = _fw.render_json
render_sarif = _fw.render_sarif
render_text = _fw.render_text
split_by_baseline = _fw.split_by_baseline
write_baseline = _fw.write_baseline

DEFAULT_BASELINE = os.path.join(REPO, ".heatlint-baseline.json")
DEFAULT_SUMMARIES_CACHE = os.path.join(REPO, ".heatlint-summaries.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="heatlint", description=__doc__)
    ap.add_argument("paths", nargs="*", help="files or directories to lint")
    ap.add_argument("--select", help="comma-separated rule codes (default: all)")
    ap.add_argument(
        "--baseline",
        default=DEFAULT_BASELINE,
        help="baseline file of grandfathered findings (default: %(default)s)",
    )
    ap.add_argument(
        "--no-baseline", action="store_true", help="ignore the baseline (report everything as new)"
    )
    ap.add_argument(
        "--write-baseline",
        action="store_true",
        help="write ALL current findings to the baseline file and exit 0",
    )
    ap.add_argument("--json", metavar="FILE", help="write JSON findings to FILE ('-' = stdout)")
    ap.add_argument(
        "--sarif",
        metavar="FILE",
        help="write SARIF 2.1.0 findings to FILE (for codeql-action/upload-sarif)",
    )
    ap.add_argument(
        "--show-baselined", action="store_true", help="also print grandfathered findings"
    )
    ap.add_argument(
        "--show-info",
        action="store_true",
        help="also print info-severity (non-gating, unresolved-call-downgraded) findings",
    )
    ap.add_argument(
        "--summaries-cache",
        default=DEFAULT_SUMMARIES_CACHE,
        help="interprocedural summary cache file (default: %(default)s)",
    )
    ap.add_argument(
        "--no-cache",
        action="store_true",
        help="disable the interprocedural summary cache",
    )
    ap.add_argument("--list-rules", action="store_true", help="list registered rules and exit")
    ap.add_argument(
        "--fix",
        action="store_true",
        help="apply every provable autofix (post-fix re-lint + idempotence "
        "asserted before anything is written); unprovable sites are left "
        "byte-identical with a refusal reason",
    )
    ap.add_argument(
        "--dry-run-diff",
        action="store_true",
        help="with --fix: print unified diffs instead of writing",
    )
    ap.add_argument(
        "--fix-check",
        action="store_true",
        help="fail (exit 1) if any NEW finding is autofixable — the CI gate "
        "that keeps autofixable debt at zero",
    )
    args = ap.parse_args(argv)

    if args.list_rules:
        # severity + program-level flag + fixable column: a program-level
        # rule consumes the package-wide Program (call graph + summaries +
        # absint); a fixable rule has a registered proof-carrying autofixer
        fixable = set(_fixes.fixable_rules())
        for rule in all_rules():
            level = "program" if rule.program_level else "file"
            fix_col = "fixable" if rule.code in fixable else "-------"
            print(
                f"{rule.code}  {rule.name:32s} [{level:7s}] [{rule.severity}] "
                f"[{fix_col}]  {rule.description}"
            )
        return 0

    if not args.paths:
        ap.error("no paths given (try: heat_tpu/)")
    if args.fix and args.fix_check:
        ap.error("--fix and --fix-check are mutually exclusive (apply vs gate)")
    if args.fix and args.write_baseline:
        ap.error("--fix and --write-baseline are mutually exclusive")
    if args.dry_run_diff and not args.fix:
        ap.error("--dry-run-diff requires --fix")

    select = [c for c in (args.select or "").split(",") if c.strip()] or None
    want_fix = args.fix or args.fix_check
    if want_fix and select:
        try:
            selected_codes = {r.code for r in all_rules(select)}
        except ValueError as exc:
            print(f"heatlint: {exc}", file=sys.stderr)
            return 2
        fixable = set(_fixes.fixable_rules())
        if not (selected_codes & fixable):
            # mirrors the --write-baseline/--select refusal: a typo'd or
            # fixer-less selection must fail loudly, not silently fix nothing
            print(
                f"heatlint: --select {args.select!r} matches no fixable rule — "
                f"fixers exist for {sorted(fixable)}",
                file=sys.stderr,
            )
            return 2

    cache_path = None if args.no_cache else args.summaries_cache
    unresolved: list = []
    contexts: dict = {}
    program_holder: list = []
    try:
        findings = lint_paths(
            args.paths,
            select=select,
            cache_path=cache_path,
            unresolved_out=unresolved,
            contexts_out=contexts if want_fix else None,
            program_out=program_holder if want_fix else None,
        )
    except ValueError as exc:
        print(f"heatlint: {exc}", file=sys.stderr)
        return 2
    program = program_holder[0] if program_holder else None

    # info findings (unresolved-call downgrades) are reported, never gated,
    # never baselined: a baseline entry would imply a human signed off on a
    # conclusion the analysis itself says it cannot prove
    errors = [f for f in findings if f.severity == "error"]
    info = [f for f in findings if f.severity != "error"]

    # ---- autofix planning/execution (file paths still as linted) ---- #
    fix_outcome = None
    fix_attempts = None
    if want_fix:
        fix_attempts = _fixes.plan_fixes(errors, contexts, program)
    if args.fix:
        try:
            fix_outcome = _fixes.execute_fixes(
                fix_attempts, contexts, write=not args.dry_run_diff
            )
        except _fixes.FixError as exc:
            print(f"heatlint: FIX CONTRACT VIOLATION: {exc}", file=sys.stderr)
            return 2

    # normalize paths relative to the baseline file's directory so the
    # committed baseline matches regardless of how the CLI was invoked
    # (absolute path, relative path, different cwd)
    base_dir = os.path.dirname(os.path.abspath(args.baseline)) or "."

    def _norm(p: str) -> str:
        abs_p = os.path.abspath(p)
        if abs_p.startswith(base_dir + os.sep):
            return os.path.relpath(abs_p, base_dir).replace(os.sep, "/")
        return p.replace(os.sep, "/")

    for f in findings:
        f.path = _norm(f.path)
        for hop in f.trace:
            hop["path"] = _norm(hop["path"])
    for u in unresolved:
        u["caller_path"] = _norm(u["caller_path"])
    if args.write_baseline:
        if select:
            print(
                "heatlint: --write-baseline cannot be combined with --select "
                "(a rule-scoped run would silently drop every other rule's "
                "grandfathered findings from the baseline)",
                file=sys.stderr,
            )
            return 2
        # a baseline write only speaks for the files THIS run linted:
        # grandfathered findings in files outside the given paths are
        # preserved, so a narrow run can't silently shrink the baseline
        linted = {_norm(p) for p in _fw.iter_python_files(args.paths)}
        preserved = [
            _fw.Finding(
                rule=r["rule"], path=r["path"], line=r.get("line", 1), col=0,
                message=r.get("message", ""), qualname=r.get("qualname", "<module>"),
                detail=r.get("detail", ""),
            )
            for r in _fw.load_baseline_records(args.baseline)
            if r.get("path") not in linted
        ]
        write_baseline(args.baseline, list(errors) + preserved)
        print(
            f"heatlint: wrote {len(errors)} finding(s) to {args.baseline}"
            + (f" (+{len(preserved)} preserved outside the linted paths)" if preserved else "")
            + (f" ({len(info)} info finding(s) not baselined)" if info else "")
        )
        return 0

    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    new, grandfathered = split_by_baseline(errors, baseline)

    # JSON-facing fix records (findings are normalized by now, so the
    # fingerprints match the findings sections)
    fixes_json = None
    if fix_attempts is not None:
        fixes_json = {
            "applied": [
                {
                    "fingerprint": a.finding.fingerprint,
                    "rule": a.finding.rule,
                    "path": a.finding.path,
                    "line": a.finding.line,
                    "qualname": a.finding.qualname,
                    "fixer": a.fixer,
                }
                for a in fix_attempts
                if a.refusal is None and a.edits
            ],
            "refused": [
                {
                    "fingerprint": a.finding.fingerprint,
                    "rule": a.finding.rule,
                    "path": a.finding.path,
                    "line": a.finding.line,
                    "qualname": a.finding.qualname,
                    "fixer": a.fixer,
                    "reason": a.refusal,
                }
                for a in fix_attempts
                if a.refusal is not None
            ],
        }

    if args.json:
        # the unresolved bucket rides along in the machine output: the
        # honesty policy's audit trail of every call the engine could not
        # place, with its reason — never silently dropped (same for the
        # autofix refusal reasons)
        payload = render_json(
            new, grandfathered, info=info, unresolved=unresolved, fixes=fixes_json
        )
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")

    if args.sarif:
        sarif_fix_map = (
            _fixes.sarif_fixes(fix_attempts, contexts, norm=_norm)
            if fix_attempts is not None
            else None
        )
        sarif = render_sarif(
            new, grandfathered, info=info, rules=all_rules(select), fixes=sarif_fix_map
        )
        with open(args.sarif, "w", encoding="utf-8") as fh:
            fh.write(sarif + "\n")

    # ---- human-facing fix summaries + exit codes ---- #
    if args.fix_check:
        new_ids = {id(f) for f in new}
        offenders = [
            a for a in fix_attempts if a.edits and not a.refusal and id(a.finding) in new_ids
        ]
        refused_new = sum(
            1 for a in fix_attempts if a.refusal is not None and id(a.finding) in new_ids
        )
        if offenders:
            for a in offenders:
                print(
                    f"{a.finding.path}:{a.finding.line}: {a.finding.rule} is "
                    f"autofixable ({a.fixer}) — run scripts/heatlint.py --fix"
                )
            print(
                f"heatlint: --fix-check FAILED: {len(offenders)} autofixable "
                f"new finding(s) ({refused_new} unprovable refusal(s) reported only)"
            )
            return 1
        print("heatlint: --fix-check OK: no autofixable new findings")
        return 0

    if fix_outcome is not None:
        if args.dry_run_diff:
            for path in sorted(fix_outcome.diffs):
                sys.stdout.write(fix_outcome.diffs[path])
        for rec in fixes_json["refused"]:
            print(
                f"{rec['path']}:{rec['line']}: {rec['rule']} NOT fixed — {rec['reason']}"
            )
        print(
            f"heatfix: {len(fix_outcome.applied)} fix(es) "
            + ("planned [dry run]" if args.dry_run_diff else "applied")
            + f" across {len(fix_outcome.new_sources)} file(s), "
            f"{len(fix_outcome.refused)} refusal(s); post-fix re-lint clean, "
            "fix∘fix = fix"
        )
        # match by object identity, not fingerprint: fingerprints are a
        # MULTISET (two same-detail findings in one function are real), so
        # a fixed site must not absolve an unfixed sibling sharing its
        # fingerprint
        fixed_ids = {id(a.finding) for a in fix_attempts if a.edits and not a.refusal}
        remaining_new = [f for f in new if id(f) not in fixed_ids]
        if remaining_new:
            for f in remaining_new:
                print(f"{f.path}:{f.line}:{f.col}: {f.rule} {f.message} [in {f.qualname}]")
            return 1
        return 0

    print(
        render_text(
            new,
            grandfathered,
            verbose_baselined=args.show_baselined,
            info=info,
            show_info=args.show_info,
        )
    )
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
