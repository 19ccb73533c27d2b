"""heatlint — plugin-based AST lint framework for distributed invariants.

The runtime's load-bearing contracts (no host syncs in library code, SPMD-
consistent control flow, byte-accounted collectives, donate-once buffers,
broadcast RNG state, immutable DNDarray metadata) are enforced here as
machine-checked rules instead of conventions.  The design follows the
MUST/Umpire line of MPI correctness tools and compiler-style lint
frameworks: each invariant is a :class:`Rule` plugin that walks a parsed
module and emits :class:`Finding`s; the driver handles discovery, inline
suppressions, and a committed baseline for grandfathered findings.

Vocabulary:

- **Finding** — one rule violation at one source location, with a stable
  *fingerprint* (``path:rule:qualname:detail``) that survives unrelated
  line-number drift.
- **Suppression** — ``# heatlint: disable=HT101`` trailing comment on the
  offending line (or ``disable=all``); ``# heatlint: disable-file=HT101``
  anywhere in a file suppresses the rule for the whole file.
- **Baseline** — a committed JSON multiset of fingerprints; findings whose
  fingerprint is covered by the baseline are *grandfathered* (reported,
  but do not fail the run).  New code must be clean or explicitly
  suppressed; ``--write-baseline`` regenerates the file.

Rules register themselves with :func:`register`; :mod:`.rules` holds the
built-in set: the lexical rules HT101–HT109, the interprocedural HT2xx
family (which runs over a package-wide :class:`~.summaries.Program` built
from :mod:`.callgraph` + :mod:`.summaries`), and the abstract-
interpretation HT3xx family (rank-taint + array-metadata domains from
:mod:`.absint`, linked through the same Program).

Findings carry a ``severity``: ``"error"`` gates CI (and is what the
baseline matches); ``"info"`` is the honesty downgrade for interprocedural
conclusions that depend on an unresolved call — reported, never gating.
Interprocedural findings also carry a ``trace`` (``entry → helper → sink``,
one ``{path, qualname, line}`` hop each) rendered in text, JSON, and SARIF
``codeFlows``.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Type

__all__ = [
    "Finding",
    "LintContext",
    "Rule",
    "register",
    "all_rules",
    "lint_file",
    "lint_paths",
    "load_baseline",
    "split_by_baseline",
    "write_baseline",
    "render_text",
    "render_json",
    "render_sarif",
    "disabled_rules_for",
]

# -------------------------------------------------------------------- #
# findings
# -------------------------------------------------------------------- #


@dataclass
class Finding:
    """One rule violation at one source location."""

    rule: str  # "HT101"
    path: str  # posix-normalized, as given to the runner
    line: int
    col: int
    message: str
    qualname: str = "<module>"  # enclosing def/class chain
    detail: str = ""  # short stable token (offending name), keys the fingerprint
    severity: str = "error"  # "error" gates; "info" = unresolved-call downgrade
    # interprocedural call chain, entry -> ... -> sink; each hop
    # {"path": ..., "qualname": ..., "line": ...}
    trace: List[dict] = field(default_factory=list)

    @property
    def fingerprint(self) -> str:
        """Line-number-free identity used for baseline matching: unrelated
        edits move lines constantly, but (file, rule, enclosing def,
        offending token) only changes when the finding itself does."""
        return f"{self.path}:{self.rule}:{self.qualname}:{self.detail}"

    def to_dict(self) -> dict:
        d = {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "qualname": self.qualname,
            "detail": self.detail,
            "severity": self.severity,
            "fingerprint": self.fingerprint,
        }
        if self.trace:
            d["trace"] = list(self.trace)
        return d

    def trace_text(self) -> str:
        return " -> ".join(f"{h['path']}:{h['qualname']}" for h in self.trace)


# -------------------------------------------------------------------- #
# per-file context shared by every rule
# -------------------------------------------------------------------- #

# codes are comma-separated tokens; the capture stops at the first token
# that isn't followed by a comma, so a trailing free-text reason
# (`disable=HT101 tolerated here`) doesn't corrupt the codes — spelling
# the full comment syntax here would ARM a (stale) suppression on this
# very line, which HT110 caught the day it was born
_CODES = r"(?:[A-Za-z0-9_]+\s*,\s*)*[A-Za-z0-9_]+"
_SUPPRESS_RE = re.compile(rf"#\s*heatlint:\s*disable=({_CODES})")
_SUPPRESS_FILE_RE = re.compile(rf"#\s*heatlint:\s*disable-file=({_CODES})")


class LintContext:
    """Parsed module + the shared lookups rules need: source lines, parent
    links, enclosing-scope qualnames, inline suppressions, and a pre-order
    node index so every rule (and the interprocedural passes) share ONE
    parse + ONE walk per file instead of re-walking the tree per rule."""

    def __init__(self, path: str, source: str, tree: Optional[ast.AST] = None):
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree if tree is not None else ast.parse(source, filename=path)
        self.parents: Dict[ast.AST, ast.AST] = {}
        self._qualnames: Dict[ast.AST, str] = {}
        self._order: List[ast.AST] = []  # pre-order (document order)
        self._by_type: Dict[type, List[ast.AST]] = {}
        self._index(self.tree, None, ())
        self._line_suppressions: Dict[int, set] = {}
        self._file_suppressions: set = set()
        self._scan_suppressions()

    def _index(self, node: ast.AST, parent: Optional[ast.AST], scope: Tuple[str, ...]):
        if parent is not None:
            self.parents[node] = parent
        self._order.append(node)
        self._by_type.setdefault(type(node), []).append(node)
        self._qualnames[node] = ".".join(scope) if scope else "<module>"
        child_scope = scope
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            child_scope = scope + (node.name,)
            self._qualnames[node] = ".".join(child_scope)
        for child in ast.iter_child_nodes(node):
            self._index(child, node, child_scope)

    def walk(self, *types: type) -> List[ast.AST]:
        """All nodes (document order), optionally filtered by exact node
        types — the shared single-walk index every rule uses instead of
        ``ast.walk(ctx.tree)``."""
        if not types:
            return self._order
        if len(types) == 1:
            return self._by_type.get(types[0], [])
        seen_types = [t for t in types if t in self._by_type]
        if len(seen_types) == 1:
            return self._by_type[seen_types[0]]
        wanted = tuple(types)
        return [n for n in self._order if isinstance(n, wanted)]

    def _scan_suppressions(self) -> None:
        # tokenize so only REAL comments suppress: a docstring that merely
        # documents the `# heatlint: disable=...` syntax (this framework's
        # own module docstring, for one) must not disable anything
        try:
            tokens = tokenize.generate_tokens(io.StringIO(self.source).readline)
            comments = [
                (tok.start[0], tok.string)
                for tok in tokens
                if tok.type == tokenize.COMMENT and "heatlint" in tok.string
            ]
        except (tokenize.TokenError, IndentationError, SyntaxError):
            comments = []  # un-tokenizable source: no suppressions
        for line_no, text in comments:
            m = _SUPPRESS_FILE_RE.search(text)
            if m:
                self._file_suppressions.update(
                    c.strip().upper() for c in m.group(1).split(",") if c.strip()
                )
                continue
            m = _SUPPRESS_RE.search(text)
            if m:
                self._line_suppressions[line_no] = {
                    c.strip().upper() for c in m.group(1).split(",") if c.strip()
                }

    # ---------------- rule-facing helpers ---------------- #
    def qualname(self, node: ast.AST) -> str:
        return self._qualnames.get(node, "<module>")

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self.parents.get(node)

    def ancestors(self, node: ast.AST) -> List[ast.AST]:
        out = []
        cur = self.parents.get(node)
        while cur is not None:
            out.append(cur)
            cur = self.parents.get(cur)
        return out

    def enclosing_function(self, node: ast.AST):
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def is_suppressed(self, code: str, line: int) -> bool:
        code = code.upper()
        if code in self._file_suppressions or "ALL" in self._file_suppressions:
            return True
        on_line = self._line_suppressions.get(line, ())
        return code in on_line or "ALL" in on_line

    def finding(
        self, rule: "Rule", node: ast.AST, message: str, detail: str = ""
    ) -> Optional[Finding]:
        """Build a Finding for ``node`` unless suppressed on its line."""
        line = getattr(node, "lineno", 1)
        if self.is_suppressed(rule.code, line):
            return None
        return Finding(
            rule=rule.code,
            path=self.path,
            line=line,
            col=getattr(node, "col_offset", 0),
            message=message,
            qualname=self.qualname(node),
            detail=detail,
        )


# -------------------------------------------------------------------- #
# rule plugin protocol + registry
# -------------------------------------------------------------------- #


class Rule:
    """One invariant.  Subclass, set ``code``/``name``/``description``,
    implement :meth:`check` (per-file rules) or set ``program_level = True``
    and implement :meth:`check_program` (interprocedural rules, which
    receive the package-wide :class:`~.summaries.Program`), and decorate
    with :func:`register`.  ``severity`` is the rule's DEFAULT finding
    severity (individual findings may downgrade to ``info`` per the
    unresolved-call honesty policy) — surfaced by ``--list-rules``."""

    code: str = "HT000"
    name: str = "unnamed"
    description: str = ""
    program_level: bool = False
    severity: str = "error"

    def check(self, ctx: LintContext) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def check_program(self, program) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError


_REGISTRY: Dict[str, Type[Rule]] = {}


def register(cls: Type[Rule]) -> Type[Rule]:
    """Class decorator: add a Rule to the global registry (last wins, so a
    downstream plugin may override a built-in by reusing its code)."""
    _REGISTRY[cls.code] = cls
    return cls


def all_rules(select: Optional[Sequence[str]] = None) -> List[Rule]:
    """Instantiate registered rules (ensures built-ins are imported).

    ``select`` entries may end in ``*`` to match a code prefix
    (``HT3*`` → HT301–HT304); a wildcard matching nothing is an error,
    like an unknown literal code — a typo must not silently select
    zero rules."""
    from . import rules as _builtin  # noqa: F401  (import side effect: registration)

    codes = sorted(_REGISTRY)
    if select:
        wanted: set = set()
        for raw in select:
            pat = raw.strip().upper()
            if pat.endswith("*"):
                hits = {c for c in codes if c.startswith(pat[:-1])}
                if not hits:
                    raise ValueError(
                        f"rule pattern {raw!r} matches no registered rule (have {codes})"
                    )
                wanted |= hits
            else:
                if pat not in codes:
                    raise ValueError(
                        f"unknown rule code(s): {[pat]} (have {codes})"
                    )
                wanted.add(pat)
        codes = [c for c in codes if c in wanted]
    return [_REGISTRY[c]() for c in codes]


# -------------------------------------------------------------------- #
# per-directory rule configuration
# -------------------------------------------------------------------- #

# Lint scope is wider than library code, but not every contract applies
# everywhere: benchmarks and tutorials are host-driving entry points, so
# host syncs (HT101 + its interprocedural twin HT202), raw local entropy
# (HT105), and unbounded timing waits (HT107/HT204 — block_until_ready IS
# the measurement) are legitimate there.  Rank-conditional collectives
# (HT102/HT201), donation misuse (HT103/HT203), and the accounting/stamp
# bypasses stay ON — a desync hazard deadlocks a benchmark world exactly
# like a library one.  First matching prefix wins; the table lives here
# (not in CLI flags) so every invocation — CLI, tests, CI — agrees.
DIR_RULE_CONFIG: Tuple[Tuple[str, frozenset], ...] = (
    ("benchmarks/", frozenset({"HT101", "HT105", "HT107", "HT202", "HT204"})),
    ("tutorials/", frozenset({"HT101", "HT105", "HT107", "HT202", "HT204"})),
)


def disabled_rules_for(path: str) -> frozenset:
    """Rule codes disabled for ``path`` by the per-directory config table."""
    p = path.replace(os.sep, "/")
    for prefix, disabled in DIR_RULE_CONFIG:
        if p.startswith(prefix) or f"/{prefix}" in p:
            return disabled
    return frozenset()


# -------------------------------------------------------------------- #
# driver
# -------------------------------------------------------------------- #


def _parse_context(path: str):
    """LintContext for ``path``, or an HT000 Finding on a syntax error —
    the ONE place read/parse/error handling lives (lint_file and lint_paths
    both route through it, so the two drivers cannot drift)."""
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    try:
        return LintContext(path, source)
    except SyntaxError as exc:
        return Finding(
            rule="HT000",
            path=path.replace(os.sep, "/"),
            line=exc.lineno or 1,
            col=exc.offset or 0,
            message=f"syntax error: {exc.msg}",
            detail="syntax-error",
        )


def lint_file(path: str, rules: Sequence[Rule]) -> List[Finding]:
    ctx = _parse_context(path)
    if isinstance(ctx, Finding):
        return [ctx]
    findings: List[Finding] = []
    disabled = disabled_rules_for(ctx.path)
    for rule in rules:
        if rule.program_level or rule.code in disabled:
            continue
        findings.extend(f for f in rule.check(ctx) if f is not None)
    return findings


def iter_python_files(paths: Sequence[str]) -> List[str]:
    # dedup on realpath: overlapping args (`heatlint.py pkg/ pkg/core`, or a
    # file listed alongside its parent dir) must not lint a file twice —
    # duplicate findings would overflow the baseline's per-fingerprint count
    # and report clean code as new
    seen: set = set()
    out: List[str] = []

    def add(path: str) -> None:
        rp = os.path.realpath(path)
        if rp not in seen:
            seen.add(rp)
            out.append(path)

    for p in paths:
        if os.path.isfile(p):
            add(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(
                d for d in dirnames if d not in ("__pycache__", ".git", ".ipynb_checkpoints")
            )
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    add(os.path.join(dirpath, fn))
    return out


def lint_paths(
    paths: Sequence[str],
    select: Optional[Sequence[str]] = None,
    cache_path: Optional[str] = None,
    unresolved_out: Optional[List[dict]] = None,
    contexts_out: Optional[Dict[str, "LintContext"]] = None,
    program_out: Optional[List] = None,
) -> List[Finding]:
    """Lint ``paths`` with every selected rule — ONE parse + ONE walk index
    per file shared by all lexical rules AND the interprocedural passes,
    which additionally share the summary cache at ``cache_path`` (keyed by
    file content hash; None disables caching).  When ``unresolved_out`` is
    given, the call graph's unresolved bucket (every unresolvable call with
    its reason — the honesty policy's audit trail) is appended to it.
    ``contexts_out``/``program_out`` hand the parsed contexts and the built
    Program back to the caller (the autofix engine reuses them instead of
    re-parsing the repo); ``program_out`` forces the
    program build even when no program-level rule is selected."""
    rules = all_rules(select)
    file_rules = [r for r in rules if not r.program_level]
    program_rules = [r for r in rules if r.program_level]
    findings: List[Finding] = []
    contexts: Dict[str, LintContext] = {}
    for path in iter_python_files(paths):
        ctx = _parse_context(path)
        if isinstance(ctx, Finding):
            findings.append(ctx)
            continue
        contexts[ctx.path] = ctx
        disabled = disabled_rules_for(ctx.path)
        for rule in file_rules:
            if rule.code in disabled:
                continue
            findings.extend(f for f in rule.check(ctx) if f is not None)
    need_program = bool(program_rules) or program_out is not None
    if need_program and contexts:
        from . import summaries as _summaries  # lazy: only when HT2xx selected

        program = _summaries.build_program(contexts, cache_path=cache_path)
        for rule in program_rules:
            for f in rule.check_program(program):
                if f is None or rule.code in disabled_rules_for(f.path):
                    continue
                findings.append(f)
        if unresolved_out is not None:
            unresolved_out.extend(program.graph.unresolved)
        if program_out is not None:
            program_out.append(program)
    if contexts_out is not None:
        contexts_out.update(contexts)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


# -------------------------------------------------------------------- #
# baseline
# -------------------------------------------------------------------- #


def load_baseline_records(path: str) -> List[dict]:
    """The baseline's raw finding records ([] when absent)."""
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return list(data.get("findings", []))


def load_baseline(path: str) -> Dict[str, int]:
    """Baseline as a fingerprint → count multiset ({} when absent)."""
    counts: Dict[str, int] = {}
    for rec in load_baseline_records(path):
        fp = rec["fingerprint"]
        counts[fp] = counts.get(fp, 0) + 1
    return counts


def split_by_baseline(
    findings: Sequence[Finding], baseline: Dict[str, int]
) -> Tuple[List[Finding], List[Finding]]:
    """(new, grandfathered): each baseline fingerprint absorbs up to its
    count of matching findings; the overflow is new."""
    budget = dict(baseline)
    new: List[Finding] = []
    old: List[Finding] = []
    for f in findings:
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    data = {
        "version": 1,
        "comment": (
            "heatlint grandfathered findings. Matching is by fingerprint "
            "(path:rule:qualname:detail), not line number. Regenerate with "
            "scripts/heatlint.py --write-baseline after intentional changes; "
            "shrinking this file is always welcome, growing it needs review."
        ),
        "findings": [
            {
                "fingerprint": f.fingerprint,
                "rule": f.rule,
                "path": f.path,
                "qualname": f.qualname,
                "detail": f.detail,
                "line": f.line,  # informational only — not used for matching
                "message": f.message,
            }
            for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule))
        ],
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=2, sort_keys=False)
        fh.write("\n")


# -------------------------------------------------------------------- #
# output
# -------------------------------------------------------------------- #


def _fmt_finding(f: Finding, suffix: str = "") -> List[str]:
    lines = [f"{f.path}:{f.line}:{f.col}: {f.rule} {f.message} [in {f.qualname}]{suffix}"]
    if f.trace:
        lines.append(f"    via {f.trace_text()}")
    return lines


def render_text(
    new: Sequence[Finding],
    grandfathered: Sequence[Finding],
    verbose_baselined: bool = False,
    info: Sequence[Finding] = (),
    show_info: bool = False,
) -> str:
    lines: List[str] = []
    for f in new:
        lines.extend(_fmt_finding(f))
    if verbose_baselined:
        for f in grandfathered:
            lines.extend(_fmt_finding(f, " (baselined)"))
    if show_info:
        for f in info:
            lines.extend(_fmt_finding(f, " (info — unresolved-call downgrade)"))
    summary = (
        f"heatlint: {len(new) + len(grandfathered)} finding(s) "
        f"({len(new)} new, {len(grandfathered)} baselined)"
    )
    if info:
        summary += f", {len(info)} info (non-gating{'' if show_info else '; --show-info to list'})"
    lines.append(summary)
    return "\n".join(lines)


def render_json(
    new: Sequence[Finding],
    grandfathered: Sequence[Finding],
    info: Sequence[Finding] = (),
    unresolved: Optional[Sequence[dict]] = None,
    fixes: Optional[dict] = None,
) -> str:
    payload = {
        "version": 2,
        "new": [f.to_dict() for f in new],
        "baselined": [f.to_dict() for f in grandfathered],
        "info": [f.to_dict() for f in info],
        "counts": {
            "new": len(new),
            "baselined": len(grandfathered),
            "info": len(info),
        },
    }
    if unresolved is not None:
        payload["unresolved_calls"] = list(unresolved)
    if fixes is not None:
        # {"applied": [...], "refused": [{..., "reason": ...}]} — the
        # refusal reasons are the autofix honesty policy's audit trail
        payload["fixes"] = fixes
    return json.dumps(payload, indent=2)


# -------------------------------------------------------------------- #
# SARIF 2.1.0 (github/codeql-action/upload-sarif -> PR annotations)
# -------------------------------------------------------------------- #

_SARIF_SCHEMA = (
    "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/master/"
    "Schemata/sarif-schema-2.1.0.json"
)


def _sarif_location(path: str, line: int, col: int, message: Optional[str] = None) -> dict:
    loc = {
        "physicalLocation": {
            "artifactLocation": {"uri": path, "uriBaseId": "%SRCROOT%"},
            "region": {"startLine": max(1, line), "startColumn": max(1, col + 1)},
        }
    }
    if message:
        loc["message"] = {"text": message}
    return loc


def _sarif_result(
    f: Finding, level: str, baselined: bool = False, fix: Optional[dict] = None
) -> dict:
    result = {
        "ruleId": f.rule,
        "level": level,
        "message": {"text": f"{f.message} [in {f.qualname}]"},
        "locations": [_sarif_location(f.path, f.line, f.col)],
        "partialFingerprints": {"heatlintFingerprint/v1": f.fingerprint},
    }
    if fix is not None:
        # SARIF `fixes`: code scanning renders the concrete patch (the
        # autofix engine's planned, proof-carrying edit) next to the finding
        result["fixes"] = [fix]
    if f.trace:
        # the interprocedural call chain maps onto one SARIF threadFlow:
        # entry -> helper -> sink, one location per hop
        result["codeFlows"] = [
            {
                "threadFlows": [
                    {
                        "locations": [
                            {
                                "location": _sarif_location(
                                    h["path"],
                                    h.get("line", 1),
                                    0,
                                    f"{h['path']}:{h['qualname']}",
                                )
                            }
                            for h in f.trace
                        ]
                    }
                ]
            }
        ]
    if baselined:
        result["suppressions"] = [
            {"kind": "external", "justification": "heatlint baseline (grandfathered)"}
        ]
    return result


def render_sarif(
    new: Sequence[Finding],
    grandfathered: Sequence[Finding],
    info: Sequence[Finding] = (),
    rules: Optional[Sequence[Rule]] = None,
    fixes: Optional[Dict[str, dict]] = None,
) -> str:
    """SARIF 2.1.0 log: new findings at ``error``, info findings at
    ``note``, baselined findings at ``note`` with an external suppression
    (so code-scanning shows them resolved instead of re-announcing them).
    ``fixes`` maps finding fingerprints to SARIF fix objects (the autofix
    engine's planned patches), attached to their results."""
    fixes = fixes or {}
    rule_meta = [
        {
            "id": r.code,
            "name": r.name,
            "shortDescription": {"text": r.description or r.name},
            "defaultConfiguration": {"level": "error"},
        }
        for r in (rules if rules is not None else all_rules())
    ]
    results = (
        [_sarif_result(f, "error", fix=fixes.get(f.fingerprint)) for f in new]
        + [_sarif_result(f, "note") for f in info]
        + [
            _sarif_result(f, "note", baselined=True, fix=fixes.get(f.fingerprint))
            for f in grandfathered
        ]
    )
    log = {
        "$schema": _SARIF_SCHEMA,
        "version": "2.1.0",
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "heatlint",
                        "informationUri": "doc/source/design.md",
                        "rules": rule_meta,
                    }
                },
                "results": results,
            }
        ],
    }
    return json.dumps(log, indent=2)
