"""Static + runtime enforcement of the runtime's distributed invariants.

Two halves, one contract set:

- **heatlint** (:mod:`.framework`, :mod:`.rules`, the interprocedural
  engine :mod:`.callgraph` + :mod:`.summaries`, and the abstract-
  interpretation layer :mod:`.absint`): a plugin-based AST linter
  (CLI: ``scripts/heatlint.py``) with lexical rules HT101–HT109 (host
  syncs, SPMD-consistency, donation, byte-accounting, broadcast seeding,
  metadata immutability, deadline scopes, seq-stamp choke point, trace
  identity), the HT2xx family that propagates effect summaries through a
  package-wide call graph (static desync, transitive host sync,
  interprocedural use-after-donate, transitively undeadlined blocking),
  and the HT3xx family that reasons about *values* via a rank-taint
  lattice + symbolic ``(gshape, split, dtype)`` metadata domain
  (rank-tainted collective flow, split mismatch, payload asymmetry,
  donation-size mismatch) — each the static twin of a runtime failure
  mode.  Gates CI against a committed baseline; unresolved-call
  conclusions are downgraded to non-gating ``info``.
- **heatfix** (:mod:`.fixes`): the proof-carrying autofix layer — fixers
  registered per rule emit span splices ONLY when a safety proof holds
  (0-d + untraced host syncs → ``Communication.host_fetch``, literal-seed
  entropy → ``core/random.host_rng``, caller-proved-undeadlined waits →
  ``with comm.deadline(...)``, stale suppressions → deleted), with
  mandatory post-fix re-lint and a fix∘fix = fix idempotence assertion;
  refusal reasons ship in ``--json`` (the honesty policy, fix edition).
- **runtime sanitizer** (:mod:`heat_tpu.core.sanitation`, armed by
  ``HEAT_TPU_CHECKS=1``): a metadata-only validator at the dispatch tails
  and factory/resplit boundaries — the dynamic complement for what the
  static rules cannot see.
- **timeline** (:mod:`.timeline`): the post-hoc cross-rank timeline
  assembler — telemetry JSONL + flight rings + journals merged into one
  clock-aligned Chrome-trace/Perfetto export with critical-path blame
  (CLI: ``scripts/traceviz.py``).

See doc/source/design.md "Static contracts".
"""

from .framework import (
    Finding,
    LintContext,
    Rule,
    all_rules,
    disabled_rules_for,
    lint_file,
    lint_paths,
    load_baseline,
    register,
    render_json,
    render_sarif,
    render_text,
    split_by_baseline,
    write_baseline,
)
from . import callgraph  # noqa: F401
from . import summaries  # noqa: F401
from . import absint  # noqa: F401
from . import rules  # noqa: F401  — registers the built-in rules on import
from . import fixes  # noqa: F401  — registers the built-in fixers on import
from . import timeline  # noqa: F401

__all__ = [
    "Finding",
    "LintContext",
    "Rule",
    "absint",
    "all_rules",
    "callgraph",
    "disabled_rules_for",
    "fixes",
    "lint_file",
    "lint_paths",
    "load_baseline",
    "register",
    "render_json",
    "render_sarif",
    "render_text",
    "rules",
    "split_by_baseline",
    "summaries",
    "timeline",
    "write_baseline",
]
