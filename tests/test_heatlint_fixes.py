"""heatfix tests (ISSUE 13 tentpole).

The proof-carrying autofix engine: every fixer gets a positive fixture
(the proof holds and the rewrite lands, re-lints clean, and is idempotent)
AND a refusal fixture per proof obligation (traced context, non-0-d value,
non-literal seed, caller-armed deadline, missing comm handle) asserting
the site is left byte-identical with the refusal reason shipped in
``--json``.  Plus: the HT110 stale-suppression rule both ways, the CLI
surface (``--fix``/``--dry-run-diff``/``--fix-check``/SARIF ``fixes``/
``--list-rules`` fixable column/``--select`` refusal), the baseline
burn-down honesty gate (every fingerprint removed from the baseline
re-lints clean UN-suppressed in the live repo).
"""

import importlib.util
import json
import os
import textwrap

import pytest

from heat_tpu.analysis import LintContext, fixes, lint_paths, summaries
from heat_tpu.analysis.framework import load_baseline_records
from heat_tpu.analysis.rules import (
    HostSyncRule,
    NakedBlockingWaitRule,
    RawEntropyRule,
    StaleSuppressionRule,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "heatlint_cli_fixes", os.path.join(REPO, "scripts", "heatlint.py")
)
heatlint_cli = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(heatlint_cli)


def _ctx(source, path="heat_tpu/cluster/somelib.py"):
    return LintContext(path, textwrap.dedent(source))


def _plan_one(rule, source, path="heat_tpu/cluster/somelib.py", with_program=False):
    ctx = _ctx(source, path)
    findings = list(rule.check(ctx))
    assert findings, "fixture must trigger the rule"
    program = (
        summaries.build_program({ctx.path: ctx}, cache_path=None)
        if with_program
        else None
    )
    attempts = fixes.plan_fixes(findings, {ctx.path: ctx}, program)
    return ctx, attempts


def _apply(ctx, attempts):
    outcome = fixes.execute_fixes(attempts, {ctx.path: ctx}, write=False)
    return outcome.new_sources.get(ctx.path, ctx.source), outcome


# ---------------------------------------------------------------------- #
# edit engine
# ---------------------------------------------------------------------- #
class TestEditEngine:
    def test_apply_edits_splices(self):
        src = "abc def ghi"
        out = fixes.apply_edits(
            src,
            [
                fixes.Edit("p", 4, 7, "XYZ"),
                fixes.Edit("p", 0, 3, "A"),
            ],
        )
        assert out == "A XYZ ghi"

    def test_overlapping_edits_raise(self):
        with pytest.raises(ValueError, match="overlapping"):
            fixes.apply_edits(
                "abcdef",
                [fixes.Edit("p", 0, 4, "x"), fixes.Edit("p", 2, 6, "y")],
            )

    def test_insertion_at_same_point(self):
        out = fixes.apply_edits("ab", [fixes.Edit("p", 1, 1, "X")])
        assert out == "aXb"

    def test_node_span_handles_unicode_lines(self):
        # ast cols are utf-8 BYTE offsets; the splice must still be correct
        src = 'x = "αβγ"\ny = float(jnp.sum(a))\n'
        ctx = LintContext("p.py", src)
        import ast

        call = next(
            n for n in ctx.walk(ast.Call)
            if getattr(n.func, "id", None) == "float"
        )
        s, e = fixes.node_span(ctx, call)
        assert src[s:e] == "float(jnp.sum(a))"

    def test_ensure_import_edit_dedupes(self):
        ctx = _ctx(
            """
            from ..core.communication import Communication
            x = 1
            """
        )
        assert (
            fixes.ensure_import_edit(
                ctx, "from ..core.communication import Communication", "Communication"
            )
            is None
        )

    def test_relative_core_prefix(self):
        assert fixes._relative_core_prefix("heat_tpu/cluster/spectral.py") == "..core"
        assert fixes._relative_core_prefix("heat_tpu/core/statistics.py") == "..core"
        assert (
            fixes._relative_core_prefix("heat_tpu/utils/data/datatools.py") == "...core"
        )
        assert fixes._relative_core_prefix("benchmarks/dispatch.py") == "heat_tpu.core"


# ---------------------------------------------------------------------- #
# HT101 fixer — host sync -> Communication.host_fetch
# ---------------------------------------------------------------------- #
class TestHostSyncFixer:
    def test_float_cast_of_reduction_fixed(self):
        ctx, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax.numpy as jnp
            def f(x):
                return float(jnp.max(x._jarray))
            """,
        )
        new_src, outcome = _apply(ctx, attempts)
        assert "float(Communication.host_fetch(jnp.max(x._jarray)))" in new_src
        assert "from ..core.communication import Communication" in new_src
        assert outcome.applied and not outcome.refused

    def test_item_inside_cast_fixed(self):
        ctx, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax.numpy as jnp
            def f(s):
                return int(jnp.sum(s > 0).item())
            """,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "int(Communication.host_fetch(jnp.sum(s > 0)))" in new_src
        assert ".item()" not in new_src

    def test_bare_item_fixed_and_relints_clean(self):
        ctx, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax.numpy as jnp
            def f(x):
                k = jnp.argmax(x._jarray).item()
                return k
            """,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "Communication.host_fetch(jnp.argmax(x._jarray)).item()" in new_src
        # the engine already asserted the fixed fingerprint is gone; double-
        # check the materializer exemption makes the rewrite lint-clean
        assert not list(HostSyncRule().check(LintContext(ctx.path, new_src)))

    def test_item_on_materialized_data_exempt_only_when_outermost(self):
        # host_fetch(x).item() (the bare-item rewrite shape) is host data —
        # exempt, including through attribute/subscript views; but a device
        # recomputation ON TOP of fetched data is a real sync again
        clean = """
        def f(x, comm):
            a = comm.host_fetch(x).item()
            b = comm.host_fetch(x).T.item()
            c = comm.host_fetch(x)[0].item()
            return a, b, c
        """
        assert list(HostSyncRule().check(_ctx(clean))) == []
        dirty = """
        import jax.numpy as jnp
        def f(x, y, comm):
            return jnp.abs(comm.host_fetch(x) - y._jarray).item()
        """
        fs = list(HostSyncRule().check(_ctx(dirty)))
        assert [f.detail for f in fs] == ["item"]

    def test_refusal_traced_decorator(self):
        ctx, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax
            import jax.numpy as jnp
            @jax.jit
            def f(x):
                return float(jnp.max(x._jarray))
            """,
        )
        assert attempts[0].refusal is not None and "traced" in attempts[0].refusal
        new_src, outcome = _apply(ctx, attempts)
        assert new_src == ctx.source  # byte-identical
        assert outcome.refused[0]["reason"] == attempts[0].refusal

    def test_refusal_nested_def(self):
        _ctx_, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax.numpy as jnp
            def outer(x):
                def body(c):
                    return float(jnp.max(x._jarray))
                return body
            """,
        )
        assert "nested def" in attempts[0].refusal

    def test_refusal_passed_to_tracer(self):
        _ctx_, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax
            import jax.numpy as jnp
            def f(x):
                return float(jnp.max(x._jarray))
            g = jax.jit(f)
            """,
        )
        assert "passed to `jit`" in attempts[0].refusal

    def test_refusal_non_zero_d(self):
        ctx, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax.numpy as jnp
            def f(x):
                return float(jnp.max(x._jarray, axis=0))
            """,
        )
        assert "not" in attempts[0].refusal and "0-d" in attempts[0].refusal
        new_src, _ = _apply(ctx, attempts)
        assert new_src == ctx.source

    def test_refusal_device_get(self):
        _ctx_, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax
            def f(x):
                return jax.device_get(x)
            """,
        )
        assert "pytrees" in attempts[0].refusal

    def test_zero_d_proof_accepts_keepdims_false_axis_none(self):
        ctx, attempts = _plan_one(
            HostSyncRule(),
            """
            import jax.numpy as jnp
            def f(x):
                return float(jnp.sum(x._jarray, axis=None, keepdims=False))
            """,
        )
        assert attempts[0].refusal is None


# ---------------------------------------------------------------------- #
# HT105 fixer — literal-seeded entropy -> core/random.host_rng
# ---------------------------------------------------------------------- #
class TestEntropyFixer:
    def test_literal_seed_rewritten(self):
        ctx, attempts = _plan_one(
            RawEntropyRule(),
            """
            import numpy as np
            def perm(n):
                return np.random.default_rng(0xC0FFEE).permutation(n)
            """,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "ht_random.host_rng(0xC0FFEE).permutation(n)" in new_src
        assert "from ..core import random as ht_random" in new_src
        assert not list(RawEntropyRule().check(LintContext(ctx.path, new_src)))

    def test_refusal_seedless(self):
        ctx, attempts = _plan_one(
            RawEntropyRule(),
            """
            import numpy as np
            def f():
                return np.random.default_rng().integers(10)
            """,
        )
        assert "seedless" in attempts[0].refusal
        new_src, _ = _apply(ctx, attempts)
        assert new_src == ctx.source

    def test_refusal_nonliteral_seed(self):
        _ctx_, attempts = _plan_one(
            RawEntropyRule(),
            """
            import numpy as np
            def f(seed):
                return np.random.default_rng(seed).integers(10)
            """,
        )
        assert "rank-uniform" in attempts[0].refusal

    def test_refusal_other_entropy_shapes(self):
        _ctx_, attempts = _plan_one(
            RawEntropyRule(),
            """
            import numpy as np
            def f():
                return np.random.randint(2**31)
            """,
        )
        assert "no mechanical route" in attempts[0].refusal


# ---------------------------------------------------------------------- #
# HT107 fixer — wrap naked waits in comm.deadline
# ---------------------------------------------------------------------- #
class TestDeadlineWrapFixer:
    def test_wait_wrapped_when_no_caller_arms(self):
        ctx, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            def fence(comm):
                comm.Barrier()
            """,
            with_program=True,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "with comm.deadline(60.0):" in new_src
        assert not list(
            NakedBlockingWaitRule().check(LintContext(ctx.path, new_src))
        )

    def test_multiline_statement_wrapped(self):
        ctx, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            import jax
            def fence(comm, xs):
                jax.block_until_ready(
                    xs
                )
            """,
            with_program=True,
        )
        new_src, _ = _apply(ctx, attempts)
        ctx2 = LintContext(ctx.path, new_src)  # must re-parse cleanly
        assert "with comm.deadline(60.0):" in new_src
        assert not list(NakedBlockingWaitRule().check(ctx2))

    def test_refusal_caller_already_arms_deadline(self):
        ctx, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            def helper(comm):
                comm.Barrier()
            def entry(comm):
                with comm.deadline(5.0):
                    helper(comm)
            """,
            with_program=True,
        )
        assert "already arms a deadline" in attempts[0].refusal
        new_src, _ = _apply(ctx, attempts)
        assert new_src == ctx.source

    def test_refusal_transitive_caller_arms_deadline(self):
        _ctx_, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            def helper(comm):
                comm.Barrier()
            def mid(comm):
                helper(comm)
            def entry(comm):
                with comm.deadline(5.0):
                    mid(comm)
            """,
            with_program=True,
        )
        assert "already arms a deadline" in attempts[0].refusal

    def test_refusal_other_class_comm_does_not_prove_handle(self):
        # a DIFFERENT class in the same file owning self.comm proves
        # nothing about this one — writing `with self.comm.deadline(...)`
        # into a comm-less class would raise AttributeError at runtime
        _ctx_, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            import jax
            class HasComm:
                def __init__(self, comm):
                    self.comm = comm
            class NoComm:
                def wait(self, x):
                    jax.block_until_ready(x)
            """,
            with_program=True,
        )
        assert "no Communication handle" in attempts[0].refusal

    def test_own_class_comm_attribute_proves_handle(self):
        ctx, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            import jax
            class Owner:
                def __init__(self, comm):
                    self.comm = comm
                def wait(self, x):
                    jax.block_until_ready(x)
            """,
            with_program=True,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "with self.comm.deadline(60.0):" in new_src

    def test_refusal_comm_bound_after_the_wait(self):
        # `comm = ...` AFTER the wait must not count: wrapping would emit
        # `with comm.deadline(...)` over an unbound local
        _ctx_, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            import jax
            def f(x, make_comm):
                jax.block_until_ready(x)
                comm = make_comm()
                return comm
            """,
            with_program=True,
        )
        assert "no Communication handle" in attempts[0].refusal

    def test_comm_bound_before_the_wait_counts(self):
        ctx, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            import jax
            def f(x, make_comm):
                comm = make_comm()
                jax.block_until_ready(x)
            """,
            with_program=True,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "with comm.deadline(60.0):" in new_src

    def test_refusal_no_comm_handle(self):
        _ctx_, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            import jax
            def f(x):
                jax.block_until_ready(x)
            """,
            with_program=True,
        )
        assert "no Communication handle" in attempts[0].refusal

    def test_idempotence_pass_keeps_cross_file_proofs(self):
        # worker.py: a fixable HT101 cast AND a naked wait whose deadline
        # is armed by caller.py.  Pass 1 fixes HT101 and refuses HT107;
        # the idempotence re-plan must see caller.py too, or the refusal
        # flips into a planned edit and the whole run dies in FixError.
        worker = _ctx(
            """
            import jax.numpy as jnp
            def work(comm, x):
                comm.Barrier()
                return float(jnp.max(x._jarray))
            """,
            path="heat_tpu/cluster/worker.py",
        )
        caller = _ctx(
            """
            from .worker import work
            def entry(comm, x):
                with comm.deadline(5.0):
                    return work(comm, x)
            """,
            path="heat_tpu/cluster/caller.py",
        )
        contexts = {worker.path: worker, caller.path: caller}
        program = summaries.build_program(contexts, cache_path=None)
        findings = []
        for rule in (HostSyncRule(), NakedBlockingWaitRule()):
            findings.extend(rule.check(worker))
        attempts = fixes.plan_fixes(findings, contexts, program)
        by_rule = {a.finding.rule: a for a in attempts}
        assert by_rule["HT101"].edits and by_rule["HT101"].refusal is None
        assert "already arms a deadline" in by_rule["HT107"].refusal
        # must NOT raise FixError (the spurious-idempotence regression)
        outcome = fixes.execute_fixes(attempts, contexts, write=False)
        assert len(outcome.applied) == 1
        assert "host_fetch" in outcome.new_sources[worker.path]

    def test_refusal_without_program_facts(self):
        _ctx_, attempts = _plan_one(
            NakedBlockingWaitRule(),
            """
            def fence(comm):
                comm.Barrier()
            """,
            with_program=False,
        )
        assert "program facts unavailable" in attempts[0].refusal


# ---------------------------------------------------------------------- #
# HT110 — stale suppressions (rule + fixer)
# ---------------------------------------------------------------------- #
class TestStaleSuppression:
    def test_stale_suppression_flagged(self):
        fs = list(
            StaleSuppressionRule().check(
                _ctx(
                    """
                    def f(x):
                        return x + 1  # heatlint: disable=HT101
                    """
                )
            )
        )
        assert [f.detail for f in fs] == ["HT101"]
        assert fs[0].rule == "HT110"

    def test_live_suppression_not_flagged(self):
        fs = list(
            StaleSuppressionRule().check(
                _ctx(
                    """
                    def f(x):
                        return x.sum().item()  # heatlint: disable=HT101
                    """
                )
            )
        )
        assert fs == []

    def test_unknown_code_flagged(self):
        fs = list(
            StaleSuppressionRule().check(
                _ctx(
                    """
                    def f(x):
                        return x.sum().item()  # heatlint: disable=HT999
                    """
                )
            )
        )
        assert [f.detail for f in fs] == ["HT999"]
        assert "no registered rule" in fs[0].message

    def test_program_level_codes_skipped(self):
        fs = list(
            StaleSuppressionRule().check(
                _ctx(
                    """
                    def f(x):
                        return x + 1  # heatlint: disable=HT202
                    """
                )
            )
        )
        assert fs == []

    def test_disable_all_stale_flagged_live_not(self):
        stale = list(
            StaleSuppressionRule().check(
                _ctx("def f(x):\n    return x + 1  # heatlint: disable=all\n")
            )
        )
        assert [f.detail for f in stale] == ["ALL"]
        live = list(
            StaleSuppressionRule().check(
                _ctx("def f(x):\n    return x.sum().item()  # heatlint: disable=all\n")
            )
        )
        assert live == []

    def test_fixer_deletes_whole_comment(self):
        ctx, attempts = _plan_one(
            StaleSuppressionRule(),
            """
            def f(x):
                return x + 1  # heatlint: disable=HT101 historic reason
            """,
        )
        new_src, _ = _apply(ctx, attempts)
        assert "heatlint" not in new_src
        assert "return x + 1\n" in new_src  # padding gone too

    def test_fixer_drops_only_stale_code_from_mixed_list(self):
        ctx, attempts = _plan_one(
            StaleSuppressionRule(),
            """
            def f(x):
                return x.sum().item()  # heatlint: disable=HT101,HT105
            """,
        )
        # HT101 is live (the .item() sync), HT105 is stale
        assert [a.finding.detail for a in attempts] == ["HT105"]
        new_src, _ = _apply(ctx, attempts)
        assert "# heatlint: disable=HT101" in new_src
        assert "HT105" not in new_src

    def test_fixer_removes_all_stale_codes_in_one_edit(self):
        # two stale codes on one comment: the sibling findings must plan
        # IDENTICAL whole-line edits (deduped), not overlapping ones that
        # would poison the idempotence assertion
        ctx, attempts = _plan_one(
            StaleSuppressionRule(),
            """
            def f(x):
                return x + 1  # heatlint: disable=HT101,HT105
            """,
        )
        assert len(attempts) == 2
        assert all(a.refusal is None for a in attempts)
        new_src, outcome = _apply(ctx, attempts)
        assert "heatlint" not in new_src
        assert outcome.applied  # engine contract held (no FixError)

    def test_fixer_mixed_live_and_two_stale_codes(self):
        ctx, attempts = _plan_one(
            StaleSuppressionRule(),
            """
            def f(x):
                return x.sum().item()  # heatlint: disable=HT101,HT105,HT106
            """,
        )
        assert sorted(a.finding.detail for a in attempts) == ["HT105", "HT106"]
        new_src, _ = _apply(ctx, attempts)
        assert "# heatlint: disable=HT101" in new_src
        assert "HT105" not in new_src and "HT106" not in new_src

    def test_fix_is_idempotent_via_engine(self):
        ctx, attempts = _plan_one(
            StaleSuppressionRule(),
            """
            def f(x):
                return x + 1  # heatlint: disable=HT106
            """,
        )
        # execute_fixes raises FixError if a second pass would still edit
        _new_src, outcome = _apply(ctx, attempts)
        assert outcome.applied


# ---------------------------------------------------------------------- #
# the CLI surface
# ---------------------------------------------------------------------- #
class TestCli:
    FIXABLE = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return float(jnp.max(x._jarray))\n"
    )

    def test_fix_check_fails_on_autofixable_new_finding(self, tmp_path, capsys):
        (tmp_path / "lib.py").write_text(self.FIXABLE)
        rc = heatlint_cli.main([str(tmp_path), "--fix-check", "--no-cache"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "autofixable" in out and "--fix" in out

    def test_fix_check_ok_on_unfixable_finding(self, tmp_path, capsys):
        (tmp_path / "lib.py").write_text(
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    return float(jnp.max(x._jarray, axis=0))\n"
        )
        rc = heatlint_cli.main([str(tmp_path), "--fix-check", "--no-cache"])
        assert rc == 0
        assert "--fix-check OK" in capsys.readouterr().out

    def test_fix_dry_run_prints_diff_and_leaves_file(self, tmp_path, capsys):
        p = tmp_path / "lib.py"
        p.write_text(self.FIXABLE)
        rc = heatlint_cli.main(
            [str(tmp_path), "--fix", "--dry-run-diff", "--no-cache"]
        )
        out = capsys.readouterr().out
        assert rc == 0  # the one new finding is fixable -> nothing remains
        assert "host_fetch" in out and "+++" in out
        assert p.read_text() == self.FIXABLE  # untouched

    def test_fix_writes_and_second_run_clean(self, tmp_path, capsys):
        p = tmp_path / "lib.py"
        p.write_text(self.FIXABLE)
        rc = heatlint_cli.main([str(tmp_path), "--fix", "--no-cache"])
        assert rc == 0
        assert "Communication.host_fetch" in p.read_text()
        capsys.readouterr()
        rc2 = heatlint_cli.main([str(tmp_path), "--fix", "--no-cache"])
        assert rc2 == 0
        assert "0 fix(es) applied" in capsys.readouterr().out

    def test_fix_exit_1_when_refused_sibling_shares_fingerprint(self, tmp_path):
        # two same-fingerprint findings (same def, same detail), one fixed
        # one refused: the refused one must still gate — identity matching,
        # not fingerprint matching
        (tmp_path / "lib.py").write_text(
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    a = float(jnp.max(x._jarray))\n"
            "    b = float(jnp.max(x._jarray, axis=0))\n"
            "    return a, b\n"
        )
        rc = heatlint_cli.main([str(tmp_path), "--fix", "--no-cache"])
        assert rc == 1

    def test_fix_exit_1_when_unfixable_new_remains(self, tmp_path, capsys):
        (tmp_path / "lib.py").write_text(
            self.FIXABLE
            + "def g(x):\n    return float(jnp.max(x._jarray, axis=0))\n"
        )
        rc = heatlint_cli.main([str(tmp_path), "--fix", "--no-cache"])
        assert rc == 1  # the refused site still gates

    def test_json_ships_refusal_reasons(self, tmp_path):
        (tmp_path / "lib.py").write_text(
            "import numpy as np\n"
            "def f(seed):\n"
            "    return np.random.default_rng(seed).integers(10)\n"
        )
        out = tmp_path / "out.json"
        heatlint_cli.main(
            [str(tmp_path), "--fix-check", "--json", str(out), "--no-cache"]
        )
        payload = json.loads(out.read_text())
        refused = payload["fixes"]["refused"]
        assert len(refused) == 1
        assert "rank-uniform" in refused[0]["reason"]
        assert refused[0]["rule"] == "HT105"

    def test_sarif_carries_fix_objects(self, tmp_path):
        (tmp_path / "lib.py").write_text(self.FIXABLE)
        out = tmp_path / "out.sarif"
        heatlint_cli.main(
            [str(tmp_path), "--fix-check", "--sarif", str(out), "--no-cache"]
        )
        sarif = json.loads(out.read_text())
        results = sarif["runs"][0]["results"]
        fixed = [r for r in results if "fixes" in r]
        assert fixed, "the fixable finding must carry a SARIF fix object"
        reps = fixed[0]["fixes"][0]["artifactChanges"][0]["replacements"]
        assert any(
            "host_fetch" in rep["insertedContent"]["text"] for rep in reps
        )

    def test_fix_with_select_matching_no_fixable_rule_refuses(self, tmp_path, capsys):
        (tmp_path / "lib.py").write_text(self.FIXABLE)
        rc = heatlint_cli.main(
            [str(tmp_path), "--fix", "--select", "HT102", "--no-cache"]
        )
        assert rc == 2
        assert "matches no fixable rule" in capsys.readouterr().err

    def test_fix_with_select_matching_fixable_rule_ok(self, tmp_path):
        (tmp_path / "lib.py").write_text(self.FIXABLE)
        rc = heatlint_cli.main(
            [str(tmp_path), "--fix", "--select", "HT101", "--no-cache"]
        )
        assert rc == 0

    def test_list_rules_has_fixable_column(self, capsys):
        heatlint_cli.main(["--list-rules"])
        out = capsys.readouterr().out
        assert "[fixable]" in out
        ht101 = next(ln for ln in out.splitlines() if ln.startswith("HT101"))
        ht102 = next(ln for ln in out.splitlines() if ln.startswith("HT102"))
        assert "[fixable]" in ht101 and "[fixable]" not in ht102


# ---------------------------------------------------------------------- #
# baseline burn-down honesty gate
# ---------------------------------------------------------------------- #
class TestBaselineBurnDown:
    # every fingerprint removed from the baseline this PR, by file: the
    # burned sites must re-lint clean UN-suppressed in the live repo —
    # asserting each removal was a real code fix, never a suppression
    BURNED = {
        "heat_tpu/cluster/spectral.py": [("HT101", "Spectral.fit", "item")],
        "heat_tpu/core/statistics.py": [
            ("HT101", "bincount", "item"),
            ("HT101", "histc", "float-cast"),
        ],
        "heat_tpu/decomposition/dmd.py": [("HT101", "DMD.fit", "item")],
        "heat_tpu/decomposition/pca.py": [
            ("HT101", "PCA.fit", "int-cast"),
            ("HT101", "PCA.fit", "float-cast"),
        ],
        "heat_tpu/naive_bayes/gaussianNB.py": [
            ("HT101", "GaussianNB.fit", "float-cast"),
            ("HT101", "GaussianNB.partial_fit", "bool-cast"),
            ("HT101", "GaussianNB.partial_fit", "float-cast"),
        ],
        "heat_tpu/parallel/sample_sort.py": [
            ("HT105", "_shuffle_perm", "np.random.default_rng")
        ],
        "heat_tpu/regression/lasso.py": [("HT101", "Lasso.fit", "float-cast")],
        "heat_tpu/utils/data/datatools.py": [
            ("HT105", "Dataset.shuffle", "np.random.randint"),
            ("HT105", "Dataset.ishuffle_start", "np.random.randint"),
        ],
        "heat_tpu/utils/data/mnist.py": [
            ("HT105", "_synthetic", "np.random.default_rng")
        ],
    }

    def test_baseline_shrunk_to_at_most_five(self):
        records = load_baseline_records(os.path.join(REPO, ".heatlint-baseline.json"))
        assert len(records) <= 5
        # the survivors are profiler's deliberate measurement syncs only
        assert {r["path"] for r in records} == {"heat_tpu/utils/profiler.py"}

    def test_burned_sites_relint_clean_unsuppressed(self):
        for rel, burned in self.BURNED.items():
            path = os.path.join(REPO, rel)
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            # honesty: the fix must not be a suppression in disguise
            assert "heatlint: disable" not in src, rel
            ctx = LintContext(rel, src)
            found = {
                (f.rule, f.qualname, f.detail)
                for rule in (HostSyncRule(), RawEntropyRule())
                for f in rule.check(ctx)
            }
            for sig in burned:
                assert sig not in found, f"{rel}: {sig} resurfaced"

    def test_repo_fix_dry_run_plans_nothing(self):
        # the repo is fully burned down: a repo-wide fix pass must be a
        # no-op (and the engine's idempotence contract holds trivially)
        contexts: dict = {}
        program_holder: list = []
        findings = lint_paths(
            [os.path.join(REPO, "heat_tpu")],
            cache_path=None,
            contexts_out=contexts,
            program_out=program_holder,
        )
        errors = [f for f in findings if f.severity == "error"]
        attempts = fixes.plan_fixes(errors, contexts, program_holder[0])
        assert [a for a in attempts if a.edits] == []
