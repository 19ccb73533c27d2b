"""Interprocedural heatlint tests (ISSUE 8 tentpole).

Covers the call-graph + effect-summary engine (analysis/callgraph.py,
analysis/summaries.py), the HT2xx rule family, the unresolved-call honesty
policy (downgrade-to-info, never a false positive), the summary cache, the
SARIF renderer, the per-directory rule config, and the single-parse
performance contract.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from heat_tpu.analysis import (
    LintContext,
    lint_paths,
    load_baseline,
    render_sarif,
)
from heat_tpu.analysis import summaries as summaries_mod
from heat_tpu.analysis.summaries import build_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "heatlint_cli_ip", os.path.join(REPO, "scripts", "heatlint.py")
)
heatlint_cli = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(heatlint_cli)


def write_pkg(tmp_path, files: dict) -> str:
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    init = pkg / "__init__.py"
    if not init.exists():
        init.write_text("")
    for name, src in files.items():
        p = pkg / name
        p.parent.mkdir(parents=True, exist_ok=True)
        if name.endswith("__init__.py") or "/" in name:
            parent_init = p.parent / "__init__.py"
            if not parent_init.exists():
                parent_init.write_text("")
        p.write_text(textwrap.dedent(src))
    return str(pkg)


def run_rules(tmp_path, files, select):
    return lint_paths([write_pkg(tmp_path, files)], select=list(select))


def make_program(tmp_path, files):
    pkg = write_pkg(tmp_path, files)
    contexts = {}
    for dirpath, _dirs, fns in os.walk(pkg):
        for fn in sorted(fns):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                with open(p) as fh:
                    ctx = LintContext(p, fh.read())
                contexts[ctx.path] = ctx
    return build_program(contexts)


# ---------------------------------------------------------------------- #
# HT201 — static desync
# ---------------------------------------------------------------------- #
class TestHT201:
    def test_cross_function_desync_flagged_where_ht102_is_silent(self, tmp_path):
        """THE acceptance fixture: a rank-conditional collective hidden two
        calls deep.  Lexical HT102 provably misses it (asserted silent);
        HT201 fires with a >=2-hop call-chain trace."""
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return _inner(comm, x)

                def _inner(comm, x):
                    return comm.Bcast(x)

                def run(comm, x):
                    if comm.rank == 0:
                        _stage(comm, x)
                    return x
            """
        }
        silent = run_rules(tmp_path, files, ["HT102"])
        assert silent == []
        fs = run_rules(tmp_path, files, ["HT201"])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "HT201" and f.severity == "error"
        assert f.qualname == "run" and f.detail == "Bcast@comm.rank"
        # entry -> _stage -> _inner (the Bcast site): >= 2 hops past entry
        assert len(f.trace) >= 3
        assert [h["qualname"] for h in f.trace] == ["run", "_stage", "_inner"]

    def test_cross_file_desync_flagged(self, tmp_path):
        files = {
            "helpers.py": """
                def stage_extra(comm):
                    return comm.Allreduce(1)
            """,
            "lib.py": """
                from .helpers import stage_extra

                def run(comm, x):
                    if comm.rank == 0:
                        stage_extra(comm)
                    return x
            """,
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.detail for f in fs] == ["Allreduce@comm.rank"]
        assert fs[0].severity == "error"
        assert fs[0].trace[-1]["qualname"] == "stage_extra"

    def test_mpdryrun_desync_worker_pattern_flaggable(self, tmp_path):
        """The chaos-CI MPDRYRUN_DESYNC_RANK shape: a rank-conditional EXTRA
        collective staged through a helper (scripts/multiprocess_dryrun.py
        stages it lexically, where HT102 already fires; one helper deep it
        is exactly HT201's territory)."""
        files = {
            "worker.py": """
                def _stage_extra(ht, comm):
                    return ht.arange(comm.size).resplit(None)

                def loop(ht, comm, pid, desync_rank, m):
                    if pid == desync_rank:
                        _stage_extra(ht, comm)
                    return m.resplit(1)
            """
        }
        assert run_rules(tmp_path, files, ["HT102"]) == []
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.detail for f in fs] == ["resplit@pid"]
        assert fs[0].severity == "error"

    def test_same_footprint_via_different_helpers_clean(self, tmp_path):
        files = {
            "lib.py": """
                def _a(comm, x):
                    return comm.Bcast(x)

                def _b(comm, x):
                    y = comm.Bcast(x)
                    return y

                def run(comm, x):
                    if comm.rank == 0:
                        return _a(comm, x)
                    else:
                        return _b(comm, x)
            """
        }
        assert run_rules(tmp_path, files, ["HT201"]) == []

    def test_lexical_vs_helper_same_collective_clean(self, tmp_path):
        # one arm stages Bcast lexically, the other through a helper — the
        # expanded footprints agree, so no desync either way
        files = {
            "lib.py": """
                def _via(comm, x):
                    return comm.Bcast(x)

                def run(comm, x):
                    if comm.rank == 0:
                        comm.Bcast(x)
                    else:
                        _via(comm, x)
            """
        }
        assert run_rules(tmp_path, files, ["HT201"]) == []

    def test_lexical_only_difference_left_to_ht102(self, tmp_path):
        # depth-0 divergence is HT102's finding; HT201 must not double-report
        files = {
            "lib.py": """
                def run(comm, x):
                    if comm.rank == 0:
                        comm.Bcast(x)
            """
        }
        assert run_rules(tmp_path, files, ["HT201"]) == []
        assert len(run_rules(tmp_path, files, ["HT102"])) == 1

    def test_rank_while_with_helper_collective_flagged(self, tmp_path):
        files = {
            "lib.py": """
                def _sync(comm, x):
                    return comm.Allgather(x)

                def drain(comm, x, n):
                    while comm.rank < n:
                        x = _sync(comm, x)
                    return x
            """
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.detail for f in fs] == ["Allgather@comm.rank"]

    def test_param_callable_downgrades_to_info(self, tmp_path):
        # the honesty policy: a callable passed as a value could stage
        # anything — report info ("cannot prove"), never a gating error
        files = {
            "lib.py": """
                def run(comm, fn, x):
                    if comm.rank == 0:
                        fn(x)
                    return x
            """
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert len(fs) == 1
        assert fs[0].severity == "info"
        assert fs[0].detail == "unproven@comm.rank"

    def test_getattr_dispatch_downgrades_to_info(self, tmp_path):
        files = {
            "lib.py": """
                def run(comm, obj, x):
                    if comm.rank == 0:
                        getattr(obj, "save")(x)
                    return x
            """
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.severity for f in fs] == ["info"]

    def test_unknown_method_receiver_is_benign_no_finding(self, tmp_path):
        # x.method() on an unknown receiver is assumed collective-free
        # (collectives are matched by NAME lexically) — no finding at all,
        # not even info: "never a false positive"
        files = {
            "lib.py": """
                import os

                def run(comm, log, path):
                    if comm.rank == 0:
                        log.write(path)
                        os.makedirs(path, exist_ok=True)
                    return path
            """
        }
        assert run_rules(tmp_path, files, ["HT201"]) == []

    def test_suppression_works(self, tmp_path):
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return comm.Bcast(x)

                def run(comm, x):
                    if comm.rank == 0:  # heatlint: disable=HT201 rank-0 ingest, peers attend via load()
                        _stage(comm, x)
                    return x
            """
        }
        assert run_rules(tmp_path, files, ["HT201"]) == []

    def test_depth0_order_mismatch_flagged_ht102_blind(self, tmp_path):
        """Both arms stage the same collective SET in a different ORDER:
        set-based HT102 is blind (asserted), and the ordered-footprint
        comparison must not hand off to it — a sequence divergence
        desynchronizes ranks exactly like a missing collective."""
        files = {
            "lib.py": """
                def run(comm, x):
                    if comm.rank == 0:
                        comm.Allreduce(x)
                        comm.Bcast(x)
                    else:
                        comm.Bcast(x)
                        comm.Allreduce(x)
            """
        }
        assert run_rules(tmp_path, files, ["HT102"]) == []
        fs = run_rules(tmp_path, files, ["HT201"])
        assert len(fs) == 1
        assert fs[0].severity == "error"
        assert "ORDER" in fs[0].message

    def test_order_mismatch_through_helpers_flagged(self, tmp_path):
        files = {
            "lib.py": """
                def _ab(comm, x):
                    comm.Allreduce(x)
                    comm.Bcast(x)

                def _ba(comm, x):
                    comm.Bcast(x)
                    comm.Allreduce(x)

                def run(comm, x):
                    if comm.rank == 0:
                        _ab(comm, x)
                    else:
                        _ba(comm, x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert len(fs) == 1 and fs[0].severity == "error"

    def test_chained_receiver_collective_seen(self, tmp_path):
        # m.resplit(None).numpy(): the receiver call stages FIRST and must
        # not be lost inside the outer call's footprint extraction
        files = {
            "lib.py": """
                def _fetch(m):
                    return m.resplit(None).numpy()

                def run(pid, m):
                    if pid == 0:
                        _fetch(m)
                    return m
            """
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.detail for f in fs] == ["resplit@pid"]


# ---------------------------------------------------------------------- #
# HT202 — transitive host sync
# ---------------------------------------------------------------------- #
class TestHT202:
    def test_sink_in_private_helper_reported_at_public_entry(self, tmp_path):
        files = {
            "lib.py": """
                def _fetch_count(x):
                    return x.sum().item()

                def truncate(x):
                    k = _fetch_count(x)
                    return k
            """
        }
        fs = run_rules(tmp_path, files, ["HT202"])
        assert len(fs) == 1
        f = fs[0]
        assert f.qualname == "truncate" and f.severity == "error"
        assert f.detail == "item@_fetch_count"
        assert [h["qualname"] for h in f.trace] == ["truncate", "_fetch_count"]

    def test_cast_of_device_returning_helper_ht101_provably_misses(self, tmp_path):
        # float(_norm(x)): no lexical device marker in the argument, so
        # HT101's heuristic cannot see it (asserted silent); the summary
        # knows _norm returns a device value
        files = {
            "lib.py": """
                import jax.numpy as jnp

                def _norm(x):
                    return jnp.sqrt(jnp.sum(x._jarray * x._jarray))

                def scale(x):
                    s = float(_norm(x))
                    return s
            """
        }
        assert run_rules(tmp_path, files, ["HT101"]) == []
        fs = run_rules(tmp_path, files, ["HT202"])
        assert len(fs) == 1
        assert fs[0].detail == "float-cast@_norm"
        assert fs[0].severity == "error"

    def test_returns_device_propagates_through_wrappers(self, tmp_path):
        files = {
            "lib.py": """
                import jax.numpy as jnp

                def _norm(x):
                    return jnp.sum(x._jarray)

                def _wrapped(x):
                    return _norm(x)

                def scale(x):
                    return float(_wrapped(x))
            """
        }
        fs = run_rules(tmp_path, files, ["HT202"])
        assert [f.detail for f in fs] == ["float-cast@_wrapped"]

    def test_suppressed_sink_propagates_as_info(self, tmp_path):
        files = {
            "lib.py": """
                def _read(x):
                    return x.sum().item()  # heatlint: disable=HT101 debug-only path

                def api(x):
                    return _read(x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT202"])
        assert [f.severity for f in fs] == ["info"]

    def test_materializer_def_is_a_barrier(self, tmp_path):
        # host_fetch_all is the sanctioned materialization API: its syncs
        # are its job, never "hidden" — nothing propagates
        files = {
            "lib.py": """
                import jax

                def host_fetch_all(arrays):
                    return [jax.device_get(a) for a in arrays]

                def api(xs):
                    return host_fetch_all(xs)
            """
        }
        assert run_rules(tmp_path, files, ["HT202"]) == []

    def test_sanctioned_module_is_a_barrier(self, tmp_path):
        files = {
            "core/io.py": """
                def save(x, path):
                    data = x.sum().item()
                    return data
            """,
            "lib.py": """
                from .core import io

                def checkpoint(x, path):
                    return io.save(x, path)
            """,
        }
        assert run_rules(tmp_path, files, ["HT202"]) == []

    def test_sink_in_public_function_consumed_there_no_cascade(self, tmp_path):
        # a public g with its own sink is HT101's finding at g; public
        # callers of g are NOT cascaded (one report per root cause)
        files = {
            "lib.py": """
                def fetch(x):
                    return x.sum().item()

                def api(x):
                    return fetch(x)
            """
        }
        assert run_rules(tmp_path, files, ["HT202"]) == []
        assert len(run_rules(tmp_path, files, ["HT101"])) == 1

    def test_nested_def_sink_propagates_to_enclosing_public(self, tmp_path):
        files = {
            "lib.py": """
                def api(x):
                    def inner():
                        return x.sum().item()
                    return inner()
            """
        }
        fs = run_rules(tmp_path, files, ["HT202"])
        assert len(fs) == 1
        assert fs[0].qualname == "api"
        assert fs[0].trace[-1]["qualname"] == "api.inner"


# ---------------------------------------------------------------------- #
# HT203 — interprocedural use-after-donate
# ---------------------------------------------------------------------- #
class TestHT203:
    def test_callee_donation_then_use_flagged_ht103_silent(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _consume(a, sh):
                    return jax.device_put(a, sh, donate=True)

                def caller(x, sh):
                    y = _consume(x, sh)
                    return x + y
            """
        }
        assert run_rules(tmp_path, files, ["HT103"]) == []
        fs = run_rules(tmp_path, files, ["HT203"])
        assert len(fs) == 1
        f = fs[0]
        assert f.detail == "x" and f.qualname == "caller" and f.severity == "error"
        assert [h["qualname"] for h in f.trace] == ["caller", "_consume"]

    def test_transitive_donation_chain(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _inner(a, sh):
                    return jax.device_put(a, sh, donate=True)

                def _outer(b, sh):
                    return _inner(b, sh)

                def api(x, sh):
                    r = _outer(x, sh)
                    return x
            """
        }
        fs = run_rules(tmp_path, files, ["HT203"])
        assert len(fs) == 1
        assert [h["qualname"] for h in fs[0].trace] == ["api", "_outer", "_inner"]

    def test_rebind_clears_taint(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _consume(a, sh):
                    return jax.device_put(a, sh, donate=True)

                def caller(x, sh):
                    x = _consume(x, sh)
                    return x
            """
        }
        assert run_rules(tmp_path, files, ["HT203"]) == []

    def test_module_level_jit_alias_donation(self, tmp_path):
        # step = jax.jit(_step, donate_argnums=(0,)) at MODULE level is
        # invisible to HT103 (which only scans function-local jits)
        files = {
            "lib.py": """
                import jax

                def _step(state, batch):
                    return state

                step = jax.jit(_step, donate_argnums=(0,))

                def train(state, batch):
                    out = step(state, batch)
                    return state, out
            """
        }
        assert run_rules(tmp_path, files, ["HT103"]) == []
        fs = run_rules(tmp_path, files, ["HT203"])
        assert [f.detail for f in fs] == ["state"]

    def test_plain_rename_alias_of_donating_helper_flagged(self, tmp_path):
        """`h = _helper` carries no lexical donation, so HT103 is blind to
        the call through the rename (asserted) — HT203 must still see it
        (only jit aliases WITH donate_argnums are HT103's)."""
        files = {
            "lib.py": """
                import jax

                def _consume(a, sh):
                    return jax.device_put(a, sh, donate=True)

                def caller(x, sh):
                    h = _consume
                    y = h(x, sh)
                    return x + y
            """
        }
        assert run_rules(tmp_path, files, ["HT103"]) == []
        fs = run_rules(tmp_path, files, ["HT203"])
        assert [f.detail for f in fs] == ["x"]

    def test_local_jit_alias_with_donate_left_to_ht103(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _step(s, b):
                    return s

                def train(state, batch):
                    prog = jax.jit(_step, donate_argnums=(0,))
                    out = prog(state, batch)
                    return state, out
            """
        }
        assert run_rules(tmp_path, files, ["HT203"]) == []
        assert len(run_rules(tmp_path, files, ["HT103"])) == 1

    def test_lexical_donate_kwarg_left_to_ht103(self, tmp_path):
        # the call site itself says donate=True: HT103's finding, not ours
        files = {
            "lib.py": """
                import jax

                def caller(x, sh):
                    y = jax.device_put(x, sh, donate=True)
                    return x + y
            """
        }
        assert run_rules(tmp_path, files, ["HT203"]) == []
        assert len(run_rules(tmp_path, files, ["HT103"])) == 1

    def test_exclusive_branch_use_not_flagged(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _consume(a, sh):
                    return jax.device_put(a, sh, donate=True)

                def caller(x, sh, fast):
                    if fast:
                        y = _consume(x, sh)
                    else:
                        y = x + 1
                    return y
            """
        }
        assert run_rules(tmp_path, files, ["HT203"]) == []


# ---------------------------------------------------------------------- #
# HT204 — transitively undeadlined blocking
# ---------------------------------------------------------------------- #
class TestHT204:
    def test_naked_wait_in_helper_reported_at_public_entry(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _fence(x):
                    jax.block_until_ready(x)

                def api(x):
                    _fence(x)
                    return x
            """
        }
        fs = run_rules(tmp_path, files, ["HT204"])
        assert len(fs) == 1
        f = fs[0]
        assert f.qualname == "api" and f.severity == "error"
        assert f.detail == "block_until_ready@_fence"
        assert [h["qualname"] for h in f.trace] == ["api", "_fence"]

    def test_barrier_through_helper_flagged(self, tmp_path):
        files = {
            "lib.py": """
                def _sync_world(comm):
                    comm.Barrier()

                def api(comm):
                    _sync_world(comm)
            """
        }
        fs = run_rules(tmp_path, files, ["HT204"])
        assert [f.detail for f in fs] == ["Barrier@_sync_world"]

    def test_deadline_at_call_site_satisfies(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _fence(x):
                    jax.block_until_ready(x)

                def api(comm, x):
                    with comm.deadline(30.0):
                        _fence(x)
                    return x
            """
        }
        assert run_rules(tmp_path, files, ["HT204"]) == []

    def test_deadline_inside_callee_satisfies(self, tmp_path):
        files = {
            "lib.py": """
                def _fence(comm, x):
                    with comm.deadline(30.0):
                        comm.Wait(x)

                def api(comm, x):
                    _fence(comm, x)
            """
        }
        assert run_rules(tmp_path, files, ["HT204"]) == []

    def test_deadline_one_hop_up_covers_two_hop_chain(self, tmp_path):
        files = {
            "lib.py": """
                def _fence(comm, x):
                    comm.Wait(x)

                def _mid(comm, x):
                    with comm.deadline(10.0):
                        _fence(comm, x)

                def api(comm, x):
                    _mid(comm, x)
            """
        }
        assert run_rules(tmp_path, files, ["HT204"]) == []

    def test_wait_in_public_function_left_to_ht107(self, tmp_path):
        files = {
            "lib.py": """
                def sync(comm):
                    comm.Barrier()

                def api(comm):
                    sync(comm)
            """
        }
        assert run_rules(tmp_path, files, ["HT204"]) == []
        fs = run_rules(tmp_path, files, ["HT107"])
        assert [f.qualname for f in fs] == ["sync"]


# ---------------------------------------------------------------------- #
# the call graph: edge cases + the unresolved-bucket honesty policy
# ---------------------------------------------------------------------- #
class TestCallGraph:
    def test_functools_wraps_decorated_helper_resolves(self, tmp_path):
        files = {
            "lib.py": """
                import functools

                def _decorate(fn):
                    @functools.wraps(fn)
                    def wrapper(*a, **k):
                        return fn(*a, **k)
                    return wrapper

                @_decorate
                def _fetch(x):
                    return x.sum().item()

                def api(x):
                    return _fetch(x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT202"])
        assert [f.detail for f in fs] == ["item@_fetch"]

    def test_jax_jit_decorated_helper_resolves(self, tmp_path):
        files = {
            "lib.py": """
                import jax

                def _stage(comm, x):
                    return comm.Bcast(x)

                @jax.jit
                def _jitted(comm, x):
                    return _stage(comm, x)

                def run(comm, x):
                    if comm.rank == 0:
                        _jitted(comm, x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.detail for f in fs] == ["Bcast@comm.rank"]

    def test_lambda_lands_in_unresolved_bucket(self, tmp_path):
        program = make_program(
            tmp_path,
            {
                "lib.py": """
                    def run(comm, x):
                        f = lambda: comm.Bcast(x)
                        if comm.rank == 0:
                            f()
                        return x
                """
            },
        )
        reasons = {u["reason"] for u in program.graph.unresolved}
        assert "lambda" in reasons
        benign = {u["reason"]: u["benign"] for u in program.graph.unresolved}
        assert benign["lambda"] is False  # poisoning: downgrades, never drops

    def test_getattr_lands_in_unresolved_bucket(self, tmp_path):
        program = make_program(
            tmp_path,
            {
                "lib.py": """
                    def run(obj, x):
                        return getattr(obj, "go")(x)
                """
            },
        )
        assert any(u["reason"] == "getattr" for u in program.graph.unresolved)

    def test_receiver_unknown_is_benign_in_bucket(self, tmp_path):
        program = make_program(
            tmp_path,
            {
                "lib.py": """
                    def run(log, x):
                        return log.write(x)
                """
            },
        )
        recs = [u for u in program.graph.unresolved if u["reason"] == "receiver-unknown"]
        assert recs and all(u["benign"] for u in recs)

    def test_self_method_resolution_through_base_class(self, tmp_path):
        files = {
            "lib.py": """
                class Base:
                    def _fetch(self, x):
                        return x.sum().item()

                class Derived(Base):
                    def read(self, x):
                        return self._fetch(x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT202"])
        assert [f.qualname for f in fs] == ["Derived.read"]
        assert fs[0].trace[-1]["qualname"] == "Base._fetch"

    def test_reexport_chase_through_init(self, tmp_path):
        files = {
            "impl.py": """
                def _stage(comm, x):
                    return comm.Allreduce(x)
            """,
            "__init__.py": """
                from .impl import _stage
            """,
            "lib.py": """
                from . import _stage

                def run(comm, x):
                    if comm.rank == 0:
                        _stage(comm, x)
            """,
        }
        fs = run_rules(tmp_path, files, ["HT201"])
        assert [f.detail for f in fs] == ["Allreduce@comm.rank"]


# ---------------------------------------------------------------------- #
# the summary cache
# ---------------------------------------------------------------------- #
class TestSummaryCache:
    SRC = """
        def _fetch(x):
            return x.sum().item()

        def api(x):
            return _fetch(x)
    """

    def _contexts(self, pkg):
        contexts = {}
        for fn in sorted(os.listdir(pkg)):
            if fn.endswith(".py"):
                p = os.path.join(pkg, fn)
                with open(p) as fh:
                    ctx = LintContext(p, fh.read())
                contexts[ctx.path] = ctx
        return contexts

    def test_cache_roundtrip_and_hit(self, tmp_path, monkeypatch):
        pkg = write_pkg(tmp_path, {"lib.py": self.SRC})
        cache = str(tmp_path / "summaries.json")
        prog1 = build_program(self._contexts(pkg), cache_path=cache)
        assert os.path.exists(cache)
        data = json.load(open(cache))
        assert data["version"] >= 1 and data["files"]
        assert prog1.sync_reports

        # a second build over IDENTICAL sources must come from the cache:
        # extraction would raise if it were (incorrectly) re-run
        def boom(ctx):
            raise AssertionError(f"cache miss: re-extracted {ctx.path}")

        monkeypatch.setattr(summaries_mod, "extract_effects", boom)
        monkeypatch.setattr(summaries_mod, "extract_structure", boom)
        prog2 = build_program(self._contexts(pkg), cache_path=cache)
        r1 = [(r.entry, r.detail, r.vis) for r in prog1.sync_reports]
        r2 = [(r.entry, r.detail, r.vis) for r in prog2.sync_reports]
        assert r1 == r2

    def test_cache_invalidates_on_edit(self, tmp_path, monkeypatch):
        pkg = write_pkg(tmp_path, {"lib.py": self.SRC})
        cache = str(tmp_path / "summaries.json")
        build_program(self._contexts(pkg), cache_path=cache)

        # edit the file: the content hash changes, so extraction MUST re-run
        (tmp_path / "pkg" / "lib.py").write_text(
            textwrap.dedent(self.SRC) + "\n# trailing comment\n"
        )
        calls = []
        real = summaries_mod.extract_effects
        monkeypatch.setattr(
            summaries_mod,
            "extract_effects",
            lambda ctx: (calls.append(ctx.path), real(ctx))[1],
        )
        build_program(self._contexts(pkg), cache_path=cache)
        assert any(p.endswith("lib.py") for p in calls)

    def test_corrupt_cache_is_a_miss_not_an_error(self, tmp_path):
        pkg = write_pkg(tmp_path, {"lib.py": self.SRC})
        cache = str(tmp_path / "summaries.json")
        with open(cache, "w") as fh:
            fh.write("{not json")
        prog = build_program(self._contexts(pkg), cache_path=cache)
        assert prog.sync_reports  # analysis still ran

    def test_findings_identical_with_and_without_cache(self, tmp_path):
        pkg = write_pkg(tmp_path, {"lib.py": self.SRC})
        cache = str(tmp_path / "summaries.json")
        cold = lint_paths([pkg], select=["HT202"], cache_path=cache)
        warm = lint_paths([pkg], select=["HT202"], cache_path=cache)
        assert [f.to_dict() for f in cold] == [f.to_dict() for f in warm]

    def test_narrow_run_preserves_out_of_scope_cache_entries(self, tmp_path):
        # a one-file invocation must not wipe the repo-wide cache: only
        # entries whose file is GONE from disk are evicted
        pkg = write_pkg(
            tmp_path, {"lib.py": self.SRC, "other.py": "def g():\n    return 1\n"}
        )
        cache = str(tmp_path / "summaries.json")
        lint_paths([pkg], select=["HT202"], cache_path=cache)
        assert len(json.load(open(cache))["files"]) >= 3  # lib, other, __init__
        lint_paths([os.path.join(pkg, "lib.py")], select=["HT202"], cache_path=cache)
        kept = json.load(open(cache))["files"]
        assert any(p.endswith("other.py") for p in kept)
        # a DELETED file's entry does get evicted on the next run
        os.remove(os.path.join(pkg, "other.py"))
        lint_paths([pkg], select=["HT202"], cache_path=cache)
        kept = json.load(open(cache))["files"]
        assert not any(p.endswith("other.py") for p in kept)


# ---------------------------------------------------------------------- #
# per-directory rule config (framework.DIR_RULE_CONFIG)
# ---------------------------------------------------------------------- #
class TestDirConfig:
    def test_benchmarks_relaxed_but_desync_rules_stay_on(self, tmp_path):
        bench = tmp_path / "benchmarks"
        bench.mkdir()
        (bench / "bench.py").write_text(
            textwrap.dedent(
                """
                import jax

                def _stage(comm, x):
                    return comm.Bcast(x)

                def measure(comm, x):
                    t = x.sum().item()          # host sync: legitimate here
                    jax.block_until_ready(x)    # timing wait: legitimate here
                    if comm.rank == 0:
                        _stage(comm, x)         # desync hazard: NOT legitimate
                    return t
                """
            )
        )
        fs = lint_paths([str(bench)])
        rules = sorted({f.rule for f in fs})
        assert "HT101" not in rules and "HT107" not in rules
        assert "HT201" in rules

    def test_library_paths_keep_full_select(self, tmp_path):
        lib = tmp_path / "somelib"
        lib.mkdir()
        (lib / "mod.py").write_text("def f(x):\n    return x.sum().item()\n")
        fs = lint_paths([str(lib)], select=["HT101"])
        assert len(fs) == 1


# ---------------------------------------------------------------------- #
# SARIF 2.1.0 renderer
# ---------------------------------------------------------------------- #
class TestSarif:
    def test_sarif_structure_and_codeflows(self, tmp_path):
        files = {
            "lib.py": """
                def _fetch(x):
                    return x.sum().item()

                def api(x):
                    return _fetch(x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT101", "HT202"])
        errors = [f for f in fs if f.severity == "error"]
        log = json.loads(render_sarif(errors, [], []))
        assert log["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in log["$schema"]
        run = log["runs"][0]
        assert run["tool"]["driver"]["name"] == "heatlint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"HT201", "HT202", "HT203", "HT204"} <= rule_ids
        results = run["results"]
        assert results and all(r["level"] == "error" for r in results)
        for r in results:
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"]
            assert loc["region"]["startLine"] >= 1
            assert "heatlintFingerprint/v1" in r["partialFingerprints"]
        flows = [r for r in results if "codeFlows" in r]
        assert flows, "interprocedural finding must carry a codeFlow"
        tf = flows[0]["codeFlows"][0]["threadFlows"][0]["locations"]
        assert len(tf) >= 2  # entry -> sink

    def test_baselined_results_carry_suppressions(self, tmp_path):
        files = {"lib.py": "def f(x):\n    return x.sum().item()\n"}
        fs = run_rules(tmp_path, files, ["HT101"])
        log = json.loads(render_sarif([], fs, []))
        res = log["runs"][0]["results"]
        assert res[0]["suppressions"][0]["kind"] == "external"
        assert res[0]["level"] == "note"

    def test_cli_json_carries_unresolved_bucket(self, tmp_path, capsys):
        # the honesty policy's audit trail: every unresolvable call with
        # its reason lands in the machine output, never silently dropped
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "lib.py").write_text(
            "def run(comm, fn, x):\n"
            "    if comm.rank == 0:\n"
            "        fn(x)\n"
            "    return x\n"
        )
        out_json = str(tmp_path / "out.json")
        heatlint_cli.main(
            [str(src_dir), "--baseline", str(tmp_path / "bl.json"),
             "--json", out_json, "--no-cache"]
        )
        capsys.readouterr()
        data = json.load(open(out_json))
        recs = data["unresolved_calls"]
        assert any(u["reason"] == "param-callable" and u["call"] == "fn" for u in recs)

    def test_cli_sarif_flag_writes_valid_log(self, tmp_path, capsys):
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "lib.py").write_text(
            "def _fetch(x):\n    return x.sum().item()\n\n"
            "def api(x):\n    return _fetch(x)\n"
        )
        sarif_path = str(tmp_path / "out.sarif")
        rc = heatlint_cli.main(
            [str(src_dir), "--baseline", str(tmp_path / "bl.json"),
             "--sarif", sarif_path, "--no-cache"]
        )
        capsys.readouterr()
        assert rc == 1  # new findings
        log = json.load(open(sarif_path))
        assert log["version"] == "2.1.0"
        assert any(r["ruleId"] == "HT202" for r in log["runs"][0]["results"])


# ---------------------------------------------------------------------- #
# performance + stdlib-only contracts
# ---------------------------------------------------------------------- #
class TestContracts:
    def test_repo_run_under_ten_seconds(self):
        """Single-parse satellite: the full repo run — every rule including
        the interprocedural passes, cold cache — stays under 10 s."""
        t0 = time.process_time()
        lint_paths(
            [
                os.path.join(REPO, "heat_tpu"),
                os.path.join(REPO, "benchmarks"),
                os.path.join(REPO, "tutorials"),
            ],
            cache_path=None,
        )
        assert time.process_time() - t0 < 10.0

    def test_cli_with_new_passes_never_imports_jax_or_numpy(self, tmp_path):
        """The jax-import-blocking contract extended to the interprocedural
        passes: the CLI (callgraph + summaries + SARIF included) completes
        with jax/numpy/torch imports BLOCKED — the CI heatlint lane installs
        nothing."""
        fixture = tmp_path / "pkg"
        fixture.mkdir()
        (fixture / "lib.py").write_text(
            "def _stage(comm, x):\n    return comm.Bcast(x)\n\n"
            "def run(comm, x):\n    if comm.rank == 0:\n        _stage(comm, x)\n"
        )
        sarif = str(tmp_path / "out.sarif")
        blocker = (
            "import sys\n"
            "class _Block:\n"
            "    def find_module(self, name, path=None):\n"
            "        if name.split('.')[0] in ('jax', 'numpy', 'torch', 'jaxlib'):\n"
            "            raise ImportError('blocked: ' + name)\n"
            "sys.meta_path.insert(0, _Block())\n"
            f"sys.argv = ['heatlint', {str(fixture)!r}, '--no-cache',\n"
            f"            '--baseline', {str(tmp_path / 'bl.json')!r},\n"
            f"            '--sarif', {sarif!r}]\n"
            "import runpy\n"
            "try:\n"
            f"    runpy.run_path({os.path.join(REPO, 'scripts', 'heatlint.py')!r}, "
            "run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    raise SystemExit(e.code)\n"
        )
        p = subprocess.run(
            [sys.executable, "-c", blocker],
            capture_output=True,
            text=True,
            timeout=120,
        )
        # exit 1 = the fixture's HT201 finding was detected, with zero
        # non-stdlib imports available
        assert p.returncode == 1, p.stderr[-2000:]
        assert "HT201" in p.stdout
        log = json.load(open(sarif))
        assert log["version"] == "2.1.0"


# ---------------------------------------------------------------------- #
# the repo gate, interprocedural edition
# ---------------------------------------------------------------------- #
class TestRepoGateInterproc:
    def test_repo_clean_with_ht2xx_and_extended_scope(self, capsys):
        """Acceptance: the repo-wide run with HT2xx enabled over heat_tpu/ +
        benchmarks/ + tutorials/ is clean vs the committed baseline."""
        rc = heatlint_cli.main(
            [
                os.path.join(REPO, "heat_tpu"),
                os.path.join(REPO, "benchmarks"),
                os.path.join(REPO, "tutorials"),
                "--no-cache",
            ]
        )
        capsys.readouterr()
        assert rc == 0

    def test_baseline_net_smaller_than_before_this_pr(self):
        """Acceptance: the interprocedural evidence FIXED grandfathered
        findings (ravel_multi_index host syncs -> one host_fetch; io.py
        sync_global_devices -> comm.deadline via _bounded_sync; the
        gaussianNB priors validation -> host-side) instead of suppressing
        them: the baseline shrank from 32 entries."""
        records = json.load(open(os.path.join(REPO, ".heatlint-baseline.json")))
        assert len(records["findings"]) <= 30  # was 32 before ISSUE 8
        baseline = load_baseline(os.path.join(REPO, ".heatlint-baseline.json"))
        gone = [
            "heat_tpu/core/factories.py:HT101:ravel_multi_index:int-cast",
            "heat_tpu/core/io.py:HT107:save_zarr:sync_global_devices",
            "heat_tpu/core/io.py:HT107:_token_ring_write:sync_global_devices",
        ]
        for fp in gone:
            assert fp not in baseline

    def test_fixed_sites_are_clean_not_suppressed(self):
        fs = lint_paths(
            [os.path.join(REPO, "heat_tpu", "core", "factories.py")], select=["HT101"]
        )
        assert [f for f in fs if f.qualname == "ravel_multi_index"] == []
        fs = lint_paths(
            [os.path.join(REPO, "heat_tpu", "core", "io.py")], select=["HT107"]
        )
        assert fs == []
