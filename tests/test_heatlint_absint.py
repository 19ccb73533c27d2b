"""Abstract-interpretation heatlint tests (ISSUE 12 tentpole).

Covers the rank-taint lattice and array-metadata domain themselves
(join/widening/loop convergence, taint through summaries and tuple
returns, metadata through resplit and binary-op promotion), the HT301–
HT304 rules (positive AND negative fixtures — the honesty policy means a
value of unknown origin never gates), the analysis-schema cache revision,
the ``--select`` prefix wildcards, the ``--list-rules`` severity/level
columns, and a determinism assertion (two runs, identical findings order).
"""

import importlib.util
import json
import os
import textwrap

import pytest

from heat_tpu.analysis import LintContext, absint, lint_paths
from heat_tpu.analysis import summaries as summaries_mod
from heat_tpu.analysis.summaries import (
    ANALYSIS_SCHEMA_REV,
    CACHE_VERSION,
    build_program,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "heatlint_cli_absint", os.path.join(REPO, "scripts", "heatlint.py")
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
        p.write_text(textwrap.dedent(src))
    return str(pkg)


def run_rules(tmp_path, files, select):
    return lint_paths([write_pkg(tmp_path, files)], select=list(select))


def make_program(tmp_path, files, cache_path=None):
    pkg = write_pkg(tmp_path, files)
    contexts = {}
    for dirpath, _dirs, fns in os.walk(pkg):
        for fn in sorted(fns):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                with open(p) as fh:
                    ctx = LintContext(p, fh.read())
                contexts[ctx.path] = ctx
    return build_program(contexts, cache_path=cache_path)


# ---------------------------------------------------------------------- #
# the abstract domains themselves
# ---------------------------------------------------------------------- #
class TestMetadataDomain:
    def test_meta_join_agreement_survives(self):
        a = absint._meta([8, 4], 0, "float32")
        b = absint._meta([8, 4], 0, "float32")
        assert absint.meta_join(a, b) == a

    def test_meta_join_disagreement_widens_fieldwise(self):
        a = absint._meta([8, 4], 0, "float32")
        b = absint._meta([8, 2], 1, "float64")
        j = absint.meta_join(a, b)
        assert j["dims"] == [8, "?"]
        assert j["split"] == "?" and j["dtype"] == "?"

    def test_meta_join_with_top_is_top(self):
        a = absint._meta([8], 0, "float32")
        assert absint.meta_join(a, None) is None
        assert absint.meta_join(None, a) is None

    def test_join_taint_sets_union(self):
        a = absint._meta([8], 0, "f32", shape_taint={"rank"})
        b = absint._meta([8], 0, "f32", shape_taint={"param:0"})
        assert absint.meta_join(a, b)["shape_taint"] == ["param:0", "rank"]

    def test_promote_split_matches_dispatch_tail(self):
        # __binary_op: replicated adopts the other side's split
        assert absint.promote_split(None, 1) == 1
        assert absint.promote_split(0, None) == 0
        assert absint.promote_split(0, 0) == 0
        assert absint.promote_split("?", 0) == "?"
        # two concrete different splits: the caller (HT302) flags it; the
        # promoted result is unknown (the tail resplits one operand)
        assert absint.promote_split(0, 1) == "?"


class TestInterpreterConvergence:
    def _function_record(self, tmp_path, src, qual):
        program = make_program(tmp_path, {"lib.py": src})
        view = program.absint
        key = next(k for k in view.functions if k[1] == qual)
        return view, key, view.functions[key]

    def test_loop_taint_reaches_fixpoint(self, tmp_path):
        # n picks up rank through the loop-carried dependency — one pass
        # misses it, the fixpoint must not (and must terminate)
        view, key, rec = self._function_record(
            tmp_path,
            """
            def f(comm, x):
                n = 0
                acc = 1
                for i in range(4):
                    acc = acc + n
                    n = n + comm.rank
                return acc
            """,
            "f",
        )
        v = view.resolve_tokens(key, rec["ret_taint"])
        assert v.rank

    def test_long_rename_chain_converges_past_constant_cap(self, tmp_path):
        # a loop-carried rename chain longer than the base iteration cap:
        # the cap scales with the number of stored names, so the taint
        # still reaches the head of the chain
        chain = "\n".join(f"        v{i} = v{i + 1}" for i in range(9))
        src = (
            "def f(comm, x):\n"
            "    v9 = 0\n"
            "    v0 = 0\n"
            "    for i in range(4):\n"
            f"{chain}\n"
            "        v9 = comm.rank\n"
            "    return v0\n"
        )
        view, key, rec = self._function_record(tmp_path, src, "f")
        assert view.resolve_tokens(key, rec["ret_taint"]).rank

    def test_loop_metadata_widens_instead_of_diverging(self, tmp_path):
        # the split flips every iteration: the domain must converge (to an
        # unknown split), never oscillate forever
        view, key, rec = self._function_record(
            tmp_path,
            """
            def f(ht):
                a = ht.zeros((8, 4), split=0)
                for i in range(3):
                    a = a.resplit(1).resplit(0)
                return a
            """,
            "f",
        )
        assert rec["ret_metas"]  # analysis terminated and recorded a return

    def test_branch_implicit_flow_taints_assigned_names(self, tmp_path):
        view, key, rec = self._function_record(
            tmp_path,
            """
            def f(comm):
                if comm.rank == 0:
                    n = 1
                else:
                    n = 2
                return n
            """,
            "f",
        )
        assert view.resolve_tokens(key, rec["ret_taint"]).rank

    def test_ifexp_implicit_flow(self, tmp_path):
        view, key, rec = self._function_record(
            tmp_path,
            "def f(comm):\n    return 1 if comm.rank == 0 else 2\n",
            "f",
        )
        assert view.resolve_tokens(key, rec["ret_taint"]).rank

    def test_untainted_stays_untainted(self, tmp_path):
        view, key, rec = self._function_record(
            tmp_path,
            "def f(comm):\n    n = comm.size\n    return n * 2\n",
            "f",
        )
        v = view.resolve_tokens(key, rec["ret_taint"])
        assert not v.rank  # world size is rank-uniform

    def test_tuple_return_element_precision(self, tmp_path):
        # (nproc, rank) helpers: unpacking must NOT smear the rank
        # element's taint onto nproc (the io.py _proc_info shape)
        program = make_program(
            tmp_path,
            {
                "lib.py": """
                    def _proc_info(comm):
                        return comm.size, comm.rank

                    def f(comm):
                        nproc, rank = _proc_info(comm)
                        return nproc

                    def g(comm):
                        nproc, rank = _proc_info(comm)
                        return rank
                """
            },
        )
        view = program.absint
        kf = next(k for k in view.functions if k[1] == "f")
        kg = next(k for k in view.functions if k[1] == "g")
        assert not view.resolve_tokens(kf, view.functions[kf]["ret_taint"]).rank
        assert view.resolve_tokens(kg, view.functions[kg]["ret_taint"]).rank

    def test_ret_verdict_memo_populated_for_cycle_free_chains(self, tmp_path):
        # the return-taint memo must actually fill on cycle-free chains —
        # repo-wide resolution cost depends on it
        program = make_program(
            tmp_path,
            {
                "lib.py": """
                    def _inner(comm):
                        return comm.rank

                    def _outer(comm):
                        return _inner(comm)

                    def f(comm, x):
                        if _outer(comm) == 0:
                            comm.Bcast(x)
                """
            },
        )
        view = program.absint
        k_inner = next(k for k in view.functions if k[1] == "_inner")
        k_outer = next(k for k in view.functions if k[1] == "_outer")
        kf = next(k for k in view.functions if k[1] == "f")
        site = view.functions[kf]["flow_sites"][0]
        assert view.resolve_tokens(kf, site["taint"]).rank
        assert k_inner in view._ret_verdicts and k_outer in view._ret_verdicts
        # a recursive function's verdict is NOT memoized (stack-specific cut)
        program2 = make_program(
            tmp_path,
            {
                "rec.py": """
                    def spin(comm, n):
                        if n:
                            return spin(comm, n - 1)
                        return comm.rank
                """
            },
        )
        view2 = program2.absint
        ks = next(k for k in view2.functions if k[1] == "spin")
        v = view2.ret_verdict(ks)
        assert v.rank  # the base case's evidence still resolves
        assert ks not in view2._ret_verdicts  # cut results stay unmemoized

    def test_metadata_through_resplit_and_promotion(self, tmp_path):
        view, key, rec = self._function_record(
            tmp_path,
            """
            def f(ht):
                a = ht.zeros((8, 4), split=1).resplit(0)
                b = ht.ones((8, 4))
                return a + b
            """,
            "f",
        )
        cm = view.concrete_meta(key, rec["ret_metas"][0])
        assert cm["dims"] == [8, 4]
        assert cm["split"] == 0  # resplit rewrote it; promotion kept it


# ---------------------------------------------------------------------- #
# HT301 — rank-tainted collective flow
# ---------------------------------------------------------------------- #
class TestHT301:
    def test_dataflow_branch_ht102_and_ht201_blind(self, tmp_path):
        """THE acceptance fixture: the rank test goes through a LOCAL, so
        lexical HT102 and marker-based HT201 are both silent (asserted);
        the taint lattice proves the derivation."""
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return comm.Bcast(x)

                def run(comm, x):
                    n = comm.rank
                    if n == 0:
                        _stage(comm, x)
                    return x
            """
        }
        assert run_rules(tmp_path, files, ["HT102"]) == []
        assert run_rules(tmp_path, files, ["HT201"]) == []
        fs = run_rules(tmp_path, files, ["HT301"])
        assert len(fs) == 1
        f = fs[0]
        assert f.severity == "error" and f.qualname == "run"
        assert f.detail == "Bcast@if"
        assert f.trace  # codeFlow material

    def test_rank_loop_bound_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    def f(comm, x):
                        k = comm.rank + 1
                        for i in range(k):
                            comm.Allreduce(x)
                """
            },
            ["HT301"],
        )
        assert [f.detail for f in fs] == ["Allreduce@for"]

    def test_rank_collective_argument_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {"lib.py": "def f(comm, x):\n    comm.Bcast(x, root=comm.rank)\n"},
            ["HT301"],
        )
        assert [f.detail for f in fs] == ["Bcast:kw:root"]

    def test_interprocedural_param_sink_with_chain(self, tmp_path):
        files = {
            "lib.py": """
                def _post(comm, x, n):
                    for i in range(n):
                        comm.Bcast(x)

                def run(comm, x):
                    _post(comm, x, comm.rank)
            """
        }
        fs = run_rules(tmp_path, files, ["HT301"])
        assert len(fs) == 1
        assert fs[0].qualname == "run"
        assert [h["qualname"] for h in fs[0].trace] == ["run", "_post"]

    def test_taint_through_return_summary(self, tmp_path):
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return comm.Bcast(x)

                def _myrank(comm):
                    return comm.rank

                def run(comm, x):
                    n = _myrank(comm)
                    if n == 0:
                        _stage(comm, x)
            """
        }
        fs = run_rules(tmp_path, files, ["HT301"])
        assert [f.qualname for f in fs] == ["run"]

    def test_lexical_marker_left_to_ht102_ht201(self, tmp_path):
        # `if comm.rank == 0:` is HT102's (lexical) / HT201's (call-borne)
        files = {
            "lib.py": """
                def run(comm, x):
                    if comm.rank == 0:
                        comm.Bcast(x)
            """
        }
        assert run_rules(tmp_path, files, ["HT301"]) == []
        assert len(run_rules(tmp_path, files, ["HT102"])) == 1

    def test_both_arms_same_traffic_clean(self, tmp_path):
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return comm.Bcast(x)

                def run(comm, x):
                    n = comm.rank
                    if n == 0:
                        _stage(comm, x)
                    else:
                        comm.Bcast(x)
            """
        }
        assert run_rules(tmp_path, files, ["HT301"]) == []

    def test_unknown_origin_never_gates(self, tmp_path):
        # cfg.workers is unanalyzable — the honesty policy: no finding
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return comm.Bcast(x)

                def run(comm, x, cfg):
                    n = cfg.workers
                    if n == 0:
                        _stage(comm, x)
            """
        }
        assert run_rules(tmp_path, files, ["HT301"]) == []

    def test_raw_lax_collective_operand_exempt(self, tmp_path):
        # the masked-psum Bcast idiom: axis_index feeds the OPERAND of a
        # traced lax collective — per-shard values are the semantics
        files = {
            "lib.py": """
                from jax import lax
                import jax.numpy as jnp

                def bcast(x, axis, root):
                    mine = lax.axis_index(axis) == root
                    contrib = jnp.where(mine, x, jnp.zeros_like(x))
                    return lax.psum(contrib, axis)
            """
        }
        assert run_rules(tmp_path, files, ["HT301"]) == []

    def test_curried_call_keeps_inner_record(self, tmp_path):
        # `make(comm.rank)(7)`: inner and outer call share (line, col) —
        # only the end offsets distinguish them, and a record collision
        # overwrote the inner call's rank-tainted argument with the outer
        # call's.  The curried OUTER call itself is dynamic-expression
        # (poisoning), so the honest verdict is `unknown` — never `rank`,
        # never silently untainted.
        program = make_program(
            tmp_path,
            {
                "lib.py": """
                    def make_getter(base):
                        def get(off):
                            return base + off
                        return get

                    def f(comm, buf):
                        n = make_getter(comm.rank)(7)
                        comm.Bcast(buf, root=n)
                """
            },
        )
        view = program.absint
        kf = next(k for k in view.functions if k[1] == "f")
        rec = view.functions[kf]
        # BOTH calls recorded distinctly: the inner keeps its rank arg
        inner = next(
            c for c in rec["calls"] if c["desc"]["attr"] == "make_getter"
        )
        outer = next(
            c for c in rec["calls"] if c["desc"]["dynamic"] == "dynamic-expression"
        )
        assert "rank" in inner["arg_taints"][0]
        assert outer is not inner
        site = rec["coll_sites"][0]
        v = view.resolve_tokens(kf, site["kw_taints"]["root"])
        assert v.unknown and not v.rank  # honesty: unknown, not silent

    def test_suppression_honored(self, tmp_path):
        files = {
            "lib.py": """
                def _stage(comm, x):
                    return comm.Bcast(x)

                def run(comm, x):
                    n = comm.rank
                    if n == 0:  # heatlint: disable=HT301 rank-0 ingest, peers attend in load()
                        _stage(comm, x)
            """
        }
        assert run_rules(tmp_path, files, ["HT301"]) == []


# ---------------------------------------------------------------------- #
# HT302 — split mismatch at binary ops
# ---------------------------------------------------------------------- #
class TestHT302:
    def test_direct_mismatch_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        b = ht.ones((8, 4), split=1)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert [f.detail for f in fs] == ["Add:split0x1"]
        assert fs[0].severity == "error"

    def test_mismatch_through_promotion_chain(self, tmp_path):
        # c inherits split 0 through the binary-op promotion, then meets d
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        b = ht.ones((8, 4))
                        c = a + b
                        d = ht.zeros((8, 4), split=1)
                        return c * d
                """
            },
            ["HT302"],
        )
        assert [f.detail for f in fs] == ["Mult:split0x1"]

    def test_mismatch_through_wrapper_return(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def _mk():
                        return ht.zeros((8, 4), split=1)

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        return a + _mk()
                """
            },
            ["HT302"],
        )
        assert [f.detail for f in fs] == ["Add:split0x1"]

    def test_numpy_like_factory_mints_no_dndarray_meta(self, tmp_path):
        # np.zeros_like(a) returns a HOST array: inheriting the DNDarray
        # prototype's split minted a provably-wrong operand for HT302
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import numpy as np
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        host = np.zeros_like(a)
                        return host + ht.zeros((8, 4), split=1)
                """
            },
            ["HT302"],
        )
        assert fs == []

    def test_free_function_resplit_form_tracked(self, tmp_path):
        # ht.resplit(x, 0) — the module-qualified FREE form: args[0] is the
        # array and args[1] the axis; misreading it as a method on `ht`
        # dropped the metadata and recorded the wrong axis
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        b = ht.resplit(ht.ones((8, 4), split=1), 0)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert fs == []  # the resplit reconciled the splits
        fs = run_rules(
            tmp_path,
            {
                "lib2.py": """
                    import heat_tpu as ht

                    def g():
                        a = ht.zeros((8, 4), split=0)
                        b = ht.resplit(ht.ones((8, 4), split=0), 1)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert [f.detail for f in fs] == ["Add:split0x1"]

    def test_resplit_reconciles_clean(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        b = ht.ones((8, 4), split=1).resplit(0)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert fs == []

    def test_replicated_operand_clean(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 4), split=0)
                        b = ht.ones((8, 4))
                        return a + b
                """
            },
            ["HT302"],
        )
        assert fs == []

    def test_broadcast_alignment_clean(self, tmp_path):
        # (4,) split 0 + (8, 4) split 1: after right-alignment both are
        # the same output axis — the dispatch tail does NOT redistribute
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros(4, split=0)
                        b = ht.ones((8, 4), split=1)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert fs == []

    def test_unknown_ndim_never_aligns_into_a_false_mismatch(self, tmp_path):
        # a variable shape could be ANY rank: alignment arithmetic on a
        # guessed ndim must not fire on operands with IDENTICAL splits
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(shp):
                        a = ht.zeros(shp, split=1)
                        b = ht.ones((4, 5), split=1)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert fs == []

    def test_star_d_factories_get_true_ndim(self, tmp_path):
        # rand/randn are *d-style: randn(4, 5, split=1) is 2-D — reading
        # args[0] as "the shape" would fabricate ndim 1 and a false
        # alignment mismatch against a same-split 2-D operand
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.random.randn(4, 5, split=1)
                        b = ht.zeros((4, 5), split=1)
                        return a + b
                """
            },
            ["HT302"],
        )
        assert fs == []

    def test_matmul_mixed_split_is_routing_not_mismatch(self, tmp_path):
        # all eight split cases of matmul are supported by design
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f():
                        a = ht.zeros((8, 8), split=0)
                        b = ht.ones((8, 8), split=1)
                        return a @ b
                """
            },
            ["HT302"],
        )
        assert fs == []


# ---------------------------------------------------------------------- #
# HT303 — collective payload asymmetry
# ---------------------------------------------------------------------- #
class TestHT303:
    def test_rank_shaped_payload_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        x = ht.zeros((comm.rank + 1, 4))
                        comm.Allgather(x)
                """
            },
            ["HT303"],
        )
        assert [f.detail for f in fs] == ["Allgather:gshape"]
        assert fs[0].severity == "error"

    def test_rank_selected_dtype_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm, dt_small, dt_big):
                        dt = dt_small if comm.rank == 0 else dt_big
                        x = ht.zeros((8, 4), dtype=dt)
                        comm.Allreduce(x)
                """
            },
            ["HT303"],
        )
        assert [f.detail for f in fs] == ["Allreduce:dtype"]

    def test_wrapper_shape_through_nested_call_keeps_binding(self, tmp_path):
        # the wrapper's shape flows through an EXTERNAL call of its param
        # (`zeros((pad(n), 4))`): the caller's binding must survive the
        # nested-call hop, or rank-derived shapes one helper deep vanish
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht
                    import math

                    def _mk(n):
                        return ht.zeros((math.ceil(n), 4))

                    def f(comm):
                        x = _mk(comm.rank)
                        comm.Allgather(x)
                """
            },
            ["HT303"],
        )
        assert [f.detail for f in fs] == ["Allgather:gshape"]

    def test_payload_shape_through_wrapper_binding(self, tmp_path):
        # the wrapper's shape parameter binds to comm.rank at the call
        # site — cross-frame metadata taint must rebind, not copy
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def _mk(n):
                        return ht.zeros((n, 4))

                    def f(comm):
                        x = _mk(comm.rank)
                        comm.Allgather(x)
                """
            },
            ["HT303"],
        )
        assert [f.detail for f in fs] == ["Allgather:gshape"]

    def test_linspace_bounds_do_not_taint_shape(self, tmp_path):
        # linspace's shape is num alone; rank-derived BOUNDS set values,
        # not the fingerprint — (100,) is rank-uniform here
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        x = ht.linspace(0.0, comm.rank, 100)
                        comm.Allreduce(x)
                """
            },
            ["HT303"],
        )
        assert fs == []

    def test_linspace_rank_num_flagged(self, tmp_path):
        # …but a rank-derived num= IS a payload-shape divergence
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        x = ht.linspace(0.0, 1.0, num=comm.rank + 2)
                        comm.Allreduce(x)
                """
            },
            ["HT303"],
        )
        assert [f.detail for f in fs] == ["Allreduce:gshape"]

    def test_uniform_payload_clean(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm, n):
                        x = ht.zeros((n, 4), split=0)
                        comm.Allgather(x)
                """
            },
            ["HT303"],
        )
        assert fs == []


# ---------------------------------------------------------------------- #
# HT304 — donation-size mismatch
# ---------------------------------------------------------------------- #
class TestHT304:
    def test_dtype_mismatch_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        src = ht.zeros((8, 4), dtype="float64")
                        dst = ht.zeros((8, 4), dtype="float32")
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert len(fs) == 1
        assert "dtype float64 vs float32" in fs[0].message

    def test_shape_mismatch_flagged(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        src = ht.zeros((8, 4))
                        dst = ht.zeros((4, 4))
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert len(fs) == 1
        assert "shape (8, 4) vs (4, 4)" in fs[0].message

    def test_matching_donation_clean(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        src = ht.zeros((8, 4), dtype="float32")
                        dst = ht.zeros((8, 4), dtype="float32")
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert fs == []

    def test_unknown_shapes_never_gate(self, tmp_path):
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    def f(comm, src, dst):
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert fs == []

    def test_dtype_aliases_are_not_a_mismatch(self, tmp_path):
        # types.py: float IS float32 — aliasing succeeds at runtime
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        src = ht.zeros((4,), dtype=float)
                        dst = ht.zeros((4,), dtype=ht.float32)
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert fs == []

    def test_dtype_forwarding_is_unknown_not_concrete(self, tmp_path):
        # dtype=x.dtype forwards an existing dtype: fabricating the
        # concrete string "dtype" from the attr name made this a
        # "provable" mismatch against float32
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm, x):
                        src = ht.zeros((4, 4), dtype=x.dtype)
                        dst = ht.zeros((4, 4), dtype=ht.float32)
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert fs == []

    def test_randint_low_is_not_a_shape(self, tmp_path):
        # randint(0, 10, size=(4,)): args[0] is `low`, not the shape —
        # minting dims [0] from it fabricated a shape mismatch
        fs = run_rules(
            tmp_path,
            {
                "lib.py": """
                    import heat_tpu as ht

                    def f(comm):
                        src = ht.random.randint(0, 10, size=(4,))
                        dst = ht.zeros((4,))
                        comm.Allreduce(src, out=dst, donate=True)
                """
            },
            ["HT304"],
        )
        assert fs == []


# ---------------------------------------------------------------------- #
# the analysis-schema cache revision
# ---------------------------------------------------------------------- #
class TestCacheSchemaRevision:
    SRC = """
        def _stage(comm, x):
            return comm.Bcast(x)

        def run(comm, x):
            n = comm.rank
            if n == 0:
                _stage(comm, x)
    """

    def _mutate_cache(self, cache_file, **changes):
        data = json.load(open(cache_file))
        data.update(changes)
        json.dump(data, open(cache_file, "w"))

    def test_old_schema_rev_is_a_full_miss(self, tmp_path, monkeypatch):
        cache = str(tmp_path / "summaries.json")
        make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        assert json.load(open(cache))["schema"] == ANALYSIS_SCHEMA_REV
        # an older analyzer wrote this cache: same content hashes, but the
        # facts predate the HT3xx atoms — MUST re-extract, not silently
        # serve fact-free summaries
        self._mutate_cache(cache, schema=ANALYSIS_SCHEMA_REV - 1)
        calls = []
        real = summaries_mod.extract_effects
        monkeypatch.setattr(
            summaries_mod,
            "extract_effects",
            lambda ctx: (calls.append(ctx.path), real(ctx))[1],
        )
        program = make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        assert calls, "stale-schema cache was served as a hit"
        # and the findings still materialize from the fresh facts
        assert any(
            k[1] == "run" and program.absint.functions[k]["flow_sites"]
            for k in program.absint.functions
        )

    def test_old_layout_version_is_a_full_miss(self, tmp_path, monkeypatch):
        cache = str(tmp_path / "summaries.json")
        make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        self._mutate_cache(cache, version=CACHE_VERSION - 1)
        calls = []
        real = summaries_mod.extract_effects
        monkeypatch.setattr(
            summaries_mod,
            "extract_effects",
            lambda ctx: (calls.append(ctx.path), real(ctx))[1],
        )
        make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        assert calls

    def test_entry_missing_absint_record_is_a_miss(self, tmp_path, monkeypatch):
        cache = str(tmp_path / "summaries.json")
        make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        data = json.load(open(cache))
        for ent in data["files"].values():
            ent.pop("absint", None)
        json.dump(data, open(cache, "w"))
        calls = []
        real = summaries_mod.extract_effects
        monkeypatch.setattr(
            summaries_mod,
            "extract_effects",
            lambda ctx: (calls.append(ctx.path), real(ctx))[1],
        )
        make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        assert calls

    def test_fresh_schema_cache_hits(self, tmp_path, monkeypatch):
        cache = str(tmp_path / "summaries.json")
        make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)

        def boom(ctx):
            raise AssertionError(f"cache miss: re-extracted {ctx.path}")

        monkeypatch.setattr(summaries_mod, "extract_effects", boom)
        monkeypatch.setattr(summaries_mod, "extract_structure", boom)
        program = make_program(tmp_path, {"lib.py": self.SRC}, cache_path=cache)
        # HT301 findings come out of the CACHED absint facts
        key = next(k for k in program.absint.functions if k[1] == "run")
        assert program.absint.functions[key]["flow_sites"]

    def test_findings_identical_cold_and_warm(self, tmp_path):
        pkg = write_pkg(tmp_path, {"lib.py": self.SRC})
        cache = str(tmp_path / "summaries.json")
        cold = lint_paths([pkg], select=["HT301"], cache_path=cache)
        warm = lint_paths([pkg], select=["HT301"], cache_path=cache)
        assert [f.to_dict() for f in cold] == [f.to_dict() for f in warm]
        assert cold  # the fixture does produce a finding


# ---------------------------------------------------------------------- #
# CLI: wildcard select, list-rules columns
# ---------------------------------------------------------------------- #
class TestCli:
    FIXTURE = """
        import heat_tpu as ht

        def f(comm, x):
            n = comm.rank
            if n == 0:
                comm.Bcast(x)
            a = ht.zeros((8, 4), split=0)
            b = ht.ones((8, 4), split=1)
            return a + b
    """

    def test_select_prefix_wildcard(self, tmp_path):
        pkg = write_pkg(tmp_path, {"lib.py": self.FIXTURE})
        fs = lint_paths([pkg], select=["HT3*"])
        rules = sorted({f.rule for f in fs})
        assert rules == ["HT301", "HT302"]

    def test_select_wildcard_no_match_raises(self, tmp_path):
        pkg = write_pkg(tmp_path, {"lib.py": "x = 1\n"})
        with pytest.raises(ValueError, match="matches no registered rule"):
            lint_paths([pkg], select=["HT9*"])

    def test_cli_select_wildcard(self, tmp_path, capsys):
        pkg = write_pkg(tmp_path, {"lib.py": self.FIXTURE})
        rc = heatlint_cli.main(
            [pkg, "--select", "HT3*", "--baseline", str(tmp_path / "bl.json"),
             "--no-cache"]
        )
        out = capsys.readouterr().out
        assert rc == 1
        assert "HT301" in out and "HT302" in out

    def test_list_rules_shows_severity_and_level(self, capsys):
        rc = heatlint_cli.main(["--list-rules"])
        assert rc == 0
        out = capsys.readouterr().out
        lines = {ln.split()[0]: ln for ln in out.splitlines() if ln}
        # a file-level rule and a program-level rule are distinguishable
        assert "[file   ]" in lines["HT101"] and "[error]" in lines["HT101"]
        assert "[program]" in lines["HT301"] and "[error]" in lines["HT301"]
        assert "[program]" in lines["HT201"]


# ---------------------------------------------------------------------- #
# determinism + the repo gate
# ---------------------------------------------------------------------- #
class TestDeterminism:
    def test_two_runs_identical_findings_order(self, tmp_path):
        files = {
            "a.py": TestCli.FIXTURE,
            "b.py": """
                import heat_tpu as ht

                def g(comm):
                    x = ht.zeros((comm.rank + 1, 4))
                    comm.Allgather(x)

                def h(comm):
                    src = ht.zeros((8, 4), dtype="float64")
                    dst = ht.zeros((8, 4), dtype="float32")
                    comm.Allreduce(src, out=dst, donate=True)
            """,
        }
        pkg = write_pkg(tmp_path, files)
        r1 = [f.to_dict() for f in lint_paths([pkg])]
        r2 = [f.to_dict() for f in lint_paths([pkg])]
        assert r1 == r2
        assert {"HT301", "HT302", "HT303", "HT304"} <= {f["rule"] for f in r1}

    def test_repo_two_runs_identical(self):
        target = [os.path.join(REPO, "heat_tpu", "core")]
        r1 = [f.to_dict() for f in lint_paths(target, select=["HT3*"])]
        r2 = [f.to_dict() for f in lint_paths(target, select=["HT3*"])]
        assert r1 == r2
