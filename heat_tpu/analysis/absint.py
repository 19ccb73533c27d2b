"""Abstract interpretation for heatlint's HT3xx rules: rank-taint + array metadata.

The HT1xx/HT2xx families reason about *structure* — which collectives are
staged, in what order, behind which branches.  Nothing reasons about
*values*: a rank-dependent integer flowing into a shape, a loop bound, or a
collective payload is invisible until the flight recorder convicts a rank
at runtime.  This module closes that gap with two abstract domains, both
interpreted intraprocedurally per function and linked program-wide through
the PR 8 call graph:

- a **rank-taint lattice** over symbolic source tokens.  Concrete verdicts
  form the three-point lattice ``untainted ⊑ unknown ⊑ rank``: ``rank``
  means *provably derived from process identity* (seeded at ``comm.rank`` /
  ``self.rank`` reads, ``process_index()``/``axis_index()``/
  ``local_devices()`` calls, and parameters named like ranks — the same
  vocabulary HT102/HT201 match lexically), ``unknown`` means *no rank
  evidence, but origin unanalyzable* (a poisoning unresolved call), and
  only ``rank`` ever fires a finding — the honesty policy, value edition.
  During extraction taint is a *set of symbolic tokens* (``rank``,
  ``param:i``, ``call:cid``, ``unknown``); the program-level resolver
  substitutes call tokens through callee return-taint summaries and caller
  argument bindings, so taint crosses function boundaries
  (``n = _myrank(comm)`` is as tainted as ``n = comm.rank``).  Rank
  branches add their test taint to every name whose binding differs across
  the arms (implicit flow): ``n = 1 if comm.rank == 0 else 2`` taints
  ``n``.  Loop bodies run to an env fixpoint (joins are monotone over a
  finite token universe); metadata still unstable at the iteration cap is
  widened to TOP — convergence is structural, not hoped for.

- an **array-metadata domain** tracking symbolic ``(gshape, split, dtype)``
  for DNDarray-typed locals: factory calls (``ht.zeros((4, n), split=0)``)
  seed metadata, ``resplit``/``resplit_`` rewrite the split, binary ops
  propagate it through the dispatch tail's promotion rule (matching
  ``_operations.__binary_op``: one side replicated adopts the other's
  split; two *different* concrete splits is the HT302 hazard), and simple
  wrapper returns chain through call-site resolution.  Dims are ``int`` or
  ``"?"``; split is ``int``/``None`` (replicated)/``"?"``; shape and dtype
  carry their own taint sets so HT303 can prove a *payload* whose staged
  fingerprint depends on process identity.

Extraction (:func:`extract_absint`) is file-local and serializable — it
rides in the ``.heatlint-summaries.json`` cache next to the structure and
effect facts, which is why the cache carries an analysis-schema revision:
a summaries file written before these atoms existed must be a miss, not a
silently fact-free hit.  Linking (:class:`AbsintView`) re-resolves the
recorded call descriptors against the program call graph (``record=False``
— the effect pass already audited every site into the honesty bucket) and
computes the return-taint / param-sink / metadata resolutions the HT301–
HT304 rules consume.

Stdlib-only and standalone-loadable, like the rest of ``analysis/``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Tuple

from .callgraph import CallDesc, FuncKey, call_desc, call_name, last_attr

# ------------------------------------------------------------------ #
# vocabulary
# ------------------------------------------------------------------ #

# seeds beyond summaries.RANK_CALLS: per-process device topology reads are
# rank-derived exactly like process_index()
RANK_EXTRA_CALLS = ("local_devices", "local_device_count")

# factory entry points that mint a DNDarray with (shape, split, dtype)
FACTORY_NAMES = frozenset(
    {
        "zeros", "ones", "empty", "full", "arange", "linspace", "eye",
        "rand", "randn", "randint",
    }
)
# *_like factories inherit metadata from their prototype argument
FACTORY_LIKE_NAMES = frozenset({"zeros_like", "ones_like", "empty_like", "full_like"})

RESPLIT_NAMES = frozenset({"resplit", "resplit_", "redistribute_"})

# raw lax collectives operate on TRACED per-shard arrays inside jit/
# shard_map: per-rank operand values are their semantics (a masked psum is
# the Bcast idiom), and the staged program is identical on every rank — so
# the collective-ARGUMENT taint check never applies to them (control-flow
# enclosing them still does)
RAW_LAX_COLLECTIVES = frozenset(
    {"psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
     "ppermute", "psum_scatter", "pbroadcast"}
)

# collective-by-contract MATERIALIZERS: every rank attends, but the argument
# is the data payload being fetched — not a control argument (root/count)
# the ranks must agree on.  HT301's collective-ARGUMENT check skips them;
# payload METADATA divergence stays HT303's conviction.
_MATERIALIZER_COLLECTIVES = frozenset(
    {"host_fetch", "host_fetch_all", "numpy", "process_allgather"}
)

# dispatch-tail binary entry points (the operator forms are ast.BinOp)
BINOP_CALL_NAMES = frozenset(
    {"add", "subtract", "multiply", "divide", "true_divide", "power",
     "remainder", "matmul", "dot"}
)

_TOK_RANK = "rank"
_TOK_UNKNOWN = "unknown"


def _tok_param(i: int) -> str:
    return f"param:{i}"


def _tok_call(cid: int) -> str:
    return f"call:{cid}"


def _rank_vocab():
    # lazy: summaries imports this module inside build_program, so a
    # top-level import here would be circular
    from .summaries import COLLECTIVES, RANK_ATTRS, RANK_CALLS, RANK_NAMES

    return COLLECTIVES, RANK_ATTRS, tuple(RANK_CALLS) + RANK_EXTRA_CALLS, RANK_NAMES


# ------------------------------------------------------------------ #
# the array-metadata domain (JSON-serializable dicts)
# ------------------------------------------------------------------ #
#
# meta := None (TOP — not an array / nothing known)
#       | {"dims": [int|"?"...] | None, "split": int|None|"?", "dtype": str|"?",
#          "shape_taint": [tok...], "dtype_taint": [tok...]}
#         — dims None means the RANK itself is unknown (``zeros(shp)`` with a
#         variable shape could be any ndim), which is distinct from a known
#         rank with unknown extents (["?", "?"]); alignment arithmetic is
#         only valid on known-rank dims
#       | {"call": cid}                       (symbolic: callee's return meta)
#       | {"call": cid, "resplit": int|None|"?"}  (…re-split at this site)


def _meta(dims, split, dtype, shape_taint=(), dtype_taint=()):
    return {
        "dims": None if dims is None else list(dims),
        "split": split,
        "dtype": dtype,
        "shape_taint": sorted(set(shape_taint)),
        "dtype_taint": sorted(set(dtype_taint)),
    }


# lexical dtype identifiers that alias a canonical heat type (types.py's
# alias surface): HT304 must not call float-vs-float32 a mismatch
_DTYPE_ALIASES = {
    "float": "float32", "float_": "float32", "single": "float32",
    "double": "float64", "half": "float16",
    "int": "int32", "int_": "int32", "long": "int64",
    "bool": "bool_",
}
# identifiers that ARE dtypes — anything else (``x.dtype``, a module
# constant) is an unknown dtype, never a fabricated concrete one
_DTYPE_VOCAB = frozenset(_DTYPE_ALIASES) | frozenset(
    {
        "float16", "float32", "float64", "bfloat16",
        "int8", "int16", "int32", "int64",
        "uint8", "uint16", "uint32", "uint64",
        "bool_", "complex64", "complex128",
    }
)


def canonical_dtype_name(name):
    if isinstance(name, str):
        return _DTYPE_ALIASES.get(name, name)
    return name


def meta_join(a, b):
    """Least upper bound: agreement survives, disagreement widens the
    field (dims elementwise to ``"?"``, split/dtype to ``"?"``); symbolic
    metas join only with themselves."""
    if a is None or b is None:
        return None
    if "call" in a or "call" in b:
        return a if a == b else None
    da, db = a["dims"], b["dims"]
    if da is None or db is None or len(da) != len(db):
        dims = None
    else:
        dims = [x if x == y else "?" for x, y in zip(da, db)]
    return _meta(
        dims,
        a["split"] if a["split"] == b["split"] else "?",
        a["dtype"] if a["dtype"] == b["dtype"] else "?",
        set(a["shape_taint"]) | set(b["shape_taint"]),
        set(a["dtype_taint"]) | set(b["dtype_taint"]),
    )


def _with_split(meta, split):
    if meta is None:
        return None
    if "call" in meta:
        return {"call": meta["call"], "resplit": split}
    return _meta(meta["dims"], split, meta["dtype"], meta["shape_taint"], meta["dtype_taint"])


def promote_split(s1, s2):
    """The dispatch tail's split-promotion rule (``__binary_op``): one side
    replicated adopts the other's split; equal splits keep it; two
    different concrete splits trigger an implicit resplit — the HT302 rule
    checks for that case before asking for the result."""
    if s1 == "?" or s2 == "?":
        return "?"
    if s1 is None:
        return s2
    if s2 is None:
        return s1
    return s1 if s1 == s2 else "?"


def binop_meta(a, b):
    """Result metadata of an elementwise binary op on two concrete metas."""
    if a is None or b is None or "call" in a or "call" in b:
        return None
    da, db = a["dims"], b["dims"]
    if da is None or db is None or len(da) != len(db):
        dims = None
    else:
        dims = [x if x == y else "?" for x, y in zip(da, db)]
    return _meta(
        dims,
        promote_split(a["split"], b["split"]),
        a["dtype"] if a["dtype"] == b["dtype"] else "?",
        set(a["shape_taint"]) | set(b["shape_taint"]),
        set(a["dtype_taint"]) | set(b["dtype_taint"]),
    )


# ------------------------------------------------------------------ #
# intraprocedural interpreter (one pass per function, cacheable output)
# ------------------------------------------------------------------ #

_LOOP_FIXPOINT_CAP = 6  # taint joins are monotone over a finite universe,
# so the loop-head env chain stabilizes; the cap is the widening backstop
# for metadata (a meta still changing at the cap widens to TOP)


class _Interp:
    """Abstract interpreter over one function body.

    Produces the serializable per-function fact record: the call list with
    per-argument taint/metadata, collective sites, rank-taintable control-
    flow sites, binary-op sites and return taint/metadata.  All records
    are keyed by source position, so the loop-fixpoint re-walks update them
    in place instead of duplicating — the final pass (fixpoint env) wins,
    and call ids stay stable across passes.
    Everything downstream (verdicts, findings) happens at link time against
    the program call graph.
    """

    def __init__(self, ctx, fn):
        self.ctx = ctx
        self.fn = fn
        self.qual = ctx.qualname(fn)
        (
            self.COLLECTIVES,
            self.RANK_ATTRS,
            self.RANK_CALLS,
            self.RANK_NAMES,
        ) = _rank_vocab()
        self.calls: List[dict] = []
        self._call_ids: Dict[Tuple[int, int], int] = {}  # (line, col) -> cid
        self.coll_sites: Dict[int, dict] = {}  # cid -> site
        self.flow_sites: Dict[Tuple[str, int], dict] = {}
        self.binop_sites: Dict[Tuple[int, int, str], dict] = {}
        self.ret_taint: set = set()
        self.ret_metas: Dict[Tuple[int, int], object] = {}
        # per-element return taint when EVERY return is a same-arity tuple
        # literal ("unset" until the first return; None once invalidated) —
        # lets tuple unpacking at call sites bind element-precise taint
        # instead of smearing one tainted element over every target
        self.ret_tuple: object = "unset"
        # stack of region collectors (branch arms / loop bodies):
        # colls keyed (line, name) so fixpoint re-walks don't duplicate
        self._regions: List[dict] = []
        a = fn.args
        names = [p.arg for p in list(a.posonlyargs) + list(a.args)]
        parent = ctx.parent(fn)
        if isinstance(parent, ast.ClassDef) and names and names[0] in ("self", "cls"):
            names = names[1:]
        self.params = names

    # ---------------- entry ---------------- #

    def run(self) -> dict:
        env: Dict[str, Tuple[frozenset, object]] = {}
        for i, name in enumerate(self.params):
            taint = {_tok_param(i)}
            if name in self.RANK_NAMES:
                taint.add(_TOK_RANK)
            env[name] = (frozenset(taint), None)
        self._stmts(self.fn.body, env)
        return {
            "params": list(self.params),
            "calls": self.calls,
            "coll_sites": [self.coll_sites[k] for k in sorted(self.coll_sites)],
            "flow_sites": [self.flow_sites[k] for k in sorted(self.flow_sites)],
            "binop_sites": [self.binop_sites[k] for k in sorted(self.binop_sites)],
            "ret_taint": sorted(self.ret_taint),
            "ret_tuple": (
                [sorted(elt) for elt in self.ret_tuple]
                if isinstance(self.ret_tuple, list)
                else None
            ),
            "ret_metas": [self.ret_metas[k] for k in sorted(self.ret_metas)],
        }

    # ---------------- statements ---------------- #

    def _stmts(self, stmts: Sequence[ast.stmt], env) -> None:
        for stmt in stmts:
            self._stmt(stmt, env)

    def _bind_elementwise(self, env, target: ast.expr, value: ast.expr) -> bool:
        """Element-precise binding for ``a, b = <tuple or call>``: a tuple
        literal binds element taints directly; a call binds symbolic
        ``callelt:cid:i`` tokens resolved against the callee's per-element
        return taint.  Returns False when the shape doesn't allow it (the
        caller falls back to whole-value binding)."""
        if not isinstance(target, (ast.Tuple, ast.List)):
            return False
        if any(isinstance(e, ast.Starred) for e in target.elts):
            return False
        if isinstance(value, (ast.Tuple, ast.List)) and len(value.elts) == len(
            target.elts
        ):
            for tgt_e, val_e in zip(target.elts, value.elts):
                t, m = self._eval(val_e, env)
                self._bind_target(env, tgt_e, t, m)
            return True
        if isinstance(value, ast.Call):
            pos = (
                value.lineno,
                value.col_offset,
                value.end_lineno or 0,
                value.end_col_offset or 0,
            )
            cid = self._call_ids.get(pos)
            if cid is not None:
                for i, tgt_e in enumerate(target.elts):
                    self._bind_target(
                        env, tgt_e, frozenset({f"callelt:{cid}:{i}"}), None
                    )
                return True
        return False

    def _bind_target(self, env, target: ast.expr, taint: frozenset, meta) -> None:
        if isinstance(target, ast.Name):
            env[target.id] = (taint, meta)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind_target(env, elt, taint, None)
        elif isinstance(target, ast.Starred):
            self._bind_target(env, target.value, taint, None)
        # attribute/subscript stores don't bind locals (HT106's business)

    def _stmt(self, stmt: ast.stmt, env) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return  # their own entities
        if isinstance(stmt, ast.Assign):
            taint, meta = self._eval(stmt.value, env)
            for tgt in stmt.targets:
                if not self._bind_elementwise(env, tgt, stmt.value):
                    self._bind_target(env, tgt, taint, meta)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                taint, meta = self._eval(stmt.value, env)
                if isinstance(stmt.target, ast.Name):
                    env[stmt.target.id] = (taint, meta)
            return
        if isinstance(stmt, ast.AugAssign):
            taint, _m = self._eval(stmt.value, env)
            if isinstance(stmt.target, ast.Name):
                old_t, _old_m = env.get(stmt.target.id, (frozenset({_TOK_UNKNOWN}), None))
                env[stmt.target.id] = (old_t | taint, None)
            return
        if isinstance(stmt, ast.If):
            self._branch(stmt, env)
            return
        if isinstance(stmt, ast.While):
            self._loop(stmt, env, test=stmt.test, bound_taint=None)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            it_taint, _it_meta = self._eval(stmt.iter, env)
            bound_taint = it_taint
            # range(n): the bound IS the argument, not the range object —
            # but taint-wise they coincide (range() is external: arg union)
            self._bind_target(env, stmt.target, bound_taint, None)
            self._loop(stmt, env, test=None, bound_taint=bound_taint)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                taint, meta = self._eval(item.context_expr, env)
                if item.optional_vars is not None:
                    self._bind_target(env, item.optional_vars, taint, meta)
            self._stmts(stmt.body, env)
            return
        if isinstance(stmt, ast.Try):
            self._stmts(stmt.body, env)
            for h in stmt.handlers:
                henv = dict(env)
                self._stmts(h.body, henv)
                self._merge_env(env, henv)
            self._stmts(stmt.orelse, env)
            self._stmts(stmt.finalbody, env)
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                taint, meta = self._eval(stmt.value, env)
                self.ret_taint |= taint
                self.ret_metas[(stmt.lineno, stmt.col_offset)] = meta
                if isinstance(stmt.value, ast.Tuple):
                    elems = [set(self._eval(e, env)[0]) for e in stmt.value.elts]
                    if self.ret_tuple == "unset":
                        self.ret_tuple = elems
                    elif isinstance(self.ret_tuple, list) and len(
                        self.ret_tuple
                    ) == len(elems):
                        for cur, new in zip(self.ret_tuple, elems):
                            cur |= new
                    else:
                        self.ret_tuple = None  # mixed arity
                else:
                    self.ret_tuple = None  # a non-tuple return path
            return
        # anything else: evaluate child expressions for their records
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._eval(child, env)
            elif isinstance(child, ast.stmt):
                self._stmt(child, env)

    # ---------------- branches and loops ---------------- #

    def _region_push(self) -> dict:
        frame = {"colls": {}, "cids": set()}
        self._regions.append(frame)
        return frame

    def _region_pop(self) -> dict:
        return self._regions.pop()

    @staticmethod
    def _region_json(frame: dict) -> dict:
        return {
            "colls": [frame["colls"][k] for k in sorted(frame["colls"])],
            "cids": sorted(frame["cids"]),
        }

    def _merge_env(self, base, other) -> None:
        """Join ``other`` into ``base`` in place; a name bound on only one
        path joins with the unknown binding.  (Branch joins do NOT go
        through here — ``_branch`` needs the pre-branch env to decide
        which names carry the test's implicit-flow taint.)"""
        for name in set(base) | set(other):
            bt, bm = base.get(name, (frozenset({_TOK_UNKNOWN}), None))
            ot, om = other.get(name, (frozenset({_TOK_UNKNOWN}), None))
            if (bt, bm) == (ot, om):
                continue
            base[name] = (bt | ot, meta_join(bm, om))

    def _branch(self, stmt: ast.If, env) -> None:
        from .summaries import rank_marker

        test_taint, _tm = self._eval(stmt.test, env)
        lexical = rank_marker(stmt.test) is not None
        base = dict(env)
        env_a, env_b = dict(env), dict(env)
        frame_a = self._region_push()
        self._stmts(stmt.body, env_a)
        self._region_pop()
        frame_b = self._region_push()
        self._stmts(stmt.orelse, env_b)
        self._region_pop()
        interesting = frame_a["colls"] or frame_b["colls"] or frame_a["cids"] or frame_b["cids"]
        if test_taint and not lexical and interesting:
            self.flow_sites[("if", stmt.lineno)] = {
                "kind": "if",
                "line": stmt.lineno,
                "taint": sorted(test_taint),
                "arm_a": self._region_json(frame_a),
                "arm_b": self._region_json(frame_b),
            }
        # join + implicit flow: a name ASSIGNED under the branch (its
        # binding in either arm differs from the pre-branch one) carries
        # the test taint even when both arms' ABSTRACTIONS coincide —
        # the abstraction cannot distinguish `n = 1` from `n = 2`, but
        # the concrete value still depends on the test
        env.clear()
        for name in set(env_a) | set(env_b):
            at = env_a.get(name, (frozenset({_TOK_UNKNOWN}), None))
            bt = env_b.get(name, (frozenset({_TOK_UNKNOWN}), None))
            joined_t = at[0] | bt[0]
            joined_m = at[1] if at == bt else meta_join(at[1], bt[1])
            if test_taint and (
                env_a.get(name) != base.get(name)
                or env_b.get(name) != base.get(name)
            ):
                joined_t = joined_t | test_taint
            env[name] = (joined_t, joined_m)

    def _loop(self, stmt, env, test: Optional[ast.expr], bound_taint) -> None:
        from .summaries import rank_marker

        if test is not None:
            test_taint, _tm = self._eval(test, env)
            lexical = rank_marker(test) is not None
            kind = "while"
        else:
            test_taint = bound_taint or frozenset()
            lexical = False
            kind = "for"
        frame = self._region_push()
        body = list(stmt.body) + list(getattr(stmt, "orelse", []) or [])
        # env fixpoint at the loop head: cur = join(env, transfer(cur)).
        # Taint joins are monotone over a finite token universe and each
        # pass propagates taint at least one assignment hop, so the chain
        # stabilizes within (#distinct stored names + 2) iterations — size
        # the cap to THAT, not a constant, or a long loop-carried rename
        # chain (b = a; c = b; …) silently under-propagates rank taint
        stored = {
            n.id
            for s in body
            for n in ast.walk(s)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)
        }
        cap = max(_LOOP_FIXPOINT_CAP, len(stored) + 2)
        base = dict(env)
        cur = dict(env)
        for _ in range(cap):
            body_env = dict(cur)
            self._stmts(body, body_env)
            new = dict(cur)
            self._merge_env(new, body_env)
            if new == cur:
                break
            cur = new
        else:
            for name, (t, m) in list(cur.items()):
                if m is not None:
                    cur[name] = (t, None)  # widening backstop
        if test_taint:
            # implicit flow: how many iterations ran depends on the test,
            # so every name the body assigns carries its taint
            for name, binding in list(cur.items()):
                if binding != base.get(name):
                    cur[name] = (binding[0] | test_taint, binding[1])
        env.clear()
        env.update(cur)
        self._region_pop()
        if test_taint and not lexical and (frame["colls"] or frame["cids"]):
            self.flow_sites[(kind, stmt.lineno)] = {
                "kind": kind,
                "line": stmt.lineno,
                "taint": sorted(test_taint),
                "arm_a": self._region_json(frame),
                "arm_b": {"colls": [], "cids": []},
            }

    # ---------------- expressions ---------------- #

    def _eval(self, node: ast.expr, env) -> Tuple[frozenset, object]:
        if isinstance(node, ast.Constant):
            return frozenset(), None
        if isinstance(node, ast.Name):
            if node.id in env:
                return env[node.id]
            if node.id in self.RANK_NAMES:
                return frozenset({_TOK_RANK}), None
            return frozenset(), None  # module global / builtin: no evidence
        if isinstance(node, ast.Attribute):
            base_t, _bm = self._eval(node.value, env)
            if node.attr in self.RANK_ATTRS:
                return base_t | {_TOK_RANK}, None
            if node.attr == "split" and isinstance(getattr(node, "ctx", None), ast.Load):
                return frozenset(), None  # metadata is rank-uniform
            return base_t, None
        if isinstance(node, ast.Call):
            return self._call(node, env)
        if isinstance(node, ast.BinOp):
            lt, lm = self._eval(node.left, env)
            rt, rm = self._eval(node.right, env)
            if (
                lm is not None
                and rm is not None
                and not isinstance(node.op, ast.MatMult)
            ):
                self.binop_sites[(node.lineno, node.col_offset, type(node.op).__name__)] = {
                    "line": node.lineno,
                    "op": type(node.op).__name__,
                    "left": lm,
                    "right": rm,
                }
            out_meta = None if isinstance(node.op, ast.MatMult) else binop_meta(
                lm if isinstance(lm, dict) and "call" not in lm else None,
                rm if isinstance(rm, dict) and "call" not in rm else None,
            )
            if isinstance(node.op, ast.MatMult) and lm is not None and rm is not None:
                self.binop_sites[(node.lineno, node.col_offset, "MatMult")] = {
                    "line": node.lineno,
                    "op": "MatMult",
                    "left": lm,
                    "right": rm,
                }
            return lt | rt, out_meta
        if isinstance(node, ast.BoolOp):
            t = frozenset()
            for v in node.values:
                vt, _vm = self._eval(v, env)
                t |= vt
            return t, None
        if isinstance(node, ast.UnaryOp):
            return self._eval(node.operand, env)
        if isinstance(node, ast.Compare):
            t, _m = self._eval(node.left, env)
            for comp in node.comparators:
                ct, _cm = self._eval(comp, env)
                t |= ct
            return t, None
        if isinstance(node, ast.IfExp):
            tt, _tm = self._eval(node.test, env)
            at, am = self._eval(node.body, env)
            bt, bm = self._eval(node.orelse, env)
            return tt | at | bt, meta_join(am, bm)  # implicit flow
        if isinstance(node, ast.Subscript):
            vt, _vm = self._eval(node.value, env)
            st, _sm = self._eval(node.slice, env)
            return vt | st, None
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            t = frozenset()
            for elt in node.elts:
                et, _em = self._eval(elt, env)
                t |= et
            return t, None
        if isinstance(node, ast.Dict):
            t = frozenset()
            for k, v in zip(node.keys, node.values):
                if k is not None:
                    kt, _km = self._eval(k, env)
                    t |= kt
                vt, _vm = self._eval(v, env)
                t |= vt
            return t, None
        if isinstance(node, ast.Starred):
            return self._eval(node.value, env)
        if isinstance(node, (ast.Lambda, ast.GeneratorExp, ast.ListComp,
                             ast.SetComp, ast.DictComp)):
            return frozenset(), None  # deferred bodies: their own scope
        # fallback (f-strings, slices, await, …): union of child taints
        t = frozenset()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                ct, _cm = self._eval(child, env)
                t |= ct
        return t, None

    # ---------------- calls ---------------- #

    def _literal_split(self, node: Optional[ast.expr]) -> object:
        if isinstance(node, ast.Constant) and (
            node.value is None or isinstance(node.value, int)
        ):
            return node.value
        return "?"

    def _literal_dims(self, node: ast.expr, env) -> Tuple[object, set]:
        """(dims, shape_taint) for a factory's shape argument.  A variable
        shape expression could be ANY rank (an int or an arbitrary tuple),
        so the fallback is the unknown-ndim sentinel ``None``, never a
        fabricated 1-D shape — alignment arithmetic on a guessed rank
        manufactures false mismatches."""
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return [node.value], set()
        if isinstance(node, (ast.Tuple, ast.List)):
            dims, taint = [], set()
            for elt in node.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                    dims.append(elt.value)
                else:
                    t, _m = self._eval(elt, env)
                    dims.append("?")
                    taint |= t
            return dims, taint
        t, _m = self._eval(node, env)
        return None, set(t)

    def _dtype_of(self, node: ast.expr, env) -> Tuple[object, set]:
        # canonicalized at extraction so `float` and `float32` (aliases in
        # types.py) never read as different dtypes downstream; identifiers
        # OUTSIDE the dtype vocabulary (``x.dtype`` forwarding, a module
        # constant) are unknown — fabricating a concrete dtype from an
        # arbitrary name manufactures "provable" mismatches
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in _DTYPE_VOCAB:
                return canonical_dtype_name(node.value), set()
            return "?", set()
        if isinstance(node, ast.Attribute) and node.attr in _DTYPE_VOCAB:
            return canonical_dtype_name(node.attr), set()
        if isinstance(node, ast.Name) and node.id not in env:
            if node.id in _DTYPE_VOCAB:
                return canonical_dtype_name(node.id), set()
            return "?", set()
        if isinstance(node, ast.Attribute):
            return "?", set()  # dtype forwarding: metadata is rank-uniform
        t, _m = self._eval(node, env)
        return "?", set(t)

    def _dims_star_d(self, args, env) -> Tuple[object, set]:
        """Shape from *d-style positionals (``randn(4, 5)``; a single
        tuple/list argument is the whole shape; starred args are an
        unknown rank)."""
        if not args:
            return [1], set()  # rand()/randn() default to shape (1,)
        if len(args) == 1 and isinstance(args[0], (ast.Tuple, ast.List)):
            return self._literal_dims(args[0], env)
        if any(isinstance(a, ast.Starred) for a in args):
            taint = set()
            for a in args:
                t, _m = self._eval(a, env)
                taint |= t
            return None, taint
        dims, taint = [], set()
        for a in args:
            if isinstance(a, ast.Constant) and isinstance(a.value, int):
                dims.append(a.value)
            else:
                t, _m = self._eval(a, env)
                dims.append("?")
                taint |= t
        return dims, taint

    def _factory_meta(self, node: ast.Call, env):
        # each factory family has its own argument convention — reading
        # args[0] as "the shape" everywhere mints provably wrong dims
        # (randint's first arg is `low`) that feed HT302/HT304 false errors
        dims, shape_taint = None, set()
        la = last_attr(node)
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        if la in ("rand", "randn"):
            dims, shape_taint = self._dims_star_d(node.args, env)
        elif la == "randint":
            size = kwargs.get("size")
            if size is None and len(node.args) >= 3:
                size = node.args[2]
            if size is not None:
                dims, shape_taint = self._literal_dims(size, env)
        elif la == "arange":
            # every bound (start/stop/step) shapes the result
            taint = set()
            for arg in node.args:
                t, _m = self._eval(arg, env)
                taint |= t
            n_const = None
            if len(node.args) == 1 and isinstance(
                node.args[0], ast.Constant
            ) and isinstance(node.args[0].value, int):
                n_const = node.args[0].value
            dims = [n_const if n_const is not None else "?"]
            shape_taint = taint
        elif la == "linspace":
            # ONLY num (3rd positional / num=) shapes the result —
            # start/stop set values, and uniting their taint into the
            # shape manufactures false payload-asymmetry findings
            num = kwargs.get("num")
            if num is None and len(node.args) >= 3:
                num = node.args[2]
            if num is None:
                dims = [50]  # the numpy/heat default
            elif isinstance(num, ast.Constant) and isinstance(num.value, int):
                dims = [num.value]
            else:
                t, _m = self._eval(num, env)
                dims = ["?"]
                shape_taint = set(t)
        elif la == "eye":
            cols = node.args[1] if len(node.args) >= 2 else (
                node.args[0] if node.args else None
            )
            dims = ["?", "?"]
            for i, arg in enumerate((node.args[0] if node.args else None, cols)):
                if isinstance(arg, ast.Constant) and isinstance(arg.value, int):
                    dims[i] = arg.value
                elif arg is not None:
                    t, _m = self._eval(arg, env)
                    shape_taint |= t
            if not node.args:
                dims = None
        elif node.args:
            dims, shape_taint = self._literal_dims(node.args[0], env)
        split: object = None  # the factories' documented default
        dtype: object = "?"
        dtype_taint: set = set()
        for kw in node.keywords:
            if kw.arg == "split":
                split = self._literal_split(kw.value)
                if split == "?":
                    t, _m = self._eval(kw.value, env)
                    shape_taint |= t
            elif kw.arg == "dtype":
                dtype, dtype_taint = self._dtype_of(kw.value, env)
        return _meta(dims, split, dtype, shape_taint, dtype_taint)

    def _record_call(self, node: ast.Call, env) -> Tuple[int, dict]:
        arg_taints, arg_metas = [], []
        for arg in node.args:
            t, m = self._eval(arg, env)
            arg_taints.append(sorted(t))
            arg_metas.append(m)
        kw_taints, kw_metas = {}, {}
        for kw in node.keywords:
            t, m = self._eval(kw.value, env)
            key = kw.arg or "**"
            kw_taints[key] = sorted(t)
            kw_metas[key] = m
        # keyed by START + END position: `f(x)(y)` puts the inner call and
        # the outer call at the SAME (line, col) — only the end offsets
        # tell them apart, and a collision would overwrite the inner
        # call's record (losing its argument taint)
        pos = (
            node.lineno,
            node.col_offset,
            node.end_lineno or 0,
            node.end_col_offset or 0,
        )
        rec = {
            "desc": call_desc(node).to_json(),
            "line": node.lineno,
            "arg_taints": arg_taints,
            "arg_metas": arg_metas,
            "kw_taints": kw_taints,
            "kw_metas": kw_metas,
        }
        cid = self._call_ids.get(pos)
        if cid is None:
            cid = len(self.calls)
            self._call_ids[pos] = cid
            self.calls.append(rec)
        else:
            self.calls[cid] = rec  # fixpoint re-walk: latest taints win
        for frame in self._regions:
            frame["cids"].add(cid)
        return cid, rec

    def _call(self, node: ast.Call, env) -> Tuple[frozenset, object]:
        # callee receiver expression first (chained receivers stage first)
        if isinstance(node.func, ast.Call):
            self._eval(node.func, env)
        recv_meta = None
        if isinstance(node.func, ast.Attribute):
            _rt, recv_meta = self._eval(node.func.value, env)

        la = last_attr(node)

        # resplit family: metadata transform on the receiver/first arg.
        # Two call shapes share the names: the METHOD form `x.resplit(axis)`
        # (receiver is the array) and the FREE form `ht.resplit(x, axis)` /
        # `comm.resplit(x, axis)` / bare `resplit(x, axis)` (args[0] is the
        # array).  An attribute call is the free form when it has >= 2
        # positionals (the method form takes only the axis) or when its
        # receiver is an unbound name (a module alias like `ht`, which has
        # no array metadata to transform).
        if la in RESPLIT_NAMES:
            method_form = isinstance(node.func, ast.Attribute)
            if method_form:
                recv = node.func.value
                if len(node.args) >= 2:
                    method_form = False
                elif (
                    isinstance(recv, ast.Name)
                    and recv.id not in env
                    and recv.id not in ("self", "cls")
                ):
                    method_form = False
            if method_form:
                target_meta = recv_meta
                recv_name = (
                    node.func.value.id if isinstance(node.func.value, ast.Name) else None
                )
                split_arg = node.args[0] if node.args else None
            else:
                recv_name = (
                    node.args[0].id
                    if node.args and isinstance(node.args[0], ast.Name)
                    else None
                )
                target_meta = self._eval(node.args[0], env)[1] if node.args else None
                split_arg = node.args[1] if len(node.args) > 1 else None
            for kw in node.keywords:
                if kw.arg in ("axis", "split"):
                    split_arg = kw.value
            new_split = self._literal_split(split_arg) if split_arg is not None else "?"
            cid, _rec = self._record_call(node, env)
            for frame in self._regions:
                frame["colls"][(node.lineno, la)] = la
            out_meta = _with_split(target_meta, new_split)
            if la == "resplit_" and recv_name is not None and recv_name in env:
                old_t, _om = env[recv_name]
                env[recv_name] = (old_t, out_meta)
            return frozenset({_tok_call(cid)}), out_meta

        # factories mint metadata
        if la in FACTORY_NAMES and self._looks_like_factory(node):
            meta = self._factory_meta(node, env)
            cid, _rec = self._record_call(node, env)
            return frozenset({_tok_call(cid)}), meta
        if la in FACTORY_LIKE_NAMES and node.args and self._looks_like_factory(node):
            # same root guard as the plain factories: np.zeros_like(a)
            # returns a HOST array — inheriting the DNDarray prototype's
            # split would mint provably wrong metadata
            proto_meta = self._eval(node.args[0], env)[1]
            cid, _rec = self._record_call(node, env)
            if isinstance(proto_meta, dict) and "call" in proto_meta:
                proto_meta = None
            return frozenset({_tok_call(cid)}), proto_meta

        # rank seeds
        if la in self.RANK_CALLS:
            self._record_call(node, env)
            return frozenset({_TOK_RANK}), None

        cid, rec = self._record_call(node, env)

        # collective sites (payload + control vocabulary for HT301/HT303)
        if la in self.COLLECTIVES:
            for frame in self._regions:
                frame["colls"][(node.lineno, la)] = la
            self.coll_sites[cid] = {
                "name": la,
                "line": node.lineno,
                "cid": cid,
                "arg_taints": rec["arg_taints"],
                "arg_metas": rec["arg_metas"],
                "kw_taints": rec["kw_taints"],
                "kw_metas": rec["kw_metas"],
            }

        # dispatch-tail binary entry points: ht.add(a, b) etc.
        if la in BINOP_CALL_NAMES and len(rec["arg_metas"]) >= 2:
            lm, rm = rec["arg_metas"][0], rec["arg_metas"][1]
            if lm is not None and rm is not None:
                self.binop_sites[(node.lineno, node.col_offset, la)] = {
                    "line": node.lineno,
                    "op": la,
                    "left": lm,
                    "right": rm,
                }

        return frozenset({_tok_call(cid)}), {"call": cid}

    def _looks_like_factory(self, node: ast.Call) -> bool:
        """``ht.zeros`` / ``factories.ones`` / bare ``zeros`` count;
        numpy/jnp roots are host or raw-device arrays, not DNDarrays."""
        dn = call_name(node)
        if dn is None:
            return False
        return dn.split(".")[0] not in ("np", "numpy", "jnp", "jax", "math", "torch")


# ------------------------------------------------------------------ #
# extraction entry point (cached per file next to facts/effects)
# ------------------------------------------------------------------ #


def extract_absint(ctx) -> dict:
    """Serializable abstract-interpretation facts for every def in ``ctx``."""
    functions: Dict[str, dict] = {}
    for node in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
        functions[ctx.qualname(node)] = _Interp(ctx, node).run()
    return {"functions": functions}


# ------------------------------------------------------------------ #
# program-level linking: taint resolution + sink fixpoints
# ------------------------------------------------------------------ #

_RESOLVE_DEPTH_CAP = 10
_CHAIN_CAP = 12


def _cmeta_join(a, b):
    """Join in the frame-free concrete metadata domain."""
    if a is None or b is None:
        return None
    da, db = a["dims"], b["dims"]
    if da is None or db is None or len(da) != len(db):
        dims = None
    else:
        dims = [x if x == y else "?" for x, y in zip(da, db)]
    return {
        "dims": dims,
        "split": a["split"] if a["split"] == b["split"] else "?",
        "dtype": a["dtype"] if a["dtype"] == b["dtype"] else "?",
        "shape_rank": a["shape_rank"] or b["shape_rank"],
        "dtype_rank": a["dtype_rank"] or b["dtype_rank"],
    }


class Verdict:
    """Resolved taint: the three-point concrete lattice plus residual
    parameter dependence (for summary composition)."""

    __slots__ = ("rank", "unknown", "params")

    def __init__(self):
        self.rank = False
        self.unknown = False
        self.params: set = set()

    def merge(self, other: "Verdict") -> None:
        self.rank |= other.rank
        self.unknown |= other.unknown
        self.params |= other.params


class AbsintView:
    """Everything the HT3xx rules consume, resolved against the program."""

    def __init__(self, program, facts_by_path: Dict[str, dict]):
        self.program = program
        self.functions: Dict[FuncKey, dict] = {}
        for path in sorted(facts_by_path):
            for qual, rec in facts_by_path[path].get("functions", {}).items():
                self.functions[(path, qual)] = rec
        # resolve the absint call lists (record=False: the effect pass
        # already audited these sites into the honesty bucket)
        self.resolved: Dict[FuncKey, list] = {}
        for key in sorted(self.functions):
            rec = self.functions[key]
            self.resolved[key] = [
                program.graph.resolve(key, CallDesc.from_json(c["desc"]), record=False)
                for c in rec["calls"]
            ]
        self._ret_verdicts: Dict[FuncKey, Verdict] = {}
        self._coll_names_memo: Dict[FuncKey, frozenset] = {}
        self.param_sinks: Dict[FuncKey, Dict[int, List[dict]]] = {}
        self._build_param_sinks()

    # -------------- taint resolution -------------- #

    def resolve_tokens(self, key: FuncKey, tokens, stack=(), bind=None, cut=None) -> Verdict:
        """Concrete verdict for a symbolic token set inside ``key``.

        ``bind`` optionally maps this frame's parameter indices to already-
        resolved caller verdicts (used when a callee's return metadata is
        pulled across a call boundary — its ``param:i`` tokens mean the
        caller's arguments, not free parameters).  ``cut``, when given, is
        a one-element list set True if any cycle/depth cap truncated the
        resolution — a cut result is stack-specific and must not be
        memoized."""
        v = Verdict()
        for tok in tokens:
            if tok == _TOK_RANK:
                v.rank = True
            elif tok == _TOK_UNKNOWN:
                v.unknown = True
            elif tok.startswith("param:"):
                p = int(tok.split(":", 1)[1])
                if bind is not None and p in bind:
                    v.merge(bind[p])
                else:
                    v.params.add(p)
            elif tok.startswith("call:"):
                v.merge(
                    self._resolve_call(key, int(tok.split(":", 1)[1]), stack, cut, bind)
                )
            elif tok.startswith("callelt:"):
                _t, cid_s, idx_s = tok.split(":")
                v.merge(
                    self._resolve_call_elt(
                        key, int(cid_s), int(idx_s), stack, cut, bind
                    )
                )
        return v

    def _call_arg_tokens(self, call: dict, callee: FuncKey, p: int):
        """The token set bound to the callee's parameter ``p`` at this call
        site (positional first, then by keyword name)."""
        if p < len(call["arg_taints"]):
            return call["arg_taints"][p]
        callee_params = self.functions[callee].get("params", [])
        if p < len(callee_params):
            return call["kw_taints"].get(callee_params[p])
        return None

    def _resolve_call(self, key: FuncKey, cid: int, stack, cut=None, bind=None) -> Verdict:
        # ``bind`` is the caller-of-``key`` binding for ``key``'s OWN
        # parameters: it applies to every token expressed in ``key``'s
        # frame (this call's argument tokens), never to callee-frame
        # tokens (those get their own binding via the residual-param loop)
        v = Verdict()
        if len(stack) >= _RESOLVE_DEPTH_CAP or (key, cid) in stack:
            if cut is not None:
                cut[0] = True
            return v  # cycle/depth cap: no evidence rather than a guess
        rec = self.functions[key]["calls"][cid]
        r = self.resolved[key][cid]
        stack2 = stack + ((key, cid),)
        if r.kind == "resolved" and r.target in self.functions:
            ret = self.ret_verdict(r.target, stack2, cut)
            v.rank |= ret.rank
            v.unknown |= ret.unknown
            # residual params of the callee bind to THIS call's arguments
            for p in sorted(ret.params):
                tokens = self._call_arg_tokens(rec, r.target, p)
                if tokens:
                    v.merge(self.resolve_tokens(key, tokens, stack2, bind, cut))
            return v
        if r.kind == "external" or (r.kind == "unresolved" and r.benign):
            # library/builtin calls: taint flows through arguments
            for tokens in list(rec["arg_taints"]) + [
                rec["kw_taints"][k] for k in sorted(rec["kw_taints"])
            ]:
                v.merge(self.resolve_tokens(key, tokens, stack2, bind, cut))
            return v
        v.unknown = True  # poisoning unresolved: could return anything
        return v

    def _resolve_call_elt(
        self, key: FuncKey, cid: int, idx: int, stack, cut=None, bind=None
    ) -> Verdict:
        """Verdict for element ``idx`` of a call's tuple return — element-
        precise when the callee's every return is a same-arity tuple
        literal, otherwise the whole-return verdict."""
        if len(stack) >= _RESOLVE_DEPTH_CAP or (key, cid) in stack:
            if cut is not None:
                cut[0] = True
            return Verdict()
        r = self.resolved[key][cid]
        if r.kind == "resolved" and r.target in self.functions:
            rt = self.functions[r.target].get("ret_tuple")
            if rt and idx < len(rt):
                rec = self.functions[key]["calls"][cid]
                stack2 = stack + ((key, cid),)
                v = Verdict()
                inner = self.resolve_tokens(r.target, rt[idx], stack2, cut=cut)
                v.rank |= inner.rank
                v.unknown |= inner.unknown
                for p in sorted(inner.params):
                    tokens = self._call_arg_tokens(rec, r.target, p)
                    if tokens:
                        v.merge(self.resolve_tokens(key, tokens, stack2, bind, cut))
                return v
        return self._resolve_call(key, cid, stack, cut, bind)

    def ret_verdict(self, key: FuncKey, stack=(), cut=None) -> Verdict:
        memo = self._ret_verdicts.get(key)
        if memo is not None:
            return memo
        rec = self.functions.get(key)
        if rec is None:
            return Verdict()
        # memoize iff THIS subtree resolved without a cycle/depth cut — a
        # cut result is an under-approximation specific to the entry stack
        my_cut = [False]
        v = self.resolve_tokens(key, rec["ret_taint"], stack, cut=my_cut)
        if my_cut[0]:
            if cut is not None:
                cut[0] = True
        else:
            self._ret_verdicts[key] = v
        return v

    # -------------- metadata resolution -------------- #
    #
    # concrete meta := {"dims": [int|"?"...], "split": int|None|"?",
    #                   "dtype": str|"?", "shape_rank": bool,
    #                   "dtype_rank": bool}
    # — the frame-free form: taint token LISTS are resolved to verdicts at
    # the frame boundary (a callee meta's ``param:i`` means the caller's
    # argument, so pulling a meta across a call rebinds, never copies).

    def concrete_meta(self, key: FuncKey, meta, stack=(), bind=None) -> Optional[dict]:
        """Frame-free concrete metadata for a possibly-symbolic value."""
        if meta is None or not isinstance(meta, dict):
            return None
        if "call" in meta:
            cid = meta["call"]
            if len(stack) >= _RESOLVE_DEPTH_CAP or (key, cid) in stack:
                return None
            r = self.resolved[key][cid]
            if r.kind != "resolved" or r.target not in self.functions:
                return None
            call = self.functions[key]["calls"][cid]
            callee = r.target
            stack2 = stack + ((key, cid),)
            newbind = {}
            for p in range(len(self.functions[callee].get("params", []))):
                tokens = self._call_arg_tokens(call, callee, p)
                if tokens:
                    newbind[p] = self.resolve_tokens(key, tokens, stack2, bind)
            rms = self.functions[callee]["ret_metas"]
            if not rms:
                return None
            outs = [self.concrete_meta(callee, m, stack2, newbind) for m in rms]
            out = outs[0]
            for m in outs[1:]:
                out = _cmeta_join(out, m)
            if out is not None and "resplit" in meta:
                out = dict(out, split=meta["resplit"])
            return out
        sv = self.resolve_tokens(key, meta["shape_taint"], stack, bind)
        dv = self.resolve_tokens(key, meta["dtype_taint"], stack, bind)
        return {
            "dims": None if meta["dims"] is None else list(meta["dims"]),
            "split": meta["split"],
            "dtype": meta["dtype"],
            "shape_rank": sv.rank,
            "dtype_rank": dv.rank,
        }

    # -------------- collective reachability -------------- #

    def collective_names(self, key: FuncKey, stack=()) -> frozenset:
        """Transitive set of collective names a call to ``key`` stages —
        read off the EFFECT summaries (one source of truth for footprints)."""
        memo = self._coll_names_memo.get(key)
        if memo is not None:
            return memo
        if key in stack or len(stack) >= _RESOLVE_DEPTH_CAP:
            return frozenset()
        from .summaries import _iter_atoms

        eff = self.program.effects.get(key)
        if eff is None:
            return frozenset()
        names = set()
        for atom in _iter_atoms(eff["footprint"]):
            if atom[0] == "coll":
                names.add(atom[1])
        for cid in range(len(eff["calls"])):
            r = self.program.resolved[key][cid]
            if r.kind == "resolved":
                names |= self.collective_names(r.target, stack + (key,))
        out = frozenset(names)
        if not stack:
            self._coll_names_memo[key] = out
        return out

    def region_coll_names(self, key: FuncKey, arm: dict) -> List[str]:
        """Sorted collective names staged in a recorded region (lexical
        plus the transitive footprint of every resolved call inside)."""
        names = set(arm["colls"])
        for cid in arm["cids"]:
            r = self.resolved[key][cid]
            if r.kind == "resolved" and r.target in self.program.effects:
                names |= self.collective_names(r.target)
        return sorted(names)

    # -------------- interprocedural param sinks (HT301) -------------- #

    def sink_candidates(self, key: FuncKey):
        """Every HT301 sink candidate in ``key`` with its SYMBOLIC taint —
        the ONE enumeration shared by the intraprocedural HT301 check
        (which fires on a ``rank`` verdict) and the param-sink summaries
        below (which collect residual-parameter verdicts), so the two can
        never disagree about what counts as a sink.  Yields dicts
        ``{kind, line, colls, tokens[, role]}``; the raw-lax operand and
        provable-array-payload exclusions live HERE."""
        rec = self.functions[key]
        for site in rec["flow_sites"]:
            colls_a = self.region_coll_names(key, site["arm_a"])
            colls_b = self.region_coll_names(key, site["arm_b"])
            if colls_a == colls_b:
                continue  # both paths stage the same traffic
            yield {
                "kind": site["kind"],
                "line": site["line"],
                "colls": colls_a or colls_b,
                "tokens": site["taint"],
            }
        for site in rec["coll_sites"]:
            if site["name"] in RAW_LAX_COLLECTIVES:
                # traced per-shard operands inside jit/shard_map: per-rank
                # values are the SEMANTICS of a lax collective (masked
                # psum IS the Bcast idiom) and staging is rank-uniform —
                # only enclosing control flow can diverge, and the flow
                # sites above cover that
                continue
            if site["name"] in _MATERIALIZER_COLLECTIVES:
                # host_fetch/numpy/process_allgather take the PAYLOAD being
                # materialized, not a control argument like Bcast's root:
                # value divergence across ranks is what a gather-style
                # materializer exists to observe, and METADATA divergence
                # (shape/dtype) is HT303's finding — convicting the payload
                # here misreads a data argument as a control one
                continue
            roles = [
                (f"arg{i}", t, site["arg_metas"][i])
                for i, t in enumerate(site["arg_taints"])
            ] + [
                (f"kw:{k}", site["kw_taints"][k], site["kw_metas"].get(k))
                for k in sorted(site["kw_taints"])
            ]
            for role, tokens, meta in roles:
                if self.concrete_meta(key, meta) is not None:
                    # a provable ARRAY payload: per-rank values are the
                    # point of a collective (reduce semantics) — only its
                    # metadata can diverge, and that is HT303's
                    continue
                yield {
                    "kind": "coll-arg",
                    "line": site["line"],
                    "colls": [site["name"]],
                    "role": role,
                    "tokens": tokens,
                }

    def _direct_param_sinks(self, key: FuncKey) -> Dict[int, List[dict]]:
        """Sinks inside ``key`` whose taint is residually parameter-borne:
        a caller passing a rank-derived argument hits them."""
        path, qual = key
        out: Dict[int, List[dict]] = {}
        for cand in self.sink_candidates(key):
            v = self.resolve_tokens(key, cand["tokens"])
            for p in sorted(v.params):
                entry = {
                    "kind": cand["kind"],
                    "line": cand["line"],
                    "colls": cand["colls"],
                    "chain": [[path, qual, cand["line"]]],
                }
                if "role" in cand:
                    entry["role"] = cand["role"]
                out.setdefault(p, []).append(entry)
        return out

    def _build_param_sinks(self) -> None:
        sinks = {key: self._direct_param_sinks(key) for key in sorted(self.functions)}
        # transitive: f forwards its own param into a sink position of g
        changed, guard = True, 0
        while changed and guard < 20:
            changed = False
            guard += 1
            for key in sorted(self.functions):
                rec = self.functions[key]
                path, qual = key
                for cid, call in enumerate(rec["calls"]):
                    r = self.resolved[key][cid]
                    if r.kind != "resolved" or r.target not in sinks or r.target == key:
                        continue
                    for p in sorted(sinks[r.target]):
                        tokens = self._call_arg_tokens(call, r.target, p)
                        if not tokens:
                            continue
                        v = self.resolve_tokens(key, tokens)
                        for my_p in sorted(v.params):
                            mine = sinks[key].setdefault(my_p, [])
                            for s in sinks[r.target][p]:
                                chain = [[path, qual, call["line"]]] + list(s["chain"])
                                if len(chain) > _CHAIN_CAP:
                                    continue
                                entry = dict(s, chain=chain)
                                if entry not in mine:
                                    mine.append(entry)
                                    changed = True
        self.param_sinks = sinks


def link(program) -> AbsintView:
    """Build the resolved absint view for a :class:`~.summaries.Program`."""
    return AbsintView(program, program.absint_facts)
