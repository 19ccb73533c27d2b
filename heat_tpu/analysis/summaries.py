"""Per-function effect summaries, fixpoint-propagated through the call graph.

This is the other half of the interprocedural engine (structure lives in
:mod:`.callgraph`): for every function in the linted tree it computes a
serializable **effect summary** —

- the ordered **collective footprint** (which collectives are staged, in
  what order, with branch structure preserved: a data-conditional ``if``
  whose arms stage different sequences becomes an ``either`` atom, a
  rank-conditional one is recorded for HT201);
- **host syncs** performed (the HT101 sink vocabulary), with their
  lexical-visibility class (``naked`` = HT101 flags the site itself,
  ``suppressed`` = an inline disable hides it);
- **blocking waits** outside any lexical ``comm.deadline`` scope (HT107's
  vocabulary);
- **donated parameters** (directly, or transitively by passing a param
  into a callee position that donates);
- whether the function **returns a device value** (so ``float(helper(x))``
  can be recognized as a host sync lexical HT101 provably misses);

and then propagates them through resolved call edges to a fixpoint.
Propagation is honest about its blind spots: *poisoning* unresolved calls
(see ``callgraph.POISONING_REASONS``) turn any conclusion that crosses them
into ``info`` severity, and public functions are **consumption barriers** —
an effect is reported once, at the first public boundary that reaches it,
never cascaded to that boundary's callers.

Summaries are cached per file in ``.heatlint-summaries.json`` keyed by a
content hash, so an unchanged file costs one hash, not one AST walk; the
cross-file linking and fixpoint always re-run (they are cheap and depend on
the whole file set).  The cache carries TWO version axes: ``version`` (the
JSON layout) and ``schema`` (:data:`ANALYSIS_SCHEMA_REV` — the semantic
revision of the cached facts).  A content hash alone cannot know that the
*analysis* changed underneath an unchanged file: when a new pass adds fact
atoms (the HT3xx absint records, for one), an old cache would silently
serve summaries that lack them.  Bump ``ANALYSIS_SCHEMA_REV`` whenever the
extracted fact vocabulary changes; any mismatch — like a corrupt file — is
a miss, never an error.

Stdlib-only and standalone-loadable, like the rest of ``analysis/``.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from .callgraph import (
    CallDesc,
    CallGraph,
    FileFacts,
    FuncKey,
    Resolution,
    call_desc,
    call_name,
    dotted_name,
    extract_structure,
    last_attr,
)

CACHE_VERSION = 2  # JSON layout of the cache file
# Semantic revision of the cached per-file facts.  Bump whenever extraction
# gains/changes fact atoms so pre-existing caches (keyed by file content
# hash, which cannot see analyzer changes) become misses instead of
# silently serving summaries that lack the new facts.
# rev 2: absint records (rank-taint + array-metadata)
# rev 3: ISSUE 13 — item-on-materialized-data sink exemption,
# materializer-collective HT301 exclusion
# rev 4: ISSUE 30 — the per-function and per-module inventory records are gone
ANALYSIS_SCHEMA_REV = 4
_EXPAND_CAP = 160  # atoms per expanded footprint before truncation
_CHAIN_CAP = 12  # hops kept in a provenance chain

# ------------------------------------------------------------------ #
# shared effect vocabulary (rules.py re-exports for compatibility)
# ------------------------------------------------------------------ #

COLLECTIVES = frozenset(
    {
        # Communication public API (MPI names)
        "Allreduce", "Allgather", "Alltoall", "Bcast", "Send", "Reduce",
        "Scatter", "Gather", "ReduceScatter", "Scan", "Exscan",
        "Iallreduce", "Iallgather", "Ialltoall", "Ibcast", "Isend", "Irecv",
        "Barrier", "resplit", "resplit_", "redistribute_",
        # collective-by-contract host boundary (every process must call)
        "host_fetch", "numpy", "process_allgather", "sync_global_devices",
        # raw lax collectives
        "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
        "ppermute", "psum_scatter", "pbroadcast",
    }
)

RANK_ATTRS = ("rank",)  # comm.rank, self.rank, ...
RANK_CALLS = ("process_index", "axis_index")  # jax.process_index(), ...
RANK_NAMES = ("rank", "process_id", "pid")  # bare local variables

# calls that END a device-value expression: their result is host data
MATERIALIZERS = frozenset({"host_fetch", "numpy", "tolist", "item"})

# the materialization API: effects NEVER propagate out of these defs —
# calling them is an explicit, visible host boundary, not a hidden sync
HOST_SANCTIONED_DEFS = frozenset(
    {
        "numpy", "item", "tolist", "host_fetch", "host_fetch_all",
        "__array__", "__bool__", "__int__", "__float__", "__complex__",
        "__index__", "__torch_proxy__", "__repr__", "__str__",
    }
)
# modules whose JOB is materialization
HOST_SANCTIONED_MODULES = ("core/printing.py", "core/io.py")

BLOCKING_ATTRS = frozenset(
    {"Barrier", "Wait", "block_until_ready", "sync_global_devices"}
)
WAIT_SANCTIONED_MODULES = ("core/communication.py", "utils/health.py")


def module_matches(path: str, suffixes: Tuple[str, ...]) -> bool:
    return any(path.endswith(s) for s in suffixes)


def routed_through_materializer(node: ast.AST) -> bool:
    """True when the value ``node`` evaluates to is PRODUCED by a
    sanctioned materialization call (``host_fetch``/``numpy()``/``tolist``)
    — i.e. the outermost producer, looking through attribute/subscript
    views (``host_fetch(x).T``, ``host_fetch(x)[0]``), is a materializer:
    the value is host data, so a trailing ``.item()`` on it is plain
    numpy, not a device sync.  A materializer merely *somewhere inside*
    does NOT count: ``jnp.abs(host_fetch(x) - y).item()`` re-enters the
    device domain on top of the fetched data and is exactly the sync the
    rule exists to flag.  ``item`` itself never counts as a route."""
    cur = node
    while isinstance(cur, (ast.Attribute, ast.Subscript)):
        cur = cur.value
    return isinstance(cur, ast.Call) and last_attr(cur) in MATERIALIZERS - {"item"}


def subtree_mentions_device_value(node: ast.AST) -> bool:
    """Heuristic for 'this expression is a device value': it touches the raw
    jax array plumbing (``._jarray``/``._parray``/``.larray``) or directly
    calls into jnp/lax/jax.numpy — UNLESS the expression already routes
    through a sanctioned materialization call (``host_fetch``/``numpy()``),
    in which case the value is host-side by the time it is consumed."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and last_attr(sub) in MATERIALIZERS:
            return False
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in (
            "_jarray",
            "_parray",
            "larray",
        ):
            return True
        if isinstance(sub, ast.Call):
            dn = call_name(sub)
            if dn and (
                dn.startswith("jnp.") or dn.startswith("lax.") or dn.startswith("jax.numpy.")
            ):
                return True
    return False


def rank_marker(test: ast.AST) -> Optional[str]:
    """The rank-identity expression a branch test depends on, or None."""
    for sub in ast.walk(test):
        if isinstance(sub, ast.Attribute) and sub.attr in RANK_ATTRS:
            return dotted_name(sub) or sub.attr
        if isinstance(sub, ast.Call):
            la = last_attr(sub)
            if la in RANK_CALLS:
                return la
        if isinstance(sub, ast.Name) and sub.id in RANK_NAMES:
            return sub.id
    return None


# ------------------------------------------------------------------ #
# effect extraction (one pass per function, shares the parsed tree)
# ------------------------------------------------------------------ #
#
# Footprint atoms are plain JSON lists so summaries round-trip through the
# cache unchanged:
#   ["coll", name, line]                     staged collective (lexical)
#   ["call", call_id, line]                  edge into effects["calls"][id]
#   ["cast", detail, line, call_id]          float/int/bool/np.asarray of a
#                                            single call (device-ness known
#                                            only interprocedurally)
#   ["branch", line, [A...], [B...]]         data-conditional if
#   ["rankbranch", marker, line, [A], [B], kind]   rank-conditional if/while
#   ["loop", line, [body...]]                for / non-rank while
#   ["dlscope", line, [body...]]             with ...deadline(...):
#   ["sink", detail, line, vis]              naked host sync (vis: "naked" |
#                                            "suppressed")
#   ["wait", detail, line, vis]              naked blocking wait


_CAST_NAMES = {"float": "float-cast", "int": "int-cast", "bool": "bool-cast"}


class _EffectExtractor:
    def __init__(self, ctx, fn_node: ast.AST):
        self.ctx = ctx
        self.fn = fn_node
        self.qual = ctx.qualname(fn_node)
        self.calls: List[list] = []  # [desc_json, line, under_dl]
        self.rank_branches: List[list] = []
        self.returns_device = False
        self.returns_calls: List[int] = []  # call ids
        self.direct_donated: List[list] = []  # [param_index, line]
        self.params = self._params()
        self.host_sanctioned = module_matches(
            ctx.path, HOST_SANCTIONED_MODULES
        ) or any(part in HOST_SANCTIONED_DEFS for part in self.qual.split("."))
        self.wait_sanctioned = module_matches(ctx.path, WAIT_SANCTIONED_MODULES)

    def _params(self) -> List[str]:
        a = self.fn.args
        params = [p.arg for p in list(a.posonlyargs) + list(a.args)]
        parent = self.ctx.parent(self.fn)
        if isinstance(parent, ast.ClassDef) and params and params[0] in ("self", "cls"):
            params = params[1:]
        return params

    def run(self) -> dict:
        footprint = self._stmts(self.fn.body, under_dl=False)
        return {
            "footprint": footprint,
            "calls": self.calls,
            "rank_branches": self.rank_branches,
            "returns_device": self.returns_device,
            "returns_calls": self.returns_calls,
            "direct_donated_params": self.direct_donated,
        }

    # ---------------- statement walk ---------------- #

    def _stmts(self, stmts: Sequence[ast.stmt], under_dl: bool) -> List[list]:
        out: List[list] = []
        for stmt in stmts:
            out.extend(self._stmt(stmt, under_dl))
        return out

    def _stmt(self, stmt: ast.stmt, under_dl: bool) -> List[list]:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return []  # their own entities
        if isinstance(stmt, ast.If):
            test_atoms = self._expr(stmt.test, under_dl)
            body = self._stmts(stmt.body, under_dl)
            orelse = self._stmts(stmt.orelse, under_dl)
            marker = rank_marker(stmt.test)
            if marker is not None:
                atom = ["rankbranch", marker, stmt.lineno, body, orelse, "if"]
                self.rank_branches.append(atom)
                return test_atoms + [atom]
            return test_atoms + [["branch", stmt.lineno, body, orelse]]
        if isinstance(stmt, ast.While):
            test_atoms = self._expr(stmt.test, under_dl)
            body = self._stmts(stmt.body + stmt.orelse, under_dl)
            marker = rank_marker(stmt.test)
            if marker is not None:
                atom = ["rankbranch", marker, stmt.lineno, body, [], "while"]
                self.rank_branches.append(atom)
                return test_atoms + [atom]
            return test_atoms + ([["loop", stmt.lineno, body]] if body else [])
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            iter_atoms = self._expr(stmt.iter, under_dl)
            body = self._stmts(stmt.body + stmt.orelse, under_dl)
            return iter_atoms + ([["loop", stmt.lineno, body]] if body else [])
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            item_atoms: List[list] = []
            arms_deadline = False
            for item in stmt.items:
                expr = item.context_expr
                if isinstance(expr, ast.Call) and last_attr(expr) == "deadline":
                    arms_deadline = True
                item_atoms.extend(self._expr(expr, under_dl))
            body = self._stmts(stmt.body, under_dl or arms_deadline)
            if arms_deadline:
                return item_atoms + [["dlscope", stmt.lineno, body]]
            return item_atoms + body
        if isinstance(stmt, ast.Try):
            body = self._stmts(stmt.body + stmt.orelse, under_dl)
            final = self._stmts(stmt.finalbody, under_dl)
            handlers: List[List[list]] = [
                self._stmts(h.body, under_dl) for h in stmt.handlers
            ]
            out = list(body)
            for h in handlers:
                if h != []:
                    # a handler that stages differently from nothing: model
                    # as a branch between "no exception" and this handler
                    out = [["branch", stmt.lineno, out, out + h]]
            return out + final
        if isinstance(stmt, ast.Return):
            if stmt.value is None:
                return []
            atoms = self._expr(stmt.value, under_dl)
            if subtree_mentions_device_value(stmt.value):
                self.returns_device = True
            if isinstance(stmt.value, ast.Call):
                # the call atom for this node was just emitted; it is the
                # last "call" atom referencing this line/col
                for atom in reversed(atoms):
                    if atom[0] == "call" and atom[2] == stmt.value.lineno:
                        self.returns_calls.append(atom[1])
                        break
            return atoms
        # any other statement: collect its expressions in document order
        out = []
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, (ast.expr, ast.keyword)):
                out.extend(self._expr(child, under_dl))
            elif isinstance(child, ast.stmt):
                out.extend(self._stmt(child, under_dl))
        return out

    # ---------------- expression walk ---------------- #

    def _expr(self, node: ast.AST, under_dl: bool) -> List[list]:
        out: List[list] = []
        self._expr_into(node, under_dl, out)
        return out

    def _expr_into(self, node: ast.AST, under_dl: bool, out: List[list]) -> None:
        if isinstance(node, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return  # deferred bodies are their own (or no) entity
        if isinstance(node, ast.Call):
            self._call(node, under_dl, out)
            return
        for child in ast.iter_child_nodes(node):
            self._expr_into(child, under_dl, out)

    def _add_call(self, node: ast.Call, under_dl: bool) -> int:
        cid = len(self.calls)
        self.calls.append([call_desc(node).to_json(), node.lineno, under_dl])
        return cid

    def _call(self, node: ast.Call, under_dl: bool, out: List[list]) -> None:
        # Python evaluation order: the callee expression (including a
        # chained receiver — ``comm.resplit(x).numpy()`` stages resplit
        # FIRST) evaluates before the arguments, which evaluate before the
        # call itself; emit atoms in that order.
        if isinstance(node.func, ast.Call):
            # getattr(o, n)(...) — the resolving expression is a call itself
            self._expr_into(node.func, under_dl, out)
        elif isinstance(node.func, ast.Attribute):
            self._expr_into(node.func.value, under_dl, out)
        for child in list(node.args) + [kw.value for kw in node.keywords]:
            self._expr_into(child, under_dl, out)

        la = last_attr(node)
        dn = call_name(node)
        line = node.lineno

        # Barrier()/sync_global_devices are BOTH collectives (footprint) and
        # blocking waits (HT204): emit both atoms, not whichever comes first
        foreign_barrier = la == "Barrier" and (node.args or node.keywords)
        emitted = False
        if (
            la in BLOCKING_ATTRS
            and not self.wait_sanctioned
            and not foreign_barrier
            and not under_dl
        ):
            vis = (
                "suppressed"
                if self.ctx.is_suppressed("HT107", line)
                else "naked"
            )
            out.append(["wait", la, line, vis])
            emitted = True
        if la in COLLECTIVES and not foreign_barrier:
            out.append(["coll", la, line])
            emitted = True
        if emitted:
            return
        # host-sync sinks (HT101 vocabulary)
        if not self.host_sanctioned:
            vis = (
                "suppressed"
                if self.ctx.is_suppressed("HT101", line)
                else "naked"
            )
            if la == "item" and isinstance(node.func, ast.Attribute) and not node.args:
                if not routed_through_materializer(node.func.value):
                    # mirrors HT101: .item() on already-fetched host data is
                    # not a sync, so it must not propagate as one either
                    out.append(["sink", "item", line, vis])
                    return
            if dn == "jax.device_get":
                out.append(["sink", "device_get", line, vis])
                return
            if dn in ("np.asarray", "numpy.asarray", "np.array", "numpy.array") and node.args:
                if subtree_mentions_device_value(node.args[0]):
                    out.append(["sink", "np.asarray", line, vis])
                    return
                if isinstance(node.args[0], ast.Call):
                    cid = self._add_call(node.args[0], under_dl)
                    out.append(["cast", "np.asarray", line, cid])
                    return
            if dn in _CAST_NAMES and len(node.args) == 1:
                if subtree_mentions_device_value(node.args[0]):
                    out.append(["sink", _CAST_NAMES[dn], line, vis])
                    return
                if isinstance(node.args[0], ast.Call):
                    cid = self._add_call(node.args[0], under_dl)
                    out.append(["cast", _CAST_NAMES[dn], line, cid])
                    return

        # direct param donation: f(param, ..., donate=True) / jit positions
        desc = call_desc(node)
        if desc.donate_kwarg and node.args and isinstance(node.args[0], ast.Name):
            name = node.args[0].id
            if name in self.params:
                self.direct_donated.append([self.params.index(name), line])

        cid = self._add_call(node, under_dl)
        out.append(["call", cid, line])


def extract_effects(ctx) -> Dict[str, dict]:
    """qualname -> effect summary for every def in the file."""
    out: Dict[str, dict] = {}
    for node in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
        out[ctx.qualname(node)] = _EffectExtractor(ctx, node).run()
    return out


# ------------------------------------------------------------------ #
# the summary cache
# ------------------------------------------------------------------ #


def file_hash(source: str) -> str:
    return hashlib.sha256(source.encode("utf-8")).hexdigest()


def _empty_cache() -> dict:
    return {"version": CACHE_VERSION, "schema": ANALYSIS_SCHEMA_REV, "files": {}}


def load_cache(path: Optional[str]) -> dict:
    if not path or not os.path.exists(path):
        return _empty_cache()
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
        if data.get("version") != CACHE_VERSION:
            return _empty_cache()
        if data.get("schema") != ANALYSIS_SCHEMA_REV:
            # the analyzer changed underneath the cached facts: every entry
            # is stale regardless of content hash — full miss
            return _empty_cache()
        if not isinstance(data.get("files"), dict):
            return _empty_cache()
        return data
    except (OSError, ValueError):
        # a corrupt cache is a cache miss, never an error
        return _empty_cache()


def save_cache(path: str, data: dict) -> None:
    tmp = path + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(data, fh)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only checkout: the cache is an optimization only


# ------------------------------------------------------------------ #
# the linked program: resolution + fixpoint propagation
# ------------------------------------------------------------------ #


@dataclass
class _Norm:
    """One normalized footprint atom with provenance."""

    kind: str  # "coll" | "dyn" | "cycle" | "trunc" | "either" | "loop"
    data: object = None
    chain: Tuple[Tuple[str, str, int], ...] = ()  # (path, qualname, line) hops

    def stripped(self):
        if self.kind in ("either", "loop") and self.data is not None:
            return (self.kind, self.data)
        return (self.kind, self.data)


def _strip(seq: Sequence[_Norm]) -> Tuple:
    return tuple(n.stripped() for n in seq)


def _has_ambiguity(seq: Sequence[_Norm]) -> bool:
    for n in seq:
        if n.kind in ("dyn", "cycle", "trunc"):
            return True
        if n.kind in ("either", "loop"):
            # data holds stripped tuples; scan them textually
            if _tuple_has_ambiguity(n.data):
                return True
    return False


def _tuple_has_ambiguity(data) -> bool:
    if isinstance(data, tuple):
        if data and data[0] in ("dyn", "cycle", "trunc"):
            return True
        return any(_tuple_has_ambiguity(d) for d in data)
    return False


@dataclass
class SyncReport:
    entry: FuncKey
    entry_line: int
    chain: Tuple[Tuple[str, str, int], ...]
    detail: str
    vis: str  # "naked" | "suppressed" | "cast"


@dataclass
class WaitReport:
    entry: FuncKey
    entry_line: int
    chain: Tuple[Tuple[str, str, int], ...]
    detail: str
    vis: str


@dataclass
class DonationInfo:
    """Why calling this function donates parameter ``param``."""

    param: int
    chain: Tuple[Tuple[str, str, int], ...]


class Program:
    """Everything the HT2xx rules consume: contexts, facts, effects, the
    resolved call graph, and the fixpoint-propagated summaries."""

    def __init__(
        self,
        contexts: dict,
        facts: dict,
        effects: dict,
        graph: CallGraph,
        absint_facts: Optional[dict] = None,
    ):
        self.contexts = contexts  # path -> LintContext
        self.facts = facts  # path -> FileFacts
        self.effects = effects  # FuncKey -> effect dict
        self.graph = graph
        self.absint_facts = absint_facts or {}  # path -> absint fact dict
        self._absint_view = None
        # per function: list aligned with effects["calls"] of Resolution
        self.resolved: Dict[FuncKey, List[Resolution]] = {}
        # fixpoint results
        self.returns_device: Dict[FuncKey, bool] = {}
        self.donates: Dict[FuncKey, Dict[int, DonationInfo]] = {}
        self.sync_exposed: Dict[FuncKey, Dict[Tuple, Tuple]] = {}
        self.wait_exposed: Dict[FuncKey, Dict[Tuple, Tuple]] = {}
        self.sync_reports: List[SyncReport] = []
        self.wait_reports: List[WaitReport] = []
        self._norm_memo: Dict[FuncKey, List[_Norm]] = {}
        self._link()
        self._propagate()

    # ---------------- linking ---------------- #

    def _link(self) -> None:
        for key, eff in self.effects.items():
            res = []
            for desc_json, _line, _dl in eff["calls"]:
                res.append(self.graph.resolve(key, CallDesc.from_json(desc_json)))
            self.resolved[key] = res

    def func(self, key: FuncKey):
        return self.graph.functions.get(key)

    @property
    def absint(self):
        """The linked abstract-interpretation view (HT3xx's input), built
        lazily on first access so HT2xx-only runs never pay for it."""
        if self._absint_view is None:
            from . import absint as _absint

            self._absint_view = _absint.link(self)
        return self._absint_view

    def is_public(self, key: FuncKey) -> bool:
        fn = self.func(key)
        return fn is not None and fn.is_public

    # ---------------- fixpoint: returns_device ---------------- #

    def _propagate(self) -> None:
        rd = {k: bool(e["returns_device"]) for k, e in self.effects.items()}
        changed = True
        while changed:
            changed = False
            for key, eff in self.effects.items():
                if rd[key]:
                    continue
                for cid in eff["returns_calls"]:
                    r = self.resolved[key][cid]
                    if r.kind == "resolved" and rd.get(r.target, False):
                        fn = self.func(r.target)
                        if fn is not None and fn.name in MATERIALIZERS:
                            continue  # materializers return host data
                        rd[key] = True
                        changed = True
                        break
        self.returns_device = rd
        self._propagate_donates()
        self._propagate_sinks()
        self._propagate_waits()

    # ---------------- fixpoint: donated params ---------------- #

    def _propagate_donates(self) -> None:
        don: Dict[FuncKey, Dict[int, DonationInfo]] = {}
        for key, eff in self.effects.items():
            own: Dict[int, DonationInfo] = {}
            for p, line in eff["direct_donated_params"]:
                own[p] = DonationInfo(p, ((key[0], key[1], line),))
            don[key] = own
        changed = True
        while changed:
            changed = False
            for key, eff in self.effects.items():
                fn = self.func(key)
                if fn is None:
                    continue
                params = list(fn.params)
                for cid, (desc_json, line, _dl) in enumerate(eff["calls"]):
                    r = self.resolved[key][cid]
                    if r.kind != "resolved":
                        continue
                    callee_don = don.get(r.target, {})
                    positions = set(callee_don) | set(r.donates_override or ())
                    if not positions:
                        continue
                    args = desc_json.get("args", [])
                    for p in positions:
                        if p >= len(args) or args[p] is None:
                            continue
                        if args[p] in params:
                            my_p = params.index(args[p])
                            if my_p not in don[key]:
                                inner = callee_don.get(p)
                                chain = ((key[0], key[1], line),) + (
                                    inner.chain if inner else ()
                                )
                                don[key][my_p] = DonationInfo(my_p, chain[:_CHAIN_CAP])
                                changed = True
        self.donates = don

    # ---------------- propagation: host syncs ---------------- #

    def _sync_barrier(self, key: FuncKey) -> bool:
        path, qual = key
        if module_matches(path, HOST_SANCTIONED_MODULES):
            return True
        if any(part in HOST_SANCTIONED_DEFS for part in qual.split(".")):
            return True
        return self.is_public(key)  # consumed (and reported) at the boundary

    def _propagate_sinks(self) -> None:
        # sink id -> (vis, chain); chains kept shortest
        exposed: Dict[FuncKey, Dict[Tuple, Tuple]] = {}
        for key, eff in self.effects.items():
            own: Dict[Tuple, Tuple] = {}
            for atom in _iter_atoms(eff["footprint"]):
                if atom[0] == "sink":
                    detail, line, vis = atom[1], atom[2], atom[3]
                    sid = (key[0], key[1], line, detail, vis)
                    own[sid] = ((key[0], key[1], line),)
            exposed[key] = own
        changed = True
        while changed:
            changed = False
            for key, eff in self.effects.items():
                for cid, (desc_json, line, _dl) in enumerate(eff["calls"]):
                    r = self.resolved[key][cid]
                    if r.kind != "resolved" or self._sync_barrier(r.target):
                        continue
                    for sid, chain in exposed.get(r.target, {}).items():
                        cand = ((key[0], key[1], line),) + chain
                        cand = cand[:_CHAIN_CAP]
                        cur = exposed[key].get(sid)
                        if cur is None or len(cand) < len(cur):
                            exposed[key][sid] = cand
                            changed = True
        self.sync_exposed = exposed

        # reports: cast sinks at their containing function; naked/suppressed
        # sinks at public entries >= 1 hop away.  One report per
        # (entry, sink) — a second call path to the same sink is noise.
        seen: set = set()
        for key, eff in self.effects.items():
            for atom in _iter_atoms(eff["footprint"]):
                if atom[0] != "cast":
                    continue
                detail, line, cid = atom[1], atom[2], atom[3]
                r = self.resolved[key][cid]
                if r.kind == "resolved" and self.returns_device.get(r.target, False):
                    tf = self.func(r.target)
                    tline = tf.line if tf is not None else 1
                    self.sync_reports.append(
                        SyncReport(
                            entry=key,
                            entry_line=line,
                            chain=(
                                (key[0], key[1], line),
                                (r.target[0], r.target[1], tline),
                            ),
                            detail=detail,
                            vis="cast",
                        )
                    )
            if not self.is_public(key):
                continue
            for cid, (desc_json, line, _dl) in enumerate(eff["calls"]):
                r = self.resolved[key][cid]
                if r.kind != "resolved" or self._sync_barrier(r.target):
                    continue
                for sid, chain in self.sync_exposed.get(r.target, {}).items():
                    if (key, sid) in seen:
                        continue
                    seen.add((key, sid))
                    _p, _q, _sline, detail, vis = sid
                    self.sync_reports.append(
                        SyncReport(
                            entry=key,
                            entry_line=line,
                            chain=((key[0], key[1], line),) + chain,
                            detail=detail,
                            vis=vis,
                        )
                    )

    # ---------------- propagation: blocking waits ---------------- #

    def _wait_barrier(self, key: FuncKey) -> bool:
        path, qual = key
        if module_matches(path, WAIT_SANCTIONED_MODULES):
            return True
        if any(part in HOST_SANCTIONED_DEFS for part in qual.split(".")):
            return True  # the materialization API blocks by design
        return self.is_public(key)

    def _propagate_waits(self) -> None:
        exposed: Dict[FuncKey, Dict[Tuple, Tuple]] = {}
        for key, eff in self.effects.items():
            own: Dict[Tuple, Tuple] = {}
            for atom in _iter_atoms_outside_dlscope(eff["footprint"]):
                if atom[0] == "wait":
                    detail, line, vis = atom[1], atom[2], atom[3]
                    sid = (key[0], key[1], line, detail, vis)
                    own[sid] = ((key[0], key[1], line),)
            exposed[key] = own
        changed = True
        while changed:
            changed = False
            for key, eff in self.effects.items():
                for cid, (desc_json, line, under_dl) in enumerate(eff["calls"]):
                    if under_dl:
                        continue  # the caller armed a deadline around this call
                    r = self.resolved[key][cid]
                    if r.kind != "resolved" or self._wait_barrier(r.target):
                        continue
                    for sid, chain in exposed.get(r.target, {}).items():
                        cand = ((key[0], key[1], line),) + chain
                        cand = cand[:_CHAIN_CAP]
                        cur = exposed[key].get(sid)
                        if cur is None or len(cand) < len(cur):
                            exposed[key][sid] = cand
                            changed = True
        self.wait_exposed = exposed
        seen: set = set()
        for key, eff in self.effects.items():
            if not self.is_public(key):
                continue
            for cid, (desc_json, line, under_dl) in enumerate(eff["calls"]):
                if under_dl:
                    continue
                r = self.resolved[key][cid]
                if r.kind != "resolved" or self._wait_barrier(r.target):
                    continue
                for sid, chain in self.wait_exposed.get(r.target, {}).items():
                    if (key, sid) in seen:
                        continue
                    seen.add((key, sid))
                    _p, _q, _sline, detail, vis = sid
                    self.wait_reports.append(
                        WaitReport(
                            entry=key,
                            entry_line=line,
                            chain=((key[0], key[1], line),) + chain,
                            detail=detail,
                            vis=vis,
                        )
                    )

    # ---------------- ordered footprint expansion (HT201) ---------------- #

    def norm_function(self, key: FuncKey) -> List[_Norm]:
        memo = self._norm_memo.get(key)
        if memo is not None:
            return memo
        out, complete = self._norm_atoms(key, self.effects[key]["footprint"], (key,))
        if complete:
            self._norm_memo[key] = out
        return out

    def norm_arm(self, key: FuncKey, atoms: Sequence[list]) -> List[_Norm]:
        out, _complete = self._norm_atoms(key, atoms, (key,))
        return out

    def _norm_atoms(
        self, key: FuncKey, atoms: Sequence[list], stack: Tuple[FuncKey, ...]
    ) -> Tuple[List[_Norm], bool]:
        out: List[_Norm] = []
        complete = True
        for atom in atoms:
            if len(out) > _EXPAND_CAP:
                out.append(_Norm("trunc"))
                return out, complete
            kind = atom[0]
            if kind == "coll":
                out.append(
                    _Norm("coll", atom[1], chain=((key[0], key[1], atom[2]),))
                )
            elif kind == "call":
                cid, line = atom[1], atom[2]
                r = self.resolved[key][cid]
                if r.kind == "external":
                    continue
                if r.kind == "unresolved":
                    if not r.benign:
                        out.append(
                            _Norm("dyn", None, chain=((key[0], key[1], line),))
                        )
                    continue
                target = r.target
                if target in stack:
                    out.append(
                        _Norm("cycle", None, chain=((key[0], key[1], line),))
                    )
                    complete = False
                    continue
                if len(stack) >= 12:
                    out.append(
                        _Norm("trunc", None, chain=((key[0], key[1], line),))
                    )
                    complete = False
                    continue
                memo = self._norm_memo.get(target)
                if memo is None:
                    inner, inner_complete = self._norm_atoms(
                        target,
                        self.effects.get(target, {"footprint": []})["footprint"],
                        stack + (target,),
                    )
                    if inner_complete:
                        self._norm_memo[target] = inner
                    else:
                        complete = False
                    memo = inner
                hop = (key[0], key[1], line)
                for n in memo:
                    out.append(
                        _Norm(n.kind, n.data, chain=((hop,) + n.chain)[:_CHAIN_CAP])
                    )
                    if len(out) > _EXPAND_CAP:
                        out.append(_Norm("trunc"))
                        return out, complete
            elif kind == "cast" or kind == "sink" or kind == "wait":
                continue  # not collective traffic
            elif kind == "branch":
                a, ca = self._norm_atoms(key, atom[2], stack)
                b, cb = self._norm_atoms(key, atom[3], stack)
                complete = complete and ca and cb
                if _strip(a) == _strip(b):
                    out.extend(a)
                else:
                    out.append(
                        _Norm(
                            "either",
                            (_strip(a), _strip(b)),
                            chain=((key[0], key[1], atom[1]),),
                        )
                    )
            elif kind == "rankbranch":
                # a nested rank-conditional gets its own HT201 finding at its
                # own site; for the surrounding comparison treat it like a
                # plain branch
                a, ca = self._norm_atoms(key, atom[3], stack)
                b, cb = self._norm_atoms(key, atom[4], stack)
                complete = complete and ca and cb
                if _strip(a) == _strip(b):
                    out.extend(a)
                else:
                    out.append(
                        _Norm(
                            "either",
                            (_strip(a), _strip(b)),
                            chain=((key[0], key[1], atom[2]),),
                        )
                    )
            elif kind == "loop":
                body, cb = self._norm_atoms(key, atom[2], stack)
                complete = complete and cb
                if body:
                    out.append(
                        _Norm(
                            "loop", _strip(body), chain=((key[0], key[1], atom[1]),)
                        )
                    )
            elif kind == "dlscope":
                body, cb = self._norm_atoms(key, atom[2], stack)
                complete = complete and cb
                out.extend(body)
        return out, complete

    # ---------------- finding helper (suppression-aware) ---------------- #

    def is_suppressed(self, code: str, path: str, line: int) -> bool:
        ctx = self.contexts.get(path)
        return ctx is not None and ctx.is_suppressed(code, line)


def _iter_atoms(atoms):
    """Every atom in a footprint, including branch/loop/dlscope bodies."""
    for atom in atoms:
        yield atom
        kind = atom[0]
        if kind == "branch":
            yield from _iter_atoms(atom[2])
            yield from _iter_atoms(atom[3])
        elif kind == "rankbranch":
            yield from _iter_atoms(atom[3])
            yield from _iter_atoms(atom[4])
        elif kind in ("loop", "dlscope"):
            yield from _iter_atoms(atom[2])


def _iter_atoms_outside_dlscope(atoms):
    for atom in atoms:
        yield atom
        kind = atom[0]
        if kind == "branch":
            yield from _iter_atoms_outside_dlscope(atom[2])
            yield from _iter_atoms_outside_dlscope(atom[3])
        elif kind == "rankbranch":
            yield from _iter_atoms_outside_dlscope(atom[3])
            yield from _iter_atoms_outside_dlscope(atom[4])
        elif kind == "loop":
            yield from _iter_atoms_outside_dlscope(atom[2])
        # dlscope bodies are deliberately NOT descended into


# ------------------------------------------------------------------ #
# program assembly (the entry point framework.lint_paths uses)
# ------------------------------------------------------------------ #


def build_program(contexts: dict, cache_path: Optional[str] = None) -> Program:
    """contexts: path -> LintContext (syntax-clean files only)."""
    from . import absint as _absint  # lazy: absint imports our vocabulary

    cache = load_cache(cache_path)
    files = cache["files"]
    facts: Dict[str, object] = {}
    effects: Dict[FuncKey, dict] = {}
    absint_facts: Dict[str, dict] = {}
    dirty = False
    for path, ctx in contexts.items():
        h = file_hash(ctx.source)
        ent = files.get(ctx.path)
        # an entry missing the absint record predates the schema field's
        # introduction (or was hand-edited): treat as a miss, like any
        # other stale-schema artifact
        if ent is not None and ent.get("hash") == h and "absint" in ent:
            ff = FileFacts.from_json(ent["facts"])
            eff = ent["effects"]
            ai = ent["absint"]
        else:
            ff = extract_structure(ctx)
            eff = extract_effects(ctx)
            ai = _absint.extract_absint(ctx)
            files[ctx.path] = {
                "hash": h,
                "facts": ff.to_json(),
                "effects": eff,
                "absint": ai,
            }
            dirty = True
        facts[ctx.path] = ff
        absint_facts[ctx.path] = ai
        for qual, e in eff.items():
            effects[(ctx.path, qual)] = e
    # evict only entries whose file is GONE from disk: a narrow run (one
    # file, one subdirectory) must not wipe the repo-wide cache for
    # everything outside its scope
    linted = {ctx.path for ctx in contexts.values()}
    stale = [p for p in files if p not in linted and not os.path.exists(p)]
    for p in stale:
        del files[p]
        dirty = True
    if cache_path and dirty:
        save_cache(cache_path, cache)
    graph = CallGraph(facts)
    return Program(contexts, facts, effects, graph, absint_facts=absint_facts)
