"""Built-in heatlint rules: the runtime's distributed invariants.

Each rule encodes one contract established by earlier rounds of perf,
robustness, and telemetry work (see doc/source/design.md "Static
contracts" for the full table):

- HT101 — no host syncs in library code (the sanitation.py contract)
- HT102 — no collective lexically inside a rank-conditional branch
- HT103 — no use of a name after its buffer was donated
- HT104 — every public collective in communication.py byte-accounts
- HT105 — no raw process entropy; seeding goes through ht.random
- HT106 — no DNDarray metadata mutation outside sanctioned modules
- HT107 — no naked blocking collective waits bypassing comm.deadline
- HT108 — no collective staging bypassing the seq-stamp choke point
- HT109 — no manual trace-identity fiddling outside the tracing helpers
- HT110 — no stale suppressions (a disable comment must suppress something)

The HT1xx analyses are intentionally *lexical and intra-procedural*: false
negatives across call boundaries are accepted; false positives are kept
low enough that the committed baseline stays short and new code rarely
needs a suppression.

The HT2xx family closes exactly those call-boundary false negatives with
the interprocedural engine (:mod:`.callgraph` + :mod:`.summaries`) — each
rule is the static twin of a runtime failure mode the earlier PRs made
observable:

- HT201 — static desync: the collective footprint differs across the arms
  of a rank-dependent branch anywhere in the transitive call chain (the
  lint-time counterpart of postmortem's ``desync`` verdict)
- HT202 — transitive host sync: a public API function whose call chain
  reaches a host sync lexical HT101 cannot see at the entry
- HT203 — interprocedural use-after-donate: a name is read after a call
  that donates it inside the callee (HT103 is intra-function only)
- HT204 — transitively undeadlined blocking: a blocking wait reachable
  from a public entry with no ``comm.deadline`` scope on any path (the
  lint-time counterpart of ``health.deadline.trips``)

HT2xx findings carry the full call-chain trace (``entry → helper →
sink``); conclusions that depend on an *unresolved* call (getattr
dispatch, lambdas, callables passed as values) are downgraded to ``info``
severity — reported, never gating, never a false positive.

The HT3xx family reasons about *values* with the abstract-interpretation
layer (:mod:`.absint`): a rank-taint lattice plus a symbolic
``(gshape, split, dtype)`` array-metadata domain, each the static twin of
a runtime conviction the observability PRs made nameable:

- HT301 — rank-tainted dataflow reaching collective control or arguments:
  a value *provably derived from process identity* guards a branch/loop
  that stages collectives, bounds a loop enclosing one, or is passed as a
  collective argument (the dataflow generalization of lexical HT102 and
  call-borne HT201 — ``n = comm.rank; if n == 0: _stage()`` is invisible
  to both) — front-runs postmortem's ``desync`` verdict
- HT302 — split mismatch at a binary-op/matmul site provable from the
  propagated metadata: the dispatch tail will raise or silently stage a
  communication-heavy implicit resplit — front-runs the dispatch
  ValueError / resplit warning
- HT303 — collective payload asymmetry: the staged payload's abstract
  ``gshape``/``dtype`` depends on rank-tainted data, so per-rank
  fingerprints (seq, op, gshape, dtype) cannot agree — front-runs the
  flight recorder's fingerprint-mismatch conviction
- HT304 — donation-size mismatch: a donated buffer's abstract
  shape/dtype differs from the consumer it must alias with — front-runs
  the donated-buffer RuntimeError

HT3xx findings fire only on *provable* rank derivation (``unknown`` — a
value of unanalyzable origin — never gates), and carry codeFlow traces
like the HT2xx family.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set, Tuple

from .callgraph import call_name, dotted_name, last_attr  # noqa: F401  — dotted_name re-exported (pre-interprocedural public helper)
from .framework import Finding, LintContext, Rule, register
from .summaries import (
    BLOCKING_ATTRS,
    COLLECTIVES,
    HOST_SANCTIONED_DEFS,
    HOST_SANCTIONED_MODULES,
    MATERIALIZERS,
    RANK_ATTRS,
    RANK_CALLS,
    RANK_NAMES,
    WAIT_SANCTIONED_MODULES,
    Program,
    _has_ambiguity,
    _iter_atoms,
    _strip,
    module_matches,
    rank_marker,
    routed_through_materializer,
    subtree_mentions_device_value,
)

# compatibility alias (pre-interprocedural name)
_MATERIALIZERS = MATERIALIZERS


def branch_exclusive(ctx: LintContext, a: ast.AST, b: ast.AST) -> bool:
    """True when ``a`` and ``b`` sit in mutually exclusive branches of the
    same ``if``/``try`` — sequential-order reasoning between them is invalid
    (used by HT103 to avoid flagging the untaken arm)."""
    chain_a = [a] + ctx.ancestors(a)
    chain_b = [b] + ctx.ancestors(b)
    set_b = set(map(id, chain_b))
    lca = next((n for n in chain_a if id(n) in set_b), None)
    if lca is None or not isinstance(lca, (ast.If, ast.Try)):
        return False

    def arm_of(node: ast.AST) -> Optional[str]:
        # which field of the lca contains this node's ancestor chain
        chain = [node] + ctx.ancestors(node)
        idx = [id(n) for n in chain].index(id(lca))
        if idx == 0:
            return None  # node IS the lca (e.g. the if test)
        child = chain[idx - 1]
        for fieldname in ("body", "orelse", "handlers", "finalbody"):
            if child in getattr(lca, fieldname, []):
                return fieldname
        return None

    fa, fb = arm_of(a), arm_of(b)
    if fa is None or fb is None:
        return False
    if isinstance(lca, ast.Try):
        # body vs handlers is exclusive-ish; finalbody always runs
        return fa != fb and "finalbody" not in (fa, fb)
    return fa != fb


# -------------------------------------------------------------------- #
# HT101 — host sync in library code
# -------------------------------------------------------------------- #


@register
class HostSyncRule(Rule):
    """Blocking device→host reads outside sanctioned materialization points.

    Library code runs in the middle of async dispatch pipelines: a
    ``.item()``, ``jax.device_get``, or ``np.asarray``/``float()``/``int()``
    of a device value stalls the host on the device stream (the
    ``sanitation.py`` no-value-reads contract).  Value materialization
    belongs behind the explicit points: ``numpy()``, ``item()``,
    ``Communication.host_fetch``, printing, and I/O.
    """

    code = "HT101"
    name = "host-sync-in-library"
    description = "blocking device→host read outside sanctioned materialization points"

    # modules whose JOB is materialization (printing, I/O) — shared with the
    # interprocedural summaries, which treat them as propagation barriers
    SANCTIONED_MODULES = HOST_SANCTIONED_MODULES
    # the materialization API itself + host-boundary helpers
    SANCTIONED_DEFS = HOST_SANCTIONED_DEFS

    def _sanctioned(self, ctx: LintContext, node: ast.AST) -> bool:
        fn = ctx.enclosing_function(node)
        while fn is not None:
            if fn.name in self.SANCTIONED_DEFS:
                return True
            fn = ctx.enclosing_function(ctx.parent(fn)) if ctx.parent(fn) else None
        return False

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        out = []
        for node in ctx.walk(ast.Call):
            if self._sanctioned(ctx, node):
                continue
            la = last_attr(node)
            dn = call_name(node)
            if la == "item" and isinstance(node.func, ast.Attribute) and not node.args:
                if routed_through_materializer(node.func.value):
                    # .item() on an already-fetched host array (the autofix
                    # engine's bare-item rewrite shape) is plain numpy, not
                    # a device sync
                    continue
                out.append(
                    ctx.finding(
                        self, node,
                        "`.item()` is a blocking device→host sync; route through a "
                        "sanctioned materialization point (numpy()/host_fetch) or keep "
                        "the value on device",
                        detail="item",
                    )
                )
            elif dn in ("jax.device_get",):
                out.append(
                    ctx.finding(
                        self, node,
                        "`jax.device_get` in library code is a blocking host sync; use "
                        "Communication.host_fetch at an explicit materialization point",
                        detail="device_get",
                    )
                )
            elif dn in ("np.asarray", "numpy.asarray", "np.array", "numpy.array") and node.args:
                if subtree_mentions_device_value(node.args[0]):
                    out.append(
                        ctx.finding(
                            self, node,
                            f"`{dn}` of a device value blocks on device→host transfer; "
                            "materialize via numpy()/host_fetch instead",
                            detail="np.asarray",
                        )
                    )
            elif dn in ("float", "int", "bool") and len(node.args) == 1:
                if subtree_mentions_device_value(node.args[0]):
                    out.append(
                        ctx.finding(
                            self, node,
                            f"`{dn}()` of a device value is an implicit `.item()` host "
                            "sync; keep the value on device or materialize explicitly",
                            detail=f"{dn}-cast",
                        )
                    )
        return [f for f in out if f is not None]


# -------------------------------------------------------------------- #
# HT102 — collective inside a rank-conditional branch
# -------------------------------------------------------------------- #


@register
class RankConditionalCollectiveRule(Rule):
    """A collective call lexically inside an ``if``/``while`` that branches on
    process/shard identity diverges the SPMD program: ranks that skip the
    branch never post the collective and the others deadlock (the round-5
    rank-conditional hazard class).  Rank-conditional *local* work (logging,
    file writes) is fine — only collective entry points are flagged."""

    code = "HT102"
    name = "rank-conditional-collective"
    description = "collective call inside a rank-conditional branch (SPMD divergence)"

    # the collective vocabulary and rank-identity markers are shared with
    # the interprocedural summaries (summaries.py) so HT102 and HT201 can
    # never disagree about what counts as a collective or a rank test
    COLLECTIVES: Set[str] = set(COLLECTIVES)
    RANK_ATTRS = RANK_ATTRS
    RANK_CALLS = RANK_CALLS
    RANK_NAMES = RANK_NAMES

    def _rank_conditional(self, test: ast.AST) -> Optional[str]:
        return rank_marker(test)

    def _arm_collectives(self, arm) -> dict:
        """collective name → [call nodes] for one branch arm."""
        found: dict = {}
        for stmt in arm:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call):
                    la = last_attr(sub)
                    if la in self.COLLECTIVES:
                        found.setdefault(la, []).append(sub)
        return found

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        out = []
        for node in ctx.walk(ast.If, ast.While):
            marker = self._rank_conditional(node.test)
            if marker is None:
                continue
            body = self._arm_collectives(node.body)
            orelse = self._arm_collectives(node.orelse if isinstance(node, ast.If) else [])
            for arm, other in ((body, orelse), (orelse, body)):
                for la, calls in arm.items():
                    if la in other:
                        # posted in BOTH arms: every rank attends whichever
                        # branch it takes — the sanctioned "collective fetch,
                        # rank-conditional use" idiom (e.g. save_zarr)
                        continue
                    for sub in calls:
                        out.append(
                            ctx.finding(
                                self, sub,
                                f"collective `{la}` inside a branch conditioned "
                                f"on `{marker}`: ranks that skip the branch never "
                                "post it (SPMD divergence/deadlock hazard)",
                                detail=la,
                            )
                        )
        return [f for f in out if f is not None]


# -------------------------------------------------------------------- #
# HT103 — use after donate
# -------------------------------------------------------------------- #


@register
class UseAfterDonateRule(Rule):
    """A name whose buffer was donated (``donate=True`` kwarg, or passed in a
    ``donate_argnums`` position of a locally-jitted function) must not be
    read afterwards: XLA may have aliased or freed the storage, and the read
    returns garbage or raises only under certain layouts.  Rebinding the
    name clears the taint; uses in a mutually exclusive branch don't count."""

    code = "HT103"
    name = "use-after-donate"
    description = "name referenced after its buffer was donated"

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
            out.extend(self._check_function(ctx, node))
        return out

    def _jit_donated_positions(self, call: ast.Call) -> Optional[Tuple[int, ...]]:
        """(positions) when ``call`` is jax.jit/jit with literal donate_argnums."""
        dn = call_name(call)
        if dn not in ("jax.jit", "jit"):
            return None
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                v = kw.value
                if isinstance(v, ast.Tuple):
                    pos = tuple(
                        e.value for e in v.elts if isinstance(e, ast.Constant) and isinstance(e.value, int)
                    )
                    return pos
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    return (v.value,)
                return ()  # dynamic donate_argnums: positions unknown, skip
        return None

    def _check_function(self, ctx: LintContext, fn: ast.AST) -> Iterable[Finding]:
        # jitted-callable names -> donated positions, discovered on the fly
        jitted: dict = {}
        # donation events: (sort key, donated name, donation call node)
        events: List[Tuple[Tuple[int, int], str, ast.Call]] = []

        own = [
            n
            for n in ast.walk(fn)
            if ctx.enclosing_function(n) is fn or n is fn
        ]
        for node in own:
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                pos = self._jit_donated_positions(node.value)
                if pos:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            jitted[tgt.id] = pos
        for node in own:
            if not isinstance(node, ast.Call):
                continue
            donated_names: List[str] = []
            for kw in node.keywords:
                if kw.arg == "donate" and isinstance(kw.value, ast.Constant) and kw.value.value is True:
                    if node.args and isinstance(node.args[0], ast.Name):
                        donated_names.append(node.args[0].id)
            fname = call_name(node)
            if fname in jitted:
                for p in jitted[fname]:
                    if p < len(node.args) and isinstance(node.args[p], ast.Name):
                        donated_names.append(node.args[p].id)
            for name in donated_names:
                key = (node.end_lineno or node.lineno, node.end_col_offset or 0)
                events.append((key, name, node))

        if not events:
            return []

        findings: List[Finding] = []
        for key, name, call in events:
            rebound_at: Optional[Tuple[int, int]] = None
            # the donating statement may itself rebind the name
            # (x = f(x, donate=True)) — taint never takes effect
            stmt = call
            for anc in [call] + ctx.ancestors(call):
                if isinstance(anc, ast.stmt):
                    stmt = anc
                    break
            if isinstance(stmt, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name for t in stmt.targets
            ):
                continue
            if isinstance(stmt, (ast.Return, ast.Raise)):
                # `return f(x, donate=True)` — control leaves the function at
                # the donation itself; no later read in this frame can see
                # the donated buffer
                continue
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Name)
                    and node.id == name
                    and isinstance(node.ctx, ast.Store)
                ):
                    at = (node.lineno, node.col_offset)
                    if at > key and (rebound_at is None or at < rebound_at):
                        rebound_at = at
            for node in ast.walk(fn):
                if not (
                    isinstance(node, ast.Name)
                    and node.id == name
                    and isinstance(node.ctx, ast.Load)
                ):
                    continue
                at = (node.lineno, node.col_offset)
                if at <= key:
                    continue
                if rebound_at is not None and at > rebound_at:
                    continue
                if branch_exclusive(ctx, call, node):
                    continue
                f = ctx.finding(
                    self, node,
                    f"`{name}` is read after its buffer was donated at line "
                    f"{call.lineno}; the storage may be aliased or freed",
                    detail=name,
                )
                if f is not None:
                    findings.append(f)
        return findings


# -------------------------------------------------------------------- #
# HT104 — unaccounted public collective in communication.py
# -------------------------------------------------------------------- #


@register
class CollectiveAccountingRule(Rule):
    """Every public collective in ``communication.py`` must byte-account at
    its entry (``self._account(...)`` / ``self._account_bytes(...)``) or
    delegate to another public collective that does — the telemetry round's
    invariant that no staged collective traffic is invisible to
    ``comm.<name>.calls/.bytes``.  The tiled-redistribution entry points
    (``resplit*``) may instead delegate to the chunked executor
    (``core.redistribution.execute_plan``), which byte-accounts every tile
    at its own staging point through ``_account_bytes`` — per-tile staging
    behind that entry is accounted, not invisible."""

    code = "HT104"
    name = "unaccounted-collective"
    description = "public collective without comm.<name> byte accounting"

    TARGET_SUFFIX = ("communication.py",)
    # the hierarchical/bucketed staging layer: module-level public staging
    # functions (``hierarchical_*``/``bucketed_*``/``dispatch_*``) must
    # account the same way — directly, through the telescoped stage
    # accountant ``_account_stages`` (which loops ``comm._account_bytes``
    # per stage), or by delegating to another staging function that does
    STAGING_SUFFIX = ("core/collectives.py",)
    STAGING_PREFIXES = ("hierarchical_", "bucketed_", "dispatch_")
    # public-but-not-traffic: Wait is a completion fence, Barrier moves one
    # scalar token (accounting it would pollute the traffic metric)
    EXEMPT = {"Wait", "Barrier"}
    # direct accounting calls at a collective's staging entry; the
    # comm.-qualified forms are the module-level staging layer's spelling
    # of the same choke-point delegation (comm IS a Communication)
    ACCOUNT_CALLS = {
        "self._account",
        "self._account_bytes",
        "comm._account",
        "comm._account_bytes",
        "_account_stages",
    }
    # the tiled executor: accounts each tile exactly once via _account_bytes
    # (core/redistribution.py), so delegating to it IS accounting
    TILED_EXECUTORS = {"execute_plan"}

    def _accounts(self, fn: ast.FunctionDef) -> bool:
        """Direct accounting: an ACCOUNT_CALLS call anywhere in ``fn``."""
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and call_name(node) in self.ACCOUNT_CALLS:
                return True
        return False

    def _staging_findings(self, ctx: LintContext) -> Iterable[Finding]:
        """Module-level staging functions of the hierarchical/bucketed
        layer: account directly or delegate to a sibling staging function
        (the lookahead pipelines delegate to their ``dispatch_*`` half)."""
        out = []
        for fn in ctx.tree.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            if not fn.name.startswith(self.STAGING_PREFIXES):
                continue
            accounted = self._accounts(fn)
            if not accounted:
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call):
                        la = last_attr(node)
                        if (
                            la
                            and la != fn.name
                            and la.startswith(self.STAGING_PREFIXES)
                        ):
                            accounted = True  # delegates to an accounted stager
                            break
            if not accounted:
                f = ctx.finding(
                    self, fn,
                    f"staging function `{fn.name}` never routes through "
                    "_account_stages / comm._account_bytes nor delegates to a "
                    "staging function that does — its collective traffic is "
                    "invisible to comm.<name>.calls/.bytes and the flight ring",
                    detail=fn.name,
                )
                if f is not None:
                    out.append(f)
        return out

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.STAGING_SUFFIX):
            return self._staging_findings(ctx)
        if not module_matches(ctx.path, self.TARGET_SUFFIX):
            return []
        out = []
        for cls in ctx.walk(ast.ClassDef):
            for fn in cls.body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                is_mpi_name = fn.name[:1].isupper()
                if not (
                    is_mpi_name
                    or fn.name.startswith("resplit")
                    or fn.name.startswith("hierarchical")
                ):
                    continue
                if fn.name in self.EXEMPT:
                    continue
                accounted = self._accounts(fn)
                if not accounted:
                    for node in ast.walk(fn):
                        if not isinstance(node, ast.Call):
                            continue
                        dn = call_name(node)
                        la = last_attr(node)
                        if la in self.TILED_EXECUTORS and fn.name.startswith("resplit"):
                            # scoped to the resplit* entries: a future public
                            # collective calling something named execute_plan
                            # must still account its own traffic
                            accounted = True  # per-tile accounting in the executor
                            break
                        if (
                            dn
                            and dn.startswith("self.")
                            and la
                            and (la[:1].isupper() or la.startswith("resplit"))
                            and la != fn.name
                            and la not in self.EXEMPT
                        ):
                            accounted = True  # derived: accounts under its primitive
                            break
                if not accounted:
                    f = ctx.finding(
                        self, fn,
                        f"public collective `{fn.name}` never calls self._account(...) "
                        "nor delegates to an accounted collective — its traffic is "
                        "invisible to comm.<name>.calls/.bytes",
                        detail=fn.name,
                    )
                    if f is not None:
                        out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT105 — raw process entropy
# -------------------------------------------------------------------- #


@register
class RawEntropyRule(Rule):
    """Randomness in library code must flow through the broadcast
    ``ht.random`` state (Threefry key from the global seed/counter): raw
    ``np.random``/stdlib ``random``/``os.urandom`` draws are per-process
    entropy, so under multi-process SPMD each rank generates DIFFERENT
    values from nominally identical code — the round-5 per-rank-seed
    divergence class."""

    code = "HT105"
    name = "raw-process-entropy"
    description = "raw np.random/process-entropy use instead of broadcast ht.random state"

    # the module that IMPLEMENTS the broadcast state is the one sanctioned
    # consumer of raw entropy
    SANCTIONED_MODULES = ("core/random.py",)

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        imports_stdlib_random = False
        for node in ctx.walk(ast.Import):
            if any(a.name == "random" for a in node.names):
                imports_stdlib_random = True
        for node in ctx.walk(ast.ImportFrom):
            if node.module == "random":
                imports_stdlib_random = True
        out = []
        for node in ctx.walk(ast.Call):
            dn = call_name(node)
            if dn is None:
                continue
            bad = None
            if dn.startswith("np.random.") or dn.startswith("numpy.random."):
                bad = dn
            elif imports_stdlib_random and dn.startswith("random."):
                bad = dn
            elif dn in ("os.urandom", "uuid.uuid4", "secrets.token_bytes"):
                bad = dn
            if bad is not None:
                f = ctx.finding(
                    self, node,
                    f"`{bad}` draws per-process entropy — under multi-process SPMD "
                    "each rank diverges; use the broadcast ht.random state "
                    "(ht.random.seed/rand/randn) instead",
                    detail=bad,
                )
                if f is not None:
                    out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT106 — DNDarray metadata mutation outside sanctioned modules
# -------------------------------------------------------------------- #


@register
class MetadataMutationRule(Rule):
    """``DNDarray``'s split/gshape/pad/array metadata is maintained by the
    class itself (constructor, ``_from_parts``, ``_renormalize``): writing
    the name-mangled privates from outside desynchronizes the logical
    metadata from the physical sharding — `split` starts lying.  Mutation
    goes through the public surface (``resplit_``, ``larray``/``_jarray``
    setters) instead."""

    code = "HT106"
    name = "metadata-mutation"
    description = "direct mutation of DNDarray metadata outside sanctioned modules"

    SANCTIONED_MODULES = ("core/dndarray.py",)
    # explicitly-mangled writes reach DNDarray's privates from anywhere
    MANGLED_ATTRS = {
        "_DNDarray__split", "_DNDarray__gshape", "_DNDarray__lshape",
        "_DNDarray__pad", "_DNDarray__array", "_DNDarray__dtype",
        "_DNDarray__unpadded",
    }
    # unmangled double-underscore writes only hit (or shadow) DNDarray
    # metadata OUTSIDE a class body — inside one, Python mangles them to the
    # ENCLOSING class's private (e.g. DCSR_matrix's own __gshape), which is
    # that class's business, not ours
    UNMANGLED_ATTRS = {
        "__split", "__gshape", "__lshape", "__pad", "__array", "__dtype", "__unpadded",
    }

    def _in_class_body(self, ctx: LintContext, node: ast.AST) -> bool:
        return any(isinstance(a, ast.ClassDef) for a in ctx.ancestors(node))

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        out = []
        for node in ctx.walk(ast.Assign, ast.AugAssign, ast.AnnAssign, ast.Delete):
            targets: List[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = list(node.targets)
            for tgt in targets:
                for sub in ast.walk(tgt):
                    if not isinstance(sub, ast.Attribute):
                        continue
                    hits = sub.attr in self.MANGLED_ATTRS or (
                        sub.attr in self.UNMANGLED_ATTRS
                        and not self._in_class_body(ctx, sub)
                    )
                    if not hits:
                        continue
                    f = ctx.finding(
                        self, node,
                        f"direct write to DNDarray metadata `{sub.attr}` outside "
                        "core/dndarray.py desynchronizes split/gshape from the "
                        "physical sharding; use resplit_/the _jarray setter",
                        detail=sub.attr,
                    )
                    if f is not None:
                        out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT107 — naked blocking collective wait bypassing the deadline watchdog
# -------------------------------------------------------------------- #


@register
class NakedBlockingWaitRule(Rule):
    """A blocking collective wait — ``Barrier()``, ``Wait(...)``,
    ``jax.block_until_ready``, ``multihost_utils.sync_global_devices`` —
    in library code, lexically outside any ``with comm.deadline(...)``
    scope, hangs forever when one peer is dead: the exact failure mode the
    elastic runtime's watchdog exists to convert into
    ``CollectiveTimeoutError``.  Call sites that are legitimately
    unbounded (process teardown, the materialization layer) are exempted
    via the suppression/baseline machinery, like every other rule.

    Lexical and intra-procedural on purpose: a deadline armed by a CALLER
    is invisible here and such sites belong in the baseline — the point of
    the rule is that NEW naked waits need a conscious decision."""

    code = "HT107"
    name = "naked-blocking-wait"
    description = "blocking collective wait outside a comm.deadline scope"

    # the wrapper itself and the guard implementation are the two places a
    # raw blocking wait is the point (shared with summaries.py, which uses
    # the same lists as propagation barriers for HT204)
    SANCTIONED_MODULES = WAIT_SANCTIONED_MODULES
    BLOCKING_ATTRS = BLOCKING_ATTRS

    def _under_deadline(self, ctx: LintContext, node: ast.AST) -> bool:
        """True when an ancestor ``with`` arms a deadline (``comm.deadline``
        / ``health.deadline`` / ``deadline(...)``) around this call."""
        for anc in ctx.ancestors(node):
            if not isinstance(anc, (ast.With, ast.AsyncWith)):
                continue
            for item in anc.items:
                expr = item.context_expr
                if isinstance(expr, ast.Call) and last_attr(expr) == "deadline":
                    return True
        return False

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        out = []
        for node in ctx.walk(ast.Call):
            la = last_attr(node)
            if la not in self.BLOCKING_ATTRS:
                continue
            if la == "Barrier" and (node.args or node.keywords):
                continue  # a foreign Barrier(...) API, not the collective fence
            if self._under_deadline(ctx, node):
                continue
            f = ctx.finding(
                self, node,
                f"blocking collective wait `{la}` outside any `comm.deadline(...)` "
                "scope hangs forever on a dead peer; arm a deadline (or baseline "
                "the site if it is legitimately unbounded)",
                detail=la,
            )
            if f is not None:
                out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT108 — collective staging bypassing the seq-stamp choke point
# -------------------------------------------------------------------- #


@register
class SeqStampBypassRule(Rule):
    """Every staged collective must pass through
    ``Communication._account_bytes`` — the ONE choke point where fault
    injection, deadline refusal, byte accounting AND the flight recorder's
    sequence stamp live.  A collective staged around it is invisible to
    ``scripts/postmortem.py``: the ranks' seq streams stay aligned while
    the wire traffic diverges, which is exactly the blind spot the flight
    recorder exists to close.  Two bypass shapes are flagged in library
    code (outside ``core/communication.py`` / ``core/redistribution.py``,
    the accounting layer itself):

    - a direct call to the tiled executor ``execute_plan`` — its sanctioned
      caller is ``Communication.resplit_tiled``, which wraps it in the
      sanitizer boundary and deadline scope; anything else staging a plan
      skips that wrapping;
    - a resharding ``jax.device_put`` of an already-device-resident array
      (the raw ``._jarray``/``._parray`` plumbing) onto comm sharding
      machinery (``comm.sharding(...)``/``NamedSharding``) — the lowered
      all-to-all never reaches the choke point.  Host→device uploads
      (``device_put`` of host data) are placement, not collective traffic,
      and are not flagged."""

    code = "HT108"
    name = "seq-stamp-bypass"
    description = "collective staged around the _account_bytes seq-stamp choke point"

    # the accounting layer itself: _account_bytes lives in communication.py;
    # execute_plan (redistribution.py) byte-accounts + stamps every tile
    # through it at the executor's own staging point; the hierarchical/
    # bucketed staging layer (collectives.py) routes every stage through
    # _account_stages → comm._account_bytes (HT104 enforces that)
    SANCTIONED_MODULES = (
        "core/communication.py",
        "core/redistribution.py",
        "core/collectives.py",
    )
    SHARDING_MARKERS = {"sharding", "NamedSharding", "PositionalSharding"}

    def _mentions_sharding(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in self.SHARDING_MARKERS:
                return True
            if isinstance(sub, ast.Name) and sub.id in self.SHARDING_MARKERS:
                return True
        return False

    def _device_resident(self, node: ast.AST) -> bool:
        """Stricter than HT101's heuristic on purpose: only the raw device
        plumbing counts.  ``jnp.asarray(host_data)`` ahead of a sharded
        ``device_put`` is an upload idiom, not a resharding."""
        return any(
            isinstance(sub, ast.Attribute)
            and sub.attr in ("_jarray", "_parray", "larray")
            for sub in ast.walk(node)
        )

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        out = []
        for node in ctx.walk(ast.Call):
            la = last_attr(node)
            if la == "execute_plan":
                f = ctx.finding(
                    self, node,
                    "direct `execute_plan` call bypasses Communication.resplit_tiled "
                    "— the staged tiles skip the sanitizer boundary and deadline "
                    "scope of the sanctioned entry; route through comm.resplit",
                    detail="execute_plan",
                )
                if f is not None:
                    out.append(f)
            elif la == "device_put" and len(node.args) >= 2:
                if self._device_resident(node.args[0]) and self._mentions_sharding(
                    node.args[1]
                ):
                    f = ctx.finding(
                        self, node,
                        "resharding `device_put` of a device-resident array stages "
                        "an all-to-all around the `_account_bytes` choke point — "
                        "invisible to the flight recorder's seq stream and the "
                        "comm.<name> byte accounting; use Communication.resplit",
                        detail="device_put",
                    )
                    if f is not None:
                        out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT109 — trace identity owned by one choke point
# -------------------------------------------------------------------- #


@register
class TraceIdentityRule(Rule):
    """Trace identity — the ``trace_id``/``span_id``/``parent_id`` triple
    that joins one job's records across ranks, processes and restarts —
    is owned by TWO choke points: ``utils/telemetry.py`` (the
    ``tracing()`` contextvar + span machinery) and
    ``parallel/scheduler.py`` (minting at job submission,
    ``job_trace_id``).  Library code manually fiddling trace identity —
    writing ``trace_id`` keys into span attrs or records, or setting the
    trace contextvar directly — forks the causal chain: its records carry
    an id no other layer (flight recorder, journal, SLO tables) agrees
    on, which is precisely the cross-artifact join the plane exists to
    guarantee.  The sanctioned idiom is ``with telemetry.tracing(...)``
    (adopt or mint) — the same one-choke-point discipline HT104/HT108
    enforce for byte accounting and seq-stamps.

    Flagged shapes in library code:

    - a subscript store of a trace-identity key
      (``attrs["trace_id"] = ...``, ``rec["parent_id"] = ...``);
    - a trace-identity keyword smuggled into the recording calls
      (``span(..., trace_id=...)``, ``record_event(..., trace_id=...)``)
      — these write it as a plain attr, bypassing the contextvar;
    - a direct ``.set(...)`` on the trace contextvar (``_TRACE.set``).

    Reading (``attrs.get("trace_id")``, ``current_trace_id()``) is free —
    the contract is about who MINTS and PROPAGATES, not who looks."""

    code = "HT109"
    name = "manual-trace-identity"
    description = "trace identity minted/written outside the tracing choke points"

    SANCTIONED_MODULES = (
        "utils/telemetry.py",   # the contextvar + span machinery itself
        "parallel/scheduler.py",  # mints per-job ids at submission
    )
    TRACE_KEYS = {"trace_id", "span_id", "parent_id"}
    RECORDING_CALLS = {"span", "record_event", "traced"}

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        out = []
        for node in ctx.walk(ast.Assign):
            for tgt in node.targets:
                if (
                    isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.slice, ast.Constant)
                    and tgt.slice.value in self.TRACE_KEYS
                ):
                    f = ctx.finding(
                        self, node,
                        f"manual write of {tgt.slice.value!r} — trace identity "
                        "must flow through telemetry.tracing() (one choke "
                        "point owns it, like HT104/HT108 own accounting and "
                        "seq-stamps); records written around it fork the "
                        "causal chain",
                        detail=str(tgt.slice.value),
                    )
                    if f is not None:
                        out.append(f)
        for node in ctx.walk(ast.Call):
            la = last_attr(node)
            if la in self.RECORDING_CALLS:
                for kw in node.keywords:
                    if kw.arg in self.TRACE_KEYS:
                        f = ctx.finding(
                            self, node,
                            f"`{la}({kw.arg}=...)` smuggles trace identity in "
                            "as a plain attribute, bypassing the tracing "
                            "contextvar — open the block with "
                            "`telemetry.tracing(trace_id=...)` instead",
                            detail=f"{la}:{kw.arg}",
                        )
                        if f is not None:
                            out.append(f)
            elif la == "set":
                dn = call_name(node)
                if dn and "_TRACE" in dn.split("."):
                    f = ctx.finding(
                        self, node,
                        "direct .set() on the trace contextvar bypasses "
                        "telemetry.tracing()'s reset discipline — a leaked "
                        "token leaves every later record mis-attributed",
                        detail=dn,
                    )
                    if f is not None:
                        out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT110 — stale suppressions (hygiene: a disable that disables nothing)
# -------------------------------------------------------------------- #


@register
class StaleSuppressionRule(Rule):
    """A ``# heatlint: disable=HTxxx`` line comment that suppresses nothing
    — the named rule is clean at that line — is itself a finding: stale
    suppressions are load-bearing-looking noise that survives refactors and
    silently swallows the NEXT real finding that lands on the line.  The
    staleness check re-runs the named rule on a suppression-blind clone of
    the file (the re-lint IS the proof), so a suppression is only ever
    called stale when removing it provably changes nothing.

    Scope, deliberately conservative:

    - only line suppressions are audited (``disable-file=`` sweeps a whole
      file and is an explicit policy statement, not per-site noise);
    - program-level codes (HT2xx/HT3xx) are skipped — their findings
      depend on the whole program, which a per-file re-lint cannot decide;
    - ``disable=HT110`` itself is skipped (self-referential);
    - a code naming NO registered rule suppresses nothing by definition
      and is flagged;
    - a rule that WOULD fire but is disabled for the directory is NOT
      flagged (the comment is future-proof against config changes)."""

    code = "HT110"
    name = "stale-suppression"
    description = "a heatlint disable comment that suppresses nothing at its line"

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        sups = getattr(ctx, "_line_suppressions", {})
        if not sups:
            return []
        from .framework import all_rules as _all_rules

        bare = LintContext(ctx.path, ctx.source, tree=ctx.tree)
        bare._line_suppressions = {}
        bare._file_suppressions = set()
        rules = {
            r.code: r
            for r in _all_rules()
            if not r.program_level and r.code != self.code
        }
        program_codes = {r.code for r in _all_rules() if r.program_level}
        fired: set = set()
        for rule in rules.values():
            for f in rule.check(bare):
                if f is not None:
                    fired.add((f.line, f.rule))
        lines_with_any = {ln for ln, _code in fired}
        # an audited line's own `disable=all` must not self-suppress the
        # audit — only an explicit HT110 code (or a file-level suppression)
        # opts a line out of the staleness check
        file_sup = {"HT110", "ALL"} & set(ctx._file_suppressions)
        out: List[Finding] = []
        for line in sorted(sups):
            if file_sup or "HT110" in sups[line]:
                continue
            for code in sorted(sups[line]):
                if code == self.code or code in program_codes:
                    continue
                if code == "ALL":
                    stale = line not in lines_with_any
                    why = "no rule fires at this line"
                elif code not in rules:
                    stale = True
                    why = f"no registered rule is named {code}"
                else:
                    stale = (line, code) not in fired
                    why = f"{code} is clean at this line"
                if not stale:
                    continue
                qual = "<module>"
                for node in ctx.walk():
                    if getattr(node, "lineno", None) == line:
                        qual = ctx.qualname(node)
                        break
                out.append(
                    Finding(
                        rule=self.code,
                        path=ctx.path,
                        line=line,
                        col=0,
                        message=(
                            f"`# heatlint: disable={code}` suppresses nothing "
                            f"({why}) — a stale suppression hides intent and "
                            "silently swallows the next real finding on this "
                            "line; delete it"
                        ),
                        qualname=qual,
                        detail=code,
                    )
                )
        return out


# -------------------------------------------------------------------- #
# HT111 — device buffers minted around the memory-ledger choke points
# -------------------------------------------------------------------- #


@register
class UnledgeredDeviceBufferRule(Rule):
    """Every long-lived device buffer should be minted through a
    memory-ledger registration choke point (``factories._finalize``,
    ``DNDarray._from_parts``, ``Communication.resplit``, checkpoint load)
    — that is what makes ``mem.live_bytes`` truthful and gives an OOM
    post-mortem its provenance.  Library code creating mesh buffers
    around those points is invisible to the ledger: the live-bytes gauge
    under-reports, and the buffer shows up in an OOM dump as nothing at
    all.  Same shape as HT108's seq-stamp rule.  Flagged in library code
    (outside the registration layer itself):

    - ``jax.make_array_from_callback(...)`` — raw global-buffer assembly;
      the sanctioned wrapper is ``communication._array_from_callback``
      (whose callers wrap the result in a registering constructor);
    - a ``device_put`` whose placement argument lexically mentions mesh
      sharding machinery (``NamedSharding``/``comm.sharding(...)``) —
      a mesh buffer minted outside the choke points.  ``device_put`` onto
      a plain *device* is not a mesh buffer and is not flagged.

    An enclosing function that itself registers the buffer with the
    ledger (``memledger.register(...)``, ``_hooks.memledger.register(...)``
    or a local ``ml = _hooks.memledger`` and ``ml.register(...)``) is a
    sanctioned registrar — the optimizer's parameter placement does
    exactly this — and is exempt."""

    code = "HT111"
    name = "unledgered-device-buffer"
    description = "device buffer minted around the memory-ledger registration choke points"

    SANCTIONED_MODULES = (
        # the registration layer: these ARE the choke points (or feed them)
        "core/communication.py",
        "core/factories.py",
        "core/dndarray.py",
        "core/io.py",
        "core/redistribution.py",
        "core/_operations.py",
        "utils/memledger.py",
    )
    SHARDING_MARKERS = {"sharding", "NamedSharding", "PositionalSharding"}
    LEDGER_NAMES = {"memledger", "_memledger", "ml", "_ml"}

    def _mentions_sharding(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in self.SHARDING_MARKERS:
                return True
            if isinstance(sub, ast.Name) and sub.id in self.SHARDING_MARKERS:
                return True
        return False

    def _function_registers(self, ctx: LintContext, node: ast.AST) -> bool:
        """True when the enclosing function lexically registers with the
        ledger (``memledger.register(...)``) — it IS a registrar, the
        HT104 "accounting counts as delegation" shape."""
        fn = ctx.enclosing_function(node)
        if fn is None:
            return False
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            if last_attr(sub) not in ("register", "reclassify"):
                continue
            dn = call_name(sub)
            if dn and any(part in self.LEDGER_NAMES for part in dn.split(".")):
                return True
        return False

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if module_matches(ctx.path, self.SANCTIONED_MODULES):
            return []
        out = []
        for node in ctx.walk(ast.Call):
            la = last_attr(node)
            if la == "make_array_from_callback":
                if self._function_registers(ctx, node):
                    continue
                f = ctx.finding(
                    self, node,
                    "raw `make_array_from_callback` mints a device buffer the "
                    "memory ledger never sees — route through a registering "
                    "constructor (factories/_from_parts) or register the "
                    "result with memledger.register(...)",
                    detail="make_array_from_callback",
                )
                if f is not None:
                    out.append(f)
            elif la == "device_put":
                # placement target: second positional OR the device= kwarg
                # (both spellings mint the buffer identically)
                target = node.args[1] if len(node.args) >= 2 else next(
                    (kw.value for kw in node.keywords if kw.arg == "device"),
                    None,
                )
                if target is None or not self._mentions_sharding(target):
                    continue  # plain device placement, not a mesh buffer
                if self._function_registers(ctx, node):
                    continue
                f = ctx.finding(
                    self, node,
                    "`device_put` onto mesh sharding machinery mints a buffer "
                    "around the ledger's registration choke points — "
                    "mem.live_bytes under-reports and an OOM dump cannot name "
                    "it; use the registering constructors or register the "
                    "result with memledger.register(...)",
                    detail="device_put",
                )
                if f is not None:
                    out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT112 — federation code must inherit the journal-before-mutation path
# -------------------------------------------------------------------- #


@register
class FederationJournaledMutationRule(Rule):
    """The scheduler's crash-durability rests on ONE ordering: the journal
    append happens first, and a failed append propagates with nothing
    mutated (``submit``/``_shed``/``_finish``/``drain`` all keep it).  The
    federation layer (``parallel/federation.py``) sits above N schedulers
    and inherits that contract — a federation mutation the federation
    journal never saw is a phantom the zero-loss replay cannot requeue.

    Flagged, in federation modules only:

    - **reaching into a scheduler's privates** — mutating another
      object's ``_queue`` / ``_jobs`` / ``_done_ids`` /
      ``_tenant_inflight`` (``sched._queue.append(job)``).  Those belong
      to the scheduler; its journaled entry points (``submit`` /
      ``recover`` / ``drain``) are the only sanctioned doors.  Flagged
      unconditionally.
    - **unjournaled lifecycle writes** — mutating the federation's OWN
      job containers, or writing ``<obj>.state`` on a job/world, from a
      function that never appends to a journal.  A function whose body
      lexically contains a ``<...>journal<...>.append(...)`` call is a
      journaled path and exempt (``__init__`` constructing fresh empty
      state is too — there is nothing to journal yet)."""

    code = "HT112"
    name = "federation-unjournaled-mutation"
    description = "scheduler/job state mutated from federation code outside the journaled append path"

    FEDERATION_MODULES = ("parallel/federation.py",)
    PRIVATE_FIELDS = {"_queue", "_jobs", "_done_ids", "_tenant_inflight"}
    MUTATORS = {"append", "pop", "clear", "add", "remove", "discard",
                "update", "extend", "insert", "sort", "setdefault"}
    STATE_ATTRS = {"state"}

    def _function_journals(self, ctx: LintContext, node: ast.AST) -> bool:
        """True when the enclosing function is a journaled path: its body
        lexically appends to a journal (``self.journal.append(...)``), or
        it is ``__init__`` building fresh empty state."""
        fn = ctx.enclosing_function(node)
        if fn is None:
            return False
        if fn.name == "__init__":
            return True
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call) or last_attr(sub) != "append":
                continue
            dn = call_name(sub)
            if dn and any("journal" in part.lower() for part in dn.split(".")):
                return True
        return False

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        if not module_matches(ctx.path, self.FEDERATION_MODULES):
            return []
        out = []
        # mutating METHOD calls on job-state containers
        for node in ctx.walk(ast.Call):
            func = node.func
            if not isinstance(func, ast.Attribute) or func.attr not in self.MUTATORS:
                continue
            recv = func.value
            if not isinstance(recv, ast.Attribute) or recv.attr not in self.PRIVATE_FIELDS:
                continue
            owner_is_self = (
                isinstance(recv.value, ast.Name) and recv.value.id == "self"
            )
            if not owner_is_self:
                f = ctx.finding(
                    self, node,
                    f"federation code mutates another object's scheduler-"
                    f"private `{recv.attr}` directly — the scheduler's "
                    "journaled entry points (submit/recover/drain) are the "
                    "only doors that keep the journal-before-mutation "
                    "contract",
                    detail=f"{recv.attr}.{func.attr}",
                )
                if f is not None:
                    out.append(f)
            elif not self._function_journals(ctx, node):
                f = ctx.finding(
                    self, node,
                    f"federation state `self.{recv.attr}` mutated in a "
                    "function that never appends to a journal — a crash "
                    "here leaves a job the zero-loss replay cannot see; "
                    "journal first, mutate second",
                    detail=f"self.{recv.attr}.{func.attr}",
                )
                if f is not None:
                    out.append(f)
        # ASSIGNMENT-form mutations: obj.state = ..., self._jobs[id] = ...
        for node in ctx.walk(ast.Assign, ast.AugAssign, ast.AnnAssign):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                # lifecycle write on a non-self object: job.state / w.state
                if (
                    isinstance(t, ast.Attribute)
                    and t.attr in self.STATE_ATTRS
                    and not (isinstance(t.value, ast.Name) and t.value.id == "self")
                    and not self._function_journals(ctx, node)
                ):
                    f = ctx.finding(
                        self, node,
                        "lifecycle state written outside a journaled path — "
                        "the transition exists only in memory and dies with "
                        "the process; append the record first",
                        detail=f"{t.attr} =",
                    )
                    if f is not None:
                        out.append(f)
                    continue
                # container writes: <obj>._jobs[...] = / <obj>._queue = ...
                base = t
                if isinstance(base, ast.Subscript):
                    base = base.value
                if not isinstance(base, ast.Attribute) or base.attr not in self.PRIVATE_FIELDS:
                    continue
                owner_is_self = (
                    isinstance(base.value, ast.Name) and base.value.id == "self"
                )
                if not owner_is_self:
                    f = ctx.finding(
                        self, node,
                        f"federation code writes another object's scheduler-"
                        f"private `{base.attr}` — use the scheduler's "
                        "journaled entry points",
                        detail=f"{base.attr} =",
                    )
                    if f is not None:
                        out.append(f)
                elif not self._function_journals(ctx, node):
                    f = ctx.finding(
                        self, node,
                        f"federation state `self.{base.attr}` written in a "
                        "function that never appends to a journal — journal "
                        "first, mutate second",
                        detail=f"self.{base.attr} =",
                    )
                    if f is not None:
                        out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT113 — fault-site literals must be catalog members
# -------------------------------------------------------------------- #


@register
class UnknownFaultSiteRule(Rule):
    """Every fault site the runtime can arm or fire is registered in
    ``faults.catalog()`` — the chaos engine enumerates the fault space
    from that registry.  A string literal at an arming/firing call site
    (``faults.fire("io.wrte")``, ``faults.inject("io.wrte", fail=1)``)
    that is NOT a catalog member arms or fires *nothing*: the injection
    silently tests a healthy world, the trip counter never moves, and the
    chaos campaign's coverage claim quietly becomes a lie.  The runtime
    twin (``schedule.validate_schedule`` and the dryrun launcher's
    arming-time check) catches env-borne typos; this rule catches the
    source-borne ones before anything runs.

    Only literal first arguments of ``fire``/``inject``/``trip_count``
    and literal ``FaultSpec(...)`` sites are checked — a variable site is
    someone's abstraction and stays out of lexical scope (the
    ``call_with_retries`` site parameter names retry *counters*, not
    armed fault sites, so it is exempt by design: the chaos harness
    deliberately uses pseudo-sites like ``chaos.submit`` there)."""

    code = "HT113"
    name = "unknown-fault-site"
    description = "fault-site string literal not registered in faults.catalog()"

    SITE_ARG0 = {"fire", "inject", "trip_count", "FaultSpec"}

    _catalog_sites: Optional[frozenset] = None

    @classmethod
    def _sites(cls) -> frozenset:
        """The catalog, loaded once per process from faults.py by path —
        the analysis package is loaded standalone (scripts/heatlint.py
        synthesizes it), so a relative package import cannot reach
        utils.faults; the path load shares heatlint's no-jax guarantee
        because faults.py is stdlib-only."""
        if cls._catalog_sites is None:
            import importlib.util as _ilu
            import os as _os
            import sys as _sys

            name = "_heatlint_faults"
            if name in _sys.modules:
                mod = _sys.modules[name]
            else:
                path = _os.path.join(
                    _os.path.dirname(_os.path.abspath(__file__)),
                    "..", "utils", "faults.py",
                )
                spec = _ilu.spec_from_file_location(name, _os.path.normpath(path))
                mod = _ilu.module_from_spec(spec)
                _sys.modules[name] = mod
                spec.loader.exec_module(mod)
            cls._catalog_sites = frozenset(mod.catalog_sites())
        return cls._catalog_sites

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        out = []
        sites = None
        for node in ctx.walk(ast.Call):
            fname = last_attr(node) or call_name(node)
            if fname not in self.SITE_ARG0 or not node.args:
                continue
            arg = node.args[0]
            if not isinstance(arg, ast.Constant) or not isinstance(
                arg.value, str
            ):
                continue  # a variable site is out of lexical scope
            if sites is None:
                sites = self._sites()
            if arg.value in sites:
                continue
            f = ctx.finding(
                self, node,
                f"fault site {arg.value!r} is not in faults.catalog() — "
                f"this {fname}() arms/fires nothing and the injection "
                "silently tests a healthy world; register the site or fix "
                "the typo",
                detail=f"{fname}({arg.value!r})",
            )
            if f is not None:
                out.append(f)
        return out


# -------------------------------------------------------------------- #
# HT2xx — the interprocedural family (callgraph + summaries engine)
# -------------------------------------------------------------------- #


def _trace_dicts(chain) -> List[dict]:
    return [{"path": p, "qualname": q, "line": ln} for p, q, ln in chain]


@register
class StaticDesyncRule(Rule):
    """Static desync: the collective footprint differs across the arms of a
    rank-dependent branch *anywhere in the transitive call chain* — the
    lint-time counterpart of postmortem's ``desync`` verdict (and of the
    chaos-CI ``MPDRYRUN_DESYNC_RANK`` worker, whose rank-conditional extra
    collective is exactly this shape one helper deep).

    Lexical differences (a collective called directly in one arm) are
    HT102's finding and are NOT re-reported here; HT201 fires only when
    the divergence is call-borne (the witness collective sits >= 1 hop
    down), which is precisely what HT102 provably misses.  Arms whose
    comparison crosses a poisoning unresolved call (getattr dispatch,
    callables passed as values) yield an ``info`` finding — "cannot prove
    SPMD-uniform" — never a gating false positive."""

    code = "HT201"
    name = "static-desync"
    description = "rank-conditional branch whose arms stage different collective footprints"
    program_level = True

    def check_program(self, program: Program) -> Iterable[Finding]:
        out: List[Finding] = []
        for key in sorted(program.effects):
            eff = program.effects[key]
            path, qual = key
            for atom in eff["rank_branches"]:
                _tag, marker, line, arm_a, arm_b, kind = atom
                if program.is_suppressed(self.code, path, line):
                    continue
                na = program.norm_arm(key, arm_a)
                nb = program.norm_arm(key, arm_b)
                sa, sb = _strip(na), _strip(nb)
                if sa == sb:
                    continue
                i = 0
                while i < min(len(sa), len(sb)) and sa[i] == sb[i]:
                    i += 1
                candidates = [n for n in (na[i:i + 1] + nb[i:i + 1])]
                ambiguous = _has_ambiguity(na) or _has_ambiguity(nb)
                # lexical collective NAME sets per arm — exactly what set-
                # based HT102 compares, so the hand-off below is precise
                lex_a = {at[1] for at in _iter_atoms(arm_a) if at[0] == "coll"}
                lex_b = {at[1] for at in _iter_atoms(arm_b) if at[0] == "coll"}
                witness = next(
                    (c for c in candidates if c.kind == "coll" and len(c.chain) > 1),
                    None,
                )
                order_mismatch = False
                if witness is None:
                    depth0 = [c for c in candidates if c.kind == "coll"]
                    # HT102 fires ONLY when the name is lexically present in
                    # exactly one arm; a depth-0 ORDER difference (same name
                    # set, different sequence) is invisible to it and stays
                    # ours to report
                    if depth0 and not ambiguous:
                        w = depth0[0]
                        if (w.data in lex_a) != (w.data in lex_b):
                            continue  # one-arm-only lexical: HT102's finding
                        witness = w
                        order_mismatch = True
                    elif not ambiguous:
                        # remaining structural difference (loop/either of
                        # resolved parts): report with the branch itself
                        witness = candidates[0] if candidates else None
                elif witness.data in lex_a and witness.data in lex_b:
                    order_mismatch = True
                if witness is None or witness.kind != "coll":
                    severity = "info"
                    detail = f"unproven@{marker}"
                    message = (
                        f"cannot prove the collective footprint is identical across "
                        f"the arms of this branch on `{marker}`: the comparison "
                        "crosses an unresolved or data-conditional call — verify "
                        "manually that every rank stages the same collectives"
                        if ambiguous
                        else f"the arms of this branch on `{marker}` stage different "
                        "collective structure (loop/branch shape differs across "
                        "ranks) — ranks taking different arms will desynchronize"
                    )
                    if not ambiguous:
                        severity = "error"
                        detail = f"structure@{marker}"
                else:
                    coll = witness.data
                    severity = "info" if ambiguous else "error"
                    hops = " -> ".join(f"{q2}" for _p2, q2, _l2 in witness.chain)
                    if order_mismatch:
                        message = (
                            f"collective `{coll}` is staged in a DIFFERENT ORDER "
                            f"across the arms of a branch conditioned on `{marker}` "
                            f"(first divergence {len(witness.chain) - 1} call(s) deep, "
                            f"{hops}): ranks taking different arms post the same "
                            "collectives in different sequence and desynchronize — "
                            "the static counterpart of a postmortem `desync` verdict"
                        )
                    else:
                        message = (
                            f"collective `{coll}` is staged on only one arm of a branch "
                            f"conditioned on `{marker}`, {len(witness.chain) - 1} call(s) "
                            f"deep ({hops}): ranks that skip the branch never post it — "
                            "the static counterpart of a postmortem `desync` verdict"
                        )
                    detail = f"{coll}@{marker}"
                f = Finding(
                    rule=self.code,
                    path=path,
                    line=line,
                    col=0,
                    message=message,
                    qualname=qual,
                    detail=detail,
                    severity=severity,
                    trace=_trace_dicts(witness.chain if witness is not None else ((path, qual, line),)),
                )
                out.append(f)
        return out


@register
class TransitiveHostSyncRule(Rule):
    """Transitive host sync: a public API function whose call chain reaches
    a blocking device->host read that lexical HT101 cannot pin on the entry
    — either a naked sink hidden in a private helper (HT101 flags the
    helper's line; this rule names the public surfaces it poisons), a
    suppressed sink (downgraded to ``info``: a human vouched for the site,
    not for every caller), or a ``float()``/``int()``/``np.asarray`` cast
    of a call whose device-ness is only visible interprocedurally (the
    callee returns a device value — HT101's lexical heuristic provably
    misses these)."""

    code = "HT202"
    name = "transitive-host-sync"
    description = "public API whose call chain reaches a host sync invisible to lexical HT101"
    program_level = True

    def check_program(self, program: Program) -> Iterable[Finding]:
        out: List[Finding] = []
        for rep in sorted(
            program.sync_reports,
            key=lambda r: (r.entry[0], r.entry[1], r.entry_line, r.detail),
        ):
            path, qual = rep.entry
            if program.is_suppressed(self.code, path, rep.entry_line):
                continue
            sink_path, sink_qual, sink_line = rep.chain[-1]
            if rep.vis == "cast":
                message = (
                    f"`{rep.detail.split('-')[0]}()` of `{sink_qual}(...)` is a hidden "
                    f"device->host sync: `{sink_qual}` returns a device value "
                    f"({sink_path}:{sink_line}), so this cast blocks like `.item()` — "
                    "route through host_fetch/numpy() or keep the value on device"
                )
            else:
                suffix = (
                    " (the sink is suppressed at its site; suppressions vouch for "
                    "the helper, not for every public caller)"
                    if rep.vis == "suppressed"
                    else ""
                )
                message = (
                    f"public API `{qual}` reaches a naked host sync `{rep.detail}` "
                    f"in `{sink_qual}` ({sink_path}:{sink_line}), "
                    f"{len(rep.chain) - 1} call(s) deep: callers expecting async "
                    f"dispatch stall on the device stream{suffix}"
                )
            out.append(
                Finding(
                    rule=self.code,
                    path=path,
                    line=rep.entry_line,
                    col=0,
                    message=message,
                    qualname=qual,
                    detail=f"{rep.detail}@{sink_qual}",
                    severity="info" if rep.vis == "suppressed" else "error",
                    trace=_trace_dicts(rep.chain),
                )
            )
        return out


@register
class InterproceduralUseAfterDonateRule(Rule):
    """Interprocedural use-after-donate: a name is read after being passed
    to a call that donates that parameter *inside the callee* (directly or
    transitively).  HT103 only sees ``donate=True`` kwargs and locally-
    jitted ``donate_argnums`` — a helper that donates its argument is
    invisible to it, and the caller's later read returns garbage or raises
    only under certain layouts.  Call sites HT103 already covers (lexical
    donate kwarg, the caller's own jit aliases) are excluded."""

    code = "HT203"
    name = "interprocedural-use-after-donate"
    description = "name read after a call that donates it inside the callee"
    program_level = True

    def check_program(self, program: Program) -> Iterable[Finding]:
        out: List[Finding] = []
        for key in sorted(program.effects):
            eff = program.effects[key]
            path, qual = key
            ctx = program.contexts.get(path)
            caller_facts = program.facts[path].functions.get(qual)
            if ctx is None or caller_facts is None:
                continue
            events = []
            for cid, (desc_json, line, _dl) in enumerate(eff["calls"]):
                if desc_json.get("donate_kwarg"):
                    continue  # lexical donation: HT103's finding
                dotted = desc_json.get("dotted") or ""
                alias = caller_facts.local_aliases.get(dotted)
                if alias is not None and alias[1]:
                    # caller's own jit alias WITH donate_argnums: HT103's
                    # finding.  A plain rename (`h = _helper`) carries no
                    # lexical donation — HT103 is blind to it, so it is ours.
                    continue
                r = program.resolved[key][cid]
                if r.kind != "resolved":
                    continue
                callee_don = program.donates.get(r.target, {})
                positions = set(callee_don) | set(r.donates_override or ())
                args = desc_json.get("args", [])
                for p in sorted(positions):
                    if p < len(args) and args[p]:
                        events.append(
                            (line, desc_json.get("col", 0), args[p], r.target,
                             callee_don.get(p))
                        )
            if not events:
                continue
            fn_node = next(
                (
                    n
                    for n in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef)
                    if ctx.qualname(n) == qual
                ),
                None,
            )
            if fn_node is None:
                continue
            call_index = {
                (c.lineno, c.col_offset): c
                for c in ast.walk(fn_node)
                if isinstance(c, ast.Call)
            }
            for line, col, name, target, dinfo in events:
                call = call_index.get((line, col))
                if call is None:
                    continue
                out.extend(
                    self._uses_after(program, ctx, fn_node, call, name, key, target, dinfo)
                )
        return out

    def _uses_after(self, program, ctx, fn, call, name, key, target, dinfo):
        path, qual = key
        donate_key = (call.end_lineno or call.lineno, call.end_col_offset or 0)
        stmt = call
        for anc in [call] + ctx.ancestors(call):
            if isinstance(anc, ast.stmt):
                stmt = anc
                break
        if isinstance(stmt, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == name for t in stmt.targets
        ):
            return  # x = helper(x): the donation rebinds, taint never lands
        if isinstance(stmt, (ast.Return, ast.Raise)):
            return  # control leaves the frame at the donating call
        rebound_at = None
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id == name and isinstance(
                node.ctx, ast.Store
            ):
                at = (node.lineno, node.col_offset)
                if at > donate_key and (rebound_at is None or at < rebound_at):
                    rebound_at = at
        chain = ((path, qual, call.lineno),) + (dinfo.chain if dinfo else ())
        callee_qual = target[1]
        for node in ast.walk(fn):
            if not (
                isinstance(node, ast.Name)
                and node.id == name
                and isinstance(node.ctx, ast.Load)
            ):
                continue
            at = (node.lineno, node.col_offset)
            if at <= donate_key:
                continue
            if rebound_at is not None and at > rebound_at:
                continue
            if branch_exclusive(ctx, call, node):
                continue
            if program.is_suppressed(self.code, path, node.lineno):
                continue
            yield Finding(
                rule=self.code,
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message=(
                    f"`{name}` is read after `{callee_qual}(...)` at line "
                    f"{call.lineno} donated it inside the callee "
                    f"({dinfo.chain[-1][0]}:{dinfo.chain[-1][2]} donates)"
                    if dinfo
                    else f"`{name}` is read after `{callee_qual}(...)` at line "
                    f"{call.lineno} donated it inside the callee"
                ),
                qualname=ctx.qualname(node),
                detail=name,
                severity="error",
                trace=_trace_dicts(chain),
            )


@register
class TransitiveUndeadlinedBlockingRule(Rule):
    """Transitively undeadlined blocking: a public library entry whose call
    chain reaches a naked blocking wait (``Barrier()``, ``Wait``,
    ``block_until_ready``, ``sync_global_devices``) with NO
    ``comm.deadline(...)`` scope on any hop of the path — the lint-time
    counterpart of a ``health.deadline.trips`` increment that never fires
    because nothing armed the watchdog.  A deadline anywhere on the path
    (around the wait itself, or around any call on the chain) satisfies
    the rule; a wait suppressed at its site propagates as ``info``."""

    code = "HT204"
    name = "transitive-undeadlined-blocking"
    description = "public entry reaching a blocking wait with no deadline on any path"
    program_level = True

    def check_program(self, program: Program) -> Iterable[Finding]:
        out: List[Finding] = []
        for rep in sorted(
            program.wait_reports,
            key=lambda r: (r.entry[0], r.entry[1], r.entry_line, r.detail),
        ):
            path, qual = rep.entry
            if program.is_suppressed(self.code, path, rep.entry_line):
                continue
            sink_path, sink_qual, sink_line = rep.chain[-1]
            suffix = (
                " (suppressed at its site; the suppression vouches for the "
                "helper, not for every public caller)"
                if rep.vis == "suppressed"
                else ""
            )
            out.append(
                Finding(
                    rule=self.code,
                    path=path,
                    line=rep.entry_line,
                    col=0,
                    message=(
                        f"public entry `{qual}` reaches blocking wait `{rep.detail}` "
                        f"in `{sink_qual}` ({sink_path}:{sink_line}) with no "
                        f"comm.deadline scope on any path — a dead peer hangs this "
                        f"API forever; arm `with comm.deadline(...)` around the call "
                        f"or at the wait site{suffix}"
                    ),
                    qualname=qual,
                    detail=f"{rep.detail}@{sink_qual}",
                    severity="info" if rep.vis == "suppressed" else "error",
                    trace=_trace_dicts(rep.chain),
                )
            )
        return out


# -------------------------------------------------------------------- #
# HT3xx — the abstract-interpretation family (absint rank-taint + metadata)
# -------------------------------------------------------------------- #


@register
class RankTaintedCollectiveFlowRule(Rule):
    """Rank-tainted dataflow reaching collective control or arguments.

    HT102 matches ``comm.rank`` lexically in a branch test; HT201 compares
    footprints across such branches through calls.  Both are blind to the
    *value* flowing: ``n = comm.rank; if n == 0: _stage()`` — or a helper
    whose loop bound is a rank-derived argument — stages a different
    collective count per rank and desynchronizes the world exactly like
    the lexical shapes.  The absint taint lattice proves the derivation
    and this rule fires on three sink classes:

    - a branch/while whose test is rank-tainted and whose arms stage
      different collective traffic (lexically or via resolved calls);
    - a for-loop whose bound is rank-tainted and whose body stages
      collectives — a per-rank *count* divergence;
    - a rank-tainted value passed directly as a collective argument
      (``Bcast(..., root=comm.rank)``: every rank nominates itself).

    Interprocedural: a function whose *parameter* reaches such a sink
    becomes a summary; call sites passing a provably rank-derived argument
    fire here with the full chain.  Only provable rank derivation gates —
    a value of unknown origin never fires (the honesty policy, value
    edition)."""

    code = "HT301"
    name = "rank-tainted-collective-flow"
    description = "rank-derived value controls or feeds a collective (dataflow SPMD divergence)"
    program_level = True

    _KIND_TEXT = {
        "if": "a branch",
        "while": "a while-loop",
        "for": "a for-loop bound",
    }

    def check_program(self, program) -> Iterable[Finding]:
        view = program.absint
        out: List[Finding] = []
        seen: Set[Tuple] = set()

        def emit(path, qual, line, message, detail, trace):
            if program.is_suppressed(self.code, path, line):
                return
            dk = (path, line, detail)
            if dk in seen:
                return
            seen.add(dk)
            out.append(
                Finding(
                    rule=self.code,
                    path=path,
                    line=line,
                    col=0,
                    message=message,
                    qualname=qual,
                    detail=detail,
                    severity="error",
                    trace=trace,
                )
            )

        for key in sorted(view.functions):
            rec = view.functions[key]
            path, qual = key
            # direct sinks: the shared enumeration (absint.sink_candidates)
            # also feeds the param-sink summaries, so the two stay in step
            for cand in view.sink_candidates(key):
                v = view.resolve_tokens(key, cand["tokens"])
                if not v.rank:
                    continue
                witness = cand["colls"][0]
                if cand["kind"] == "coll-arg":
                    message = (
                        f"collective `{witness}` receives a rank-derived value "
                        f"({cand['role']}): each rank passes a DIFFERENT value "
                        "where the collective contract requires agreement "
                        "(root/count/shape arguments must be rank-uniform)"
                    )
                    detail = f"{witness}:{cand['role']}"
                else:
                    message = (
                        f"{self._KIND_TEXT[cand['kind']]} controlled by a "
                        f"rank-derived value stages collective `{witness}`: "
                        "ranks compute different values from process identity, "
                        "take different paths, and post different collective "
                        "sequences — the dataflow shape lexical HT102/HT201 "
                        "cannot see"
                    )
                    detail = f"{witness}@{cand['kind']}"
                emit(
                    path, qual, cand["line"], message, detail,
                    trace=[{"path": path, "qualname": qual, "line": cand["line"]}],
                )
            # interprocedural: rank-derived argument into a param sink
            for cid, call in enumerate(rec["calls"]):
                r = view.resolved[key][cid]
                if r.kind != "resolved" or r.target == key:
                    continue
                callee_sinks = view.param_sinks.get(r.target)
                if not callee_sinks:
                    continue
                for p in sorted(callee_sinks):
                    tokens = view._call_arg_tokens(call, r.target, p)
                    if not tokens:
                        continue
                    v = view.resolve_tokens(key, tokens)
                    if not v.rank:
                        continue
                    for s in callee_sinks[p]:
                        witness = s["colls"][0] if s["colls"] else "collective"
                        chain = [[path, qual, call["line"]]] + list(s["chain"])
                        sink_path, sink_qual, sink_line = chain[-1]
                        emit(
                            path, qual, call["line"],
                            f"rank-derived argument flows into `{r.target[1]}` "
                            f"where it {'bounds' if s['kind'] == 'for' else 'controls'} "
                            f"collective `{witness}` ({sink_path}:{sink_line}) — "
                            f"{len(chain) - 1} call(s) deep: ranks passing different "
                            "values stage different collective sequences",
                            detail=f"{witness}@{r.target[1]}",
                            trace=[
                                {"path": hp, "qualname": hq, "line": hl}
                                for hp, hq, hl in chain
                            ],
                        )
        out.sort(key=lambda f: (f.path, f.line, f.detail))
        return out


@register
class SplitMismatchRule(Rule):
    """Split mismatch at a binary-op/matmul site, provable from propagated
    metadata.  The dispatch tail reconciles mismatched splits with an
    implicit ``resplit`` — a full redistribution of one operand, warned
    about at runtime, communication-heavy, and invisible at the call site.
    When the abstract metadata (tracked through factories, ``resplit``,
    wrapper returns and binary-op promotion) proves both operands carry
    *different concrete* split axes after broadcast alignment, the
    redistribution (or, for paths that validate instead, the dispatch
    ValueError) is a static certainty, not a possibility.  Operands whose
    split is unknown or replicated never fire."""

    code = "HT302"
    name = "split-mismatch-binop"
    description = "binary op on operands with provably different split axes"
    program_level = True

    def check_program(self, program) -> Iterable[Finding]:
        view = program.absint
        out: List[Finding] = []
        for key in sorted(view.functions):
            rec = view.functions[key]
            path, qual = key
            for site in rec["binop_sites"]:
                if site["op"] in ("MatMult", "matmul", "dot"):
                    # matmul supports every split pairing by design (the
                    # reference's eight-case table in linalg/basics.py) —
                    # mixed splits are a routing decision there, not the
                    # elementwise implicit-resplit hazard
                    continue
                lm = view.concrete_meta(key, site["left"])
                rm = view.concrete_meta(key, site["right"])
                if lm is None or rm is None:
                    continue
                s1, s2 = lm["split"], rm["split"]
                if not (isinstance(s1, int) and not isinstance(s1, bool)):
                    continue
                if not (isinstance(s2, int) and not isinstance(s2, bool)):
                    continue
                if lm["dims"] is None or rm["dims"] is None:
                    # unknown RANK: broadcast alignment is undefined, and a
                    # guessed ndim manufactures false mismatches — the
                    # honesty policy applies to shapes too
                    continue
                d1, d2 = len(lm["dims"]), len(rm["dims"])
                out_ndim = max(d1, d2)
                al1, al2 = s1 + (out_ndim - d1), s2 + (out_ndim - d2)
                if al1 == al2:
                    continue
                if program.is_suppressed(self.code, path, site["line"]):
                    continue
                out.append(
                    Finding(
                        rule=self.code,
                        path=path,
                        line=site["line"],
                        col=0,
                        message=(
                            f"`{site['op']}` on operands with provably different "
                            f"split axes ({s1} vs {s2}): the dispatch tail stages "
                            "an implicit full redistribution of one operand "
                            "(communication-heavy, warned only at runtime) — "
                            "resplit explicitly at a chosen boundary instead"
                        ),
                        qualname=qual,
                        detail=f"{site['op']}:split{s1}x{s2}",
                        severity="error",
                        trace=[{"path": path, "qualname": qual, "line": site["line"]}],
                    )
                )
        out.sort(key=lambda f: (f.path, f.line, f.detail))
        return out


@register
class CollectivePayloadAsymmetryRule(Rule):
    """Collective payload asymmetry: the staged payload's abstract
    ``gshape`` or ``dtype`` depends on rank-tainted data.  Lockstep SPMD
    requires every rank's staged fingerprint ``(seq, op, gshape, dtype)``
    to agree — the exact stream the flight recorder stamps at
    ``_account_bytes`` and postmortem compares across ranks.  A payload
    built as ``ht.zeros((comm.rank + 1, 4))`` (or with a rank-selected
    dtype) makes the mismatch a static certainty: byte counts differ on
    the wire and the collective corrupts or deadlocks.  Shapes of unknown
    provenance never fire — only provable rank derivation gates."""

    code = "HT303"
    name = "collective-payload-asymmetry"
    description = "collective payload whose gshape/dtype provably depends on rank"
    program_level = True

    def check_program(self, program) -> Iterable[Finding]:
        view = program.absint
        out: List[Finding] = []
        for key in sorted(view.functions):
            rec = view.functions[key]
            path, qual = key
            for site in rec["coll_sites"]:
                roles = [(f"arg{i}", m) for i, m in enumerate(site["arg_metas"])] + [
                    (f"kw:{k}", site["kw_metas"][k]) for k in sorted(site["kw_metas"])
                ]
                for role, meta in roles:
                    cm = view.concrete_meta(key, meta)
                    if cm is None:
                        continue
                    aspects = []
                    if cm["shape_rank"]:
                        aspects.append("gshape")
                    if cm["dtype_rank"]:
                        aspects.append("dtype")
                    if not aspects:
                        continue
                    if program.is_suppressed(self.code, path, site["line"]):
                        continue
                    what = "/".join(aspects)
                    out.append(
                        Finding(
                            rule=self.code,
                            path=path,
                            line=site["line"],
                            col=0,
                            message=(
                                f"payload of collective `{site['name']}` has a "
                                f"rank-derived {what}: ranks stage different "
                                "fingerprints (seq, op, gshape, dtype) for the "
                                "same sequence number — the exact mismatch the "
                                "flight recorder convicts post-hoc; make the "
                                "payload metadata rank-uniform"
                            ),
                            qualname=qual,
                            detail=f"{site['name']}:{what}",
                            severity="error",
                            trace=[
                                {"path": path, "qualname": qual, "line": site["line"]}
                            ],
                        )
                    )
        out.sort(key=lambda f: (f.path, f.line, f.detail))
        return out


@register
class DonationSizeMismatchRule(Rule):
    """Donation-size mismatch: a donated buffer's abstract metadata differs
    from the consumer it must alias with.  XLA donation is an aliasing
    contract — same shape, same dtype, or the alias silently fails (extra
    copy) and the donated source is deleted anyway, so a later read raises
    the donated-buffer RuntimeError while the intended in-place reuse never
    happened.  Flagged when a call donates a buffer (lexical
    ``donate=True``, a jit alias's ``donate_argnums``, or a callee that
    donates the position — the HT103/HT203 vocabulary) AND an ``out=``
    destination is present at the same site whose abstract
    ``(gshape, dtype)`` provably differs from the donated buffer's."""

    code = "HT304"
    name = "donation-size-mismatch"
    description = "donated buffer's abstract shape/dtype differs from its consumer's"
    program_level = True

    def check_program(self, program) -> Iterable[Finding]:
        view = program.absint
        out: List[Finding] = []
        for key in sorted(view.functions):
            rec = view.functions[key]
            path, qual = key
            for cid, call in enumerate(rec["calls"]):
                donated = set()
                if call["desc"].get("donate_kwarg"):
                    donated.add(0)
                r = view.resolved[key][cid]
                if r.kind == "resolved":
                    donated |= set(r.donates_override or ())
                    donated |= set(program.donates.get(r.target, {}))
                if not donated:
                    continue
                om = view.concrete_meta(key, call["kw_metas"].get("out"))
                if om is None:
                    continue
                for p in sorted(donated):
                    if p >= len(call["arg_metas"]):
                        continue
                    dm = view.concrete_meta(key, call["arg_metas"][p])
                    if dm is None:
                        continue
                    mismatches = []
                    dd, od = dm["dims"], om["dims"]
                    if (
                        dd is not None
                        and od is not None
                        and all(isinstance(x, int) and not isinstance(x, bool) for x in dd)
                        and all(isinstance(x, int) and not isinstance(x, bool) for x in od)
                        and dd != od
                    ):
                        mismatches.append(f"shape {tuple(dd)} vs {tuple(od)}")
                    if (
                        dm["dtype"] not in (None, "?")
                        and om["dtype"] not in (None, "?")
                        and dm["dtype"] != om["dtype"]
                    ):
                        mismatches.append(f"dtype {dm['dtype']} vs {om['dtype']}")
                    if not mismatches:
                        continue
                    if program.is_suppressed(self.code, path, call["line"]):
                        continue
                    callee = (
                        call["desc"].get("dotted")
                        or call["desc"].get("attr")
                        or "<call>"
                    )
                    out.append(
                        Finding(
                            rule=self.code,
                            path=path,
                            line=call["line"],
                            col=0,
                            message=(
                                f"buffer donated to `{callee}` cannot alias its "
                                f"consumer: {'; '.join(mismatches)} — XLA falls "
                                "back to a copy AND deletes the donated source, "
                                "so the in-place reuse never happens and any "
                                "later read raises the donated-buffer "
                                "RuntimeError"
                            ),
                            qualname=qual,
                            detail=f"{callee}:arg{p}",
                            severity="error",
                            trace=[
                                {"path": path, "qualname": qual, "line": call["line"]}
                            ],
                        )
                    )
        out.sort(key=lambda f: (f.path, f.line, f.detail))
        return out
