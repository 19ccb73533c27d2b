"""Device time by the layer a program named, through jax's transformations.

``trace.in_scope`` finds ``ht.<layer>`` where it is a whole component of an
operation's scope.  Under ``jax.value_and_grad`` jax wraps the outermost
component of the name stack at the point of each transformation: a scope
opened directly in the differentiated function reads ``jvp(ht.<layer>)`` in the
forward pass and ``transpose(jvp(ht.<layer>))`` in the backward pass, and only
a scope inside another scope or inside a jitted call (``jvp(jit(run))/
ht.<layer>``) stays bare.  A training step is one such program, so its layers
are read here: a component names a layer if it is the layer's name inside any
nesting of ``word(...)`` wrappers, and the forward pass, what ``jax.checkpoint``
recomputes (``.../checkpoint/rematted_computation/ht.<layer>``) and the
backward pass count together.

XLA:TPU runs ``jax.lax.ragged_dot`` as a kernel of its own (the instruction
``ragged-dot-none.N``) and keeps none of jax's metadata on it, scope included
(seen in the compiled step, PR 28).  ``ops=`` therefore adds the operations
whose instruction name starts with a given prefix to those found by scope.

``least_seconds`` of a kernel is ``roofline.least_seconds``; only the time
under the scope is found differently from ``roofline.scope_share``.
"""

from __future__ import annotations

import re
import statistics

from . import roofline
from . import trace as tr

_WRAPPED = re.compile(r"^[A-Za-z_][\w.\-]*\((.*)\)$")


def bare(component: str) -> str:
    """``transpose(jvp(ht.mlp))`` -> ``ht.mlp``: the component without the
    transformations jax wrapped around it."""
    while True:
        m = _WRAPPED.match(component)
        if m is None:
            return component
        component = m.group(1)


def layers(scope: str) -> list:
    """The ``ht.`` components of a scope, outermost first, unwrapped."""
    return [b for b in map(bare, scope.split("/")) if b.startswith(tr.SCOPE_PREFIX)]


def under(event, name: str) -> bool:
    return name in layers(event.scope)


def seconds(trace, *names: str, ops: tuple = ()):
    """Self seconds of the operations under any of the scopes ``names``, or
    named ``<a prefix of ops>...``, inside the traced jobs, per job, mean over
    chips; ``None`` with no device plane or where no such operation ran (a
    program without the scopes)."""
    if trace is None or not trace.devices:
        return None
    lo, hi = tr.window(trace)
    per_device = [[self_ns for ev, self_ns, _ in tr.nested(dev.ops)
                   if lo <= ev.start < hi
                   and (any(under(ev, n) for n in names) or bool(ops) and ev.name.startswith(ops))]
                  for dev in trace.devices]
    if not any(per_device):
        return None
    return statistics.fmean(sum(ns) for ns in per_device) / 1e9 / len(tr.jobs(trace))


def milliseconds(ctx, *names: str, ops: tuple = ()):
    found = seconds(ctx.trace, *names, ops=ops)
    return None if found is None else 1e3 * found


def kernel_share(ctx, kernel: str, flop=None, ops: tuple = ()):
    """Per cent of its roofline that ``work()["kernels"][kernel]`` reaches
    under its scope; ``flop`` replaces the entry's operations where a counter
    knows them better than the shapes do."""
    entry = ctx.work.get("kernels", {}).get(kernel)
    if entry is None or ctx.peaks is None:
        return None
    found = seconds(ctx.trace, entry["scope"], ops=ops)
    if not found:
        return None
    if flop is not None:
        entry = {**entry, "flop": flop}
    least, _ = roofline.least_seconds(entry, ctx.peaks, ctx.chips)
    return 100.0 * least / found


def by_layer(trace) -> dict:
    """Self seconds per job by the innermost layer named, ``""`` for the
    operations under none: the whole step, split once."""
    if trace is None or not trace.devices:
        return {}
    lo, hi = tr.window(trace)
    out = {}
    for dev in trace.devices:
        for ev, self_ns, _ in tr.nested(dev.ops):
            if lo <= ev.start < hi:
                named = layers(ev.scope)
                if named:
                    key = named[-1]
                else:  # the scope-less grouped products under their kernel's name
                    key = ev.name.rstrip("0123456789.") if ev.name.startswith("ragged-dot") else ""
                out[key] = out.get(key, 0.0) + self_ns
    n = len(trace.devices) * len(tr.jobs(trace)) * 1e9
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}
