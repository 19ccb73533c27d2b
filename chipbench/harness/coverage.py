"""Device time by a test on the operation, for the readers that account for
the whole of a traced step: what runs under no layer's name, what a block runs
under its own name alone, what ``jax.checkpoint`` runs a second time.

With the readers by scope (``scopes.seconds``, ``scopes.by_layer``) they split
a step's busy time once: an operation's innermost ``ht.`` component names its
layer, the grouped products (``GROUPED``) are ``moe_experts_ms``'s by their
instruction's name whatever scope they carry, and what has no ``ht.`` component
at all is ``unscoped``: the job's small programs beside the step, and the
instructions XLA made without metadata (layout copies, prefetches, a fusion
whose root is a conversion the compiler put in).
"""

from __future__ import annotations

import statistics

from . import scopes
from . import trace as tr

GROUPED = "ragged-dot"  # XLA:TPU's kernels for jax.lax.ragged_dot, as moe_experts_ms finds them
RECOMPUTED = "rematted_computation"  # jax's name for what jax.checkpoint runs again in the backward pass


def milliseconds(ctx, keep):
    """Self milliseconds of the operations ``keep(event)`` holds inside the
    traced jobs, per job, mean over chips; ``None`` with no device plane or
    where it holds none."""
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    lo, hi = tr.window(trace)
    per_device = [[self_ns for ev, self_ns, _ in tr.nested(dev.ops) if lo <= ev.start < hi and keep(ev)]
                  for dev in trace.devices]
    if not any(per_device):
        return None
    return statistics.fmean(sum(ns) for ns in per_device) / 1e6 / len(tr.jobs(trace))


def innermost(event):
    """The layer an operation is counted under: its innermost ``ht.``
    component, ``GROUPED`` for a grouped product, ``""`` for neither."""
    if event.name.startswith(GROUPED):
        return GROUPED
    return (scopes.layers(event.scope) or [""])[-1]


def innermost_ms(ctx, layer: str):
    """``milliseconds`` of the operations counted under ``layer``: what the
    scope holds itself, not what lies under another name further in."""
    return milliseconds(ctx, lambda ev: innermost(ev) == layer)


def recomputed(event) -> bool:
    return RECOMPUTED in map(scopes.bare, event.scope.split("/"))
