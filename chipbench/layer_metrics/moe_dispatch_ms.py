"""``moe_dispatch_ms``: milliseconds of a traced step that bring tokens to
experts and back: ``ht.moe.route`` (scores, selection, weights),
``ht.moe.dispatch`` (sort, gather) and ``ht.moe.combine`` (gather back,
weighted sum), forward, recomputed forward and backward.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.moe.route", "ht.moe.dispatch", "ht.moe.combine")
