"""``shortconv_ms``: milliseconds of a traced step under ``ht.shortconv``, the
gated short convolutions without their projections.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.shortconv")
