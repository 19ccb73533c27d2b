"""``embed_ms``: milliseconds of a traced step under ``ht.lm.embed``: the
gather of the token embeddings and the scatter-add of their gradient.
Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.lm.embed")
