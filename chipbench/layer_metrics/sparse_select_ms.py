"""``sparse_select_ms``: milliseconds of a traced step under
``ht.sparse_attention.select``: the sparse layers' selection (compressed
keys, their scores and softmax, the sum over a group's heads, the block
maxima, the forced blocks, the top-k and each tile's union), once a step: the
block checkpoint keeps what it chose.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.sparse_attention.select")
