"""``sparse_attention_ms``: milliseconds of a traced step under
``ht.sparse_attention``, the block-sparse kernels over the blocks each token
selected (forward and backward; the block checkpoint keeps the forward's
output), without the selection (``sparse_select_ms``) and the projections.
Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.sparse_attention")
