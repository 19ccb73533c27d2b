"""``sparse_attention_roofline``: per cent of its roofline that block-sparse
attention reaches: ``6 (d_qk + d_v)`` operations for every pair of a query head
and a key its token keeps (``work()["kernels"]["sparse_attention"]``: past
``dense_len``, 63 whole blocks and the token's own up to itself, from the
shapes, whatever computes them) at the bf16 peak, or q, k, v, the output and
their cotangents once each at the memory bandwidth, the larger, over the time
under ``ht.sparse_attention``.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.kernel_share(ctx, "sparse_attention")
