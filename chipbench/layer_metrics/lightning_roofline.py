"""``lightning_roofline``: per cent of its roofline that lightning attention
reaches: q, k, v and the output once each forward and their cotangents once
each backward at the memory bandwidth (the state's update and the output,
``12 d_k d_v`` operations a token and head forward and backward, lie far below
the bf16 peak), over the time under ``ht.lightning``.  Memory-bound.  Layer:
kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.kernel_share(ctx, "lightning")
