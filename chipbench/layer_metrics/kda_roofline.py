"""``kda_roofline``: per cent of its roofline that the gated delta rule
reaches: q, k, v, the log-decay, beta and the output once each forward and
their cotangents once each backward at the memory bandwidth (``21 d^2``
operations a token and head lie far below the bf16 peak), over the time under
``ht.kda``.  Memory-bound.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.kernel_share(ctx, "kda")
