"""``shortconv_roofline``: per cent of its roofline that the gated short
convolution reaches: 11 values of the hidden size a token and layer (forward
4, backward 7) at the memory bandwidth, over the time under ``ht.shortconv``.
Memory-bound.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.kernel_share(ctx, "shortconv")
