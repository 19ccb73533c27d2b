"""``shortconv_proj_ms``: milliseconds of a traced step whose innermost scope is
``ht.shortconv.proj``: the gated short convolutions' input and output
projections and the residual sum, without the convolution itself
(``shortconv_ms``).  Layer: model layers."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.innermost_ms(ctx, "ht.shortconv.proj")
