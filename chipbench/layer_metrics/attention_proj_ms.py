"""``attention_proj_ms``: milliseconds of a traced step whose innermost scope is
``ht.attention.proj``: an attention layer's projections, QK norms, rotation and
residual sum, without the flash sweeps further in (``attention_ms``,
``window_attention_ms``).  Layer: model layers."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.innermost_ms(ctx, "ht.attention.proj")
