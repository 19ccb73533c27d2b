"""``attention_gate_ms``: milliseconds of a traced step whose innermost scope is
``ht.attention.gate``: the attention layers' gate (the fifth projection of the
layer's input, its sigmoid and the product with the merged heads; forward,
recomputed forward and backward), which ``ht.attention.proj`` holds and
``attention_proj_ms`` therefore leaves out.  Layer: model layers."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.innermost_ms(ctx, "ht.attention.gate")
