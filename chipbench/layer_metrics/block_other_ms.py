"""``block_other_ms``: milliseconds of a traced step whose innermost scope is
``ht.lm.block``: what a block runs under no layer's name (the routed branch's
residual sum, what a layer leaves outside its own scopes), the grouped products
not counted (they are ``moe_experts_ms``'s).  Layer: model layers."""

from chipbench.harness import coverage


def read(ctx):
    return coverage.innermost_ms(ctx, "ht.lm.block")
