"""``flash_attention_roofline``: per cent of its roofline that causal
grouped-query flash attention reaches: ``2 S^2 d`` operations a head and
sequence forward and twice that backward at the bf16 peak, over the time under
``ht.attention`` (which also holds the recomputed forward pass).
Compute-bound.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.kernel_share(ctx, "flash_attention")
