"""``window_attention_roofline``: per cent of its roofline that windowed causal
grouped-query attention reaches: ``6 (d_qk + d_v)`` operations for every pair
of a query and a key inside the window (forward 2 a width, backward twice
that; ``work()["kernels"]["window_attention"]``, the same count whatever
implements the scope) at the bf16 peak, over the time under
``ht.attention.window`` (which also holds the recomputed forward pass).
Compute-bound.  Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.kernel_share(ctx, "window_attention")
