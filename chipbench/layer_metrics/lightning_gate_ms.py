"""``lightning_gate_ms``: milliseconds of a traced step under
``ht.lightning.gate``: the lightning layers' gate projection, their per-head
float32 output norm and the sigmoid gate ahead of ``o_proj``.  Layer: model
layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.lightning.gate")
