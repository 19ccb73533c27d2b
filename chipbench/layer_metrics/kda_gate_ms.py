"""``kda_gate_ms``: milliseconds of a traced step under ``ht.kda.gate``: the
low-rank projections and the arithmetic of the decay, beta and the output
gate, and the output's RMSNorm.  Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.kda.gate")
