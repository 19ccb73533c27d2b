"""``kda_conv_ms``: milliseconds of a traced step under ``ht.kda.conv``: the
three causal convolutions of 4 taps with SiLU and the L2 norms of q and k.
Layer: model layers."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.kda.conv")
