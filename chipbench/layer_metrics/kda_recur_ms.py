"""``kda_recur_ms``: milliseconds of a traced step under ``ht.kda.recur``, the
delta rule's scans over chunks; with ``kda_prepare_ms`` it is ``kda_ms``.
Layer: kernels."""

from chipbench.harness import scopes


def read(ctx):
    return scopes.milliseconds(ctx, "ht.kda.recur")
