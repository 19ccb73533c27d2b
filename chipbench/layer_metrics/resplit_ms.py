"""``resplit_ms``: median of the benchmark's ``ht.resplit`` span, which ends
in ``block_until_ready``, over the traced jobs.  Layer: comm."""

from chipbench.harness import trace as tr


def read(ctx):
    return tr.span_ms(ctx.trace, "ht.resplit")
