"""``uncached_launches_per_job``: programs on the chip during a traced job (mean
over chips) less its ``ht.dispatch.launch`` spans: launches the program cache never saw.
Mean over the traced jobs (``harness/spans``).  Layer: estimators."""

from chipbench.harness import spans


def read(ctx):
    return spans.read(ctx, "uncached_launches_per_job")
