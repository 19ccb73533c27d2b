"""``above_dispatch_ms_per_job``: milliseconds of a traced job outside every
``ht.dispatch.*`` span: library code between the user's call and a dispatch helper,
jnp's op-by-op calls, the job's last wait.
Mean over the traced jobs (``harness/spans``).  Layer: estimators."""

from chipbench.harness import spans


def read(ctx):
    return spans.read(ctx, "above_dispatch_ms_per_job")
