"""``jit_call_ms_per_job``: milliseconds of a traced job inside ``ht.dispatch.launch``
spans: jax's jit call of a cached program, which holds the runtime's launch.
Mean over the traced jobs (``harness/spans``).  Layer: dispatch."""

from chipbench.harness import spans


def read(ctx):
    return spans.read(ctx, "jit_call_ms_per_job")
