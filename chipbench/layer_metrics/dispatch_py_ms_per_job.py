"""``dispatch_py_ms_per_job``: milliseconds of a traced job inside an
``ht.dispatch.<kind>`` span and outside every ``ht.dispatch.launch``: heat_tpu's own
Python for an eager op (plan, cache lookup, hooks, ``_from_parts``).
Mean over the traced jobs (``harness/spans``).  Layer: dispatch."""

from chipbench.harness import spans


def read(ctx):
    return spans.read(ctx, "dispatch_py_ms_per_job")
