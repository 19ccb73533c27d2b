"""``matmul_roofline``: per cent of its roofline that the product reaches:
the least time for ``jobs/matmul_resplit.py``'s ``work()`` (compute-bound:
``2 n^3`` operations at the bf16 peak) over the device-busy time of a traced
job.  Layer: kernels."""

from chipbench.harness.roofline import job_share as read  # noqa: F401
