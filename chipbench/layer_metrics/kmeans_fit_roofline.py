"""``kmeans_fit_roofline``: per cent of its roofline that the fit reaches:
the least time for ``jobs/kmeans_fit.py``'s ``work()`` (memory-bound: X is
read once per iteration) over the device-busy time of a traced job.
Layer: kernels."""

from chipbench.harness.roofline import job_share as read  # noqa: F401
