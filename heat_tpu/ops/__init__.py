"""Custom TPU kernels (Pallas) for the framework's hot ops.

XLA's fusion covers most of the ops surface; these kernels target the spots
where manual control of the VMEM working set wins (SURVEY §2.7): the KMeans
assignment step (cdist+argmin fused so the (n, k) distance matrix never
touches HBM) and local softmax attention (flash-restructured so the (S, S)
score matrix never touches HBM).  Each entry point selects kernel or jnp
form from the platform of its data and the block sizes (`interpret=True` on
CPU so the same code path is testable on the dev mesh); a selected kernel
runs or raises.
"""

from .flash_attention import flash_attention
from .kmeans_kernels import fused_assign, fused_em_stats

__all__ = ["flash_attention", "fused_assign", "fused_em_stats"]
