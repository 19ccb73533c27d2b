"""Custom TPU kernels (Pallas) for the framework's hot ops.

XLA's fusion covers most of the ops surface; these kernels target the spots
where manual control of the VMEM working set wins (SURVEY §2.7): local
softmax attention, flash-restructured so the (S, S) score matrix never
touches HBM.  Each entry point selects kernel or jnp form from the platform
of its data and the block sizes (`interpret=True` on CPU so the same code
path is testable on the dev mesh); a selected kernel runs or raises.
"""

from .flash_attention import flash_attention
from .kda import chunk_kda

__all__ = ["flash_attention", "chunk_kda"]
