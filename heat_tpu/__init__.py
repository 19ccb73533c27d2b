"""heat_tpu — a TPU-native distributed array and data-analytics framework.

A from-scratch re-design of HeAT's capabilities (NumPy-style global arrays
sharded along a ``split`` axis, MPI-style collectives, distributed linear
algebra, sklearn-style estimators, data-parallel NN training) on
JAX/XLA/shard_map/Pallas.  ``import heat_tpu as ht`` exposes the reference's
flat namespace.
"""

from .core import *
from . import core
from .core import random
from .core.redistribution import set_redistribution_budget, get_redistribution_budget
from .core.collectives import set_grad_bucket_budget, get_grad_bucket_budget
from . import linalg
from .linalg import matmul, dot, transpose, norm  # hoist reference's flat exports
from .linalg.basics import outer, trace, tril, triu, vdot, cross, projection, vector_norm, matrix_norm, einsum, einsum_path, kron, inner, tensordot, vecdot
from .linalg.qr import qr
from .linalg.svdtools import svd
from . import spatial
from . import cluster
from . import decomposition
from . import regression
from . import naive_bayes
from . import classification
from . import preprocessing
from . import graph
from . import nn
from . import optim
from . import utils
from . import fft
from . import sparse
from . import parallel
from . import ops

__version__ = core.version.__version__


def __getattr__(name):
    # MPI_WORLD / MPI_SELF are lazy in core.communication (the mesh may not be
    # initialized at import time); forward them here for `ht.MPI_WORLD` parity.
    if name in ("MPI_WORLD", "MPI_SELF"):
        return getattr(core.communication, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
