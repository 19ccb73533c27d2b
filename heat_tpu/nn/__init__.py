"""NN layer (reference: ``heat/nn/``): module constructors + DataParallel."""

from .modules import *
from . import modules
from .activations import *
from .losses import *
from .spatial import *
from .padshuffle import *
from .extended import *
from . import activations, extended, losses, padshuffle, spatial
from .attention import LatentAttention, MultiheadAttention, apply_rope
from .linear_attention import KimiDeltaAttention, LightningAttention
from .moe import MoE
from .pipelined import Pipelined
from .recurrent import GRU, GRUCell, LSTM, LSTMCell, RNN, RNNCell
from .data_parallel import DataParallel, DataParallelMultiGPU
from . import functional
from . import models
