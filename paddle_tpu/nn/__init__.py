from paddle_tpu.core.module import Module, Context, Sequential
from paddle_tpu.nn import initializers
from paddle_tpu.nn.layers import (
    Linear, Conv2D, Conv2DTranspose, Conv3D, Conv3DTranspose, BatchNorm,
    DataNorm, LayerNorm, RMSNorm, GroupNorm, Dropout, Embedding, lrn,
    max_pool2d,
    avg_pool2d, global_avg_pool2d, max_pool3d, avg_pool3d,
)
from paddle_tpu.nn.rnn import (
    BiRNN, GRUCell, LSTMCell, RNN, StackedLSTM,
)
from paddle_tpu.nn.sampled import NCE, HierarchicalSigmoid
