"""Core NN layers (dense stack).

Capability-equivalent of the reference layers DSL (python/paddle/fluid/layers/
nn.py — fc, conv2d, conv3d, pool2d, batch_norm, layer_norm, group_norm,
dropout, embedding, one-hot, etc.) and their C++ kernels (operators/*,
conv_cudnn_op.cu.cc, batch_norm_op.cu).

TPU-first choices:
- NHWC image layout (the TPU-native layout; the reference defaults NCHW for
  cuDNN). `data_format` arg accepts both; NHWC is the fast path.
- bfloat16-friendly: params kept fp32 by default, compute dtype selectable;
  matmuls/convs hit the MXU via lax.dot_general/conv_general_dilated.
- No im2col/col2im machinery (operators/math/im2col.cc) — XLA lowers convs
  to MXU directly.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.module import Context, Module
from paddle_tpu.nn import initializers as I


def _pair(v) -> Tuple[int, int]:
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v)


def normalize_padding(pad):
    """2-D conv padding in its lax form: "SAME"/"VALID" pass through; an
    int or (h, w) int pair becomes explicit per-dim (lo, hi) pairs. One
    home for the idiom (Conv2D and the quant/int8 conv twins all accept
    the same forms)."""
    if isinstance(pad, int):
        return [(pad, pad), (pad, pad)]
    if isinstance(pad, (tuple, list)) and isinstance(pad[0], int):
        return [(pad[0], pad[0]), (pad[1], pad[1])]
    return pad


class Linear(Module):
    """Fully-connected layer (reference fluid.layers.fc, nn.py; mul+add ops).

    Input dim inferred at init-trace time (lazy, like the reference's fc
    which infers from input shape).
    """

    def __init__(self, features: int, use_bias: bool = True,
                 kernel_init=None, bias_init=None, dtype=jnp.float32,
                 param_dtype=jnp.float32):
        super().__init__()
        self.features = features
        self.use_bias = use_bias
        self.kernel_init = kernel_init or I.glorot_uniform
        self.bias_init = bias_init or I.zeros
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        in_features = x.shape[-1]
        w = cx.param("weight", (in_features, self.features),
                     self.kernel_init, self.param_dtype)
        x, w = self._qtransform(cx, x, w)
        y = jnp.matmul(x.astype(self.dtype), w.astype(self.dtype))
        if self.use_bias:
            b = cx.param("bias", (self.features,), self.bias_init,
                         self.param_dtype)
            y = y + b.astype(self.dtype)
        return y

    def _qtransform(self, cx: Context, x, w):
        """Hook for input/weight transforms (quant.layers overrides this
        with the fake-quant pair); identity in the float layer."""
        return x, w


class Conv2D(Module):
    """2-D convolution, NHWC, kernel (kh, kw, in/groups, out).

    Reference: fluid.layers.conv2d + operators/conv_op.cc, conv_cudnn_op.
    """

    def __init__(self, features: int, kernel_size, stride=1, padding="SAME",
                 dilation=1, groups: int = 1, use_bias: bool = True,
                 kernel_init=None, bias_init=None, dtype=jnp.float32,
                 param_dtype=jnp.float32):
        super().__init__()
        self.features = features
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        self.dilation = _pair(dilation)
        self.padding = padding
        self.groups = groups
        self.use_bias = use_bias
        self.kernel_init = kernel_init or I.kaiming_normal
        self.bias_init = bias_init or I.zeros
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        cin = x.shape[-1]
        kh, kw = self.kernel_size
        w = cx.param("weight", (kh, kw, cin // self.groups, self.features),
                     self.kernel_init, self.param_dtype)
        x, w = self._qtransform(cx, x, w)
        pad = normalize_padding(self.padding)
        y = lax.conv_general_dilated(
            x.astype(self.dtype), w.astype(self.dtype),
            window_strides=self.stride, padding=pad,
            rhs_dilation=self.dilation, feature_group_count=self.groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            b = cx.param("bias", (self.features,), self.bias_init,
                         self.param_dtype)
            y = y + b.astype(self.dtype)
        return y

    def _qtransform(self, cx: Context, x, w):
        """Hook for input/weight transforms (see Linear._qtransform)."""
        return x, w


class Conv2DTranspose(Module):
    """Transposed conv (reference conv2d_transpose, operators/conv_transpose_op)."""

    def __init__(self, features: int, kernel_size, stride=1, padding="SAME",
                 use_bias: bool = True, kernel_init=None, dtype=jnp.float32,
                 param_dtype=jnp.float32):
        super().__init__()
        self.features = features
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        self.padding = padding
        self.use_bias = use_bias
        self.kernel_init = kernel_init or I.glorot_uniform
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        cin = x.shape[-1]
        kh, kw = self.kernel_size
        w = cx.param("weight", (kh, kw, cin, self.features),
                     self.kernel_init, self.param_dtype)
        y = lax.conv_transpose(
            x.astype(self.dtype), w.astype(self.dtype),
            strides=self.stride, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            b = cx.param("bias", (self.features,), I.zeros, self.param_dtype)
            y = y + b.astype(self.dtype)
        return y


def _triple(v) -> Tuple[int, int, int]:
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v, v)


class Conv3D(Module):
    """3-D convolution, NDHWC, kernel (kd, kh, kw, in/groups, out).

    Reference: fluid.layers.conv3d (operators/conv_op.cc registers conv3d;
    kernels conv_op.h). TPU-first: NDHWC layout so XLA tiles the contraction
    onto the MXU exactly as for 2-D convs.
    """

    def __init__(self, features: int, kernel_size, stride=1, padding="SAME",
                 dilation=1, groups: int = 1, use_bias: bool = True,
                 kernel_init=None, bias_init=None, dtype=jnp.float32,
                 param_dtype=jnp.float32):
        super().__init__()
        self.features = features
        self.kernel_size = _triple(kernel_size)
        self.stride = _triple(stride)
        self.dilation = _triple(dilation)
        self.padding = padding
        self.groups = groups
        self.use_bias = use_bias
        self.kernel_init = kernel_init or I.kaiming_normal
        self.bias_init = bias_init or I.zeros
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        cin = x.shape[-1]
        kd, kh, kw = self.kernel_size
        w = cx.param("weight", (kd, kh, kw, cin // self.groups, self.features),
                     self.kernel_init, self.param_dtype)
        pad = self.padding
        if isinstance(pad, int):
            pad = [(pad, pad)] * 3
        elif isinstance(pad, (tuple, list)) and isinstance(pad[0], int):
            pad = [(p, p) for p in pad]
        y = lax.conv_general_dilated(
            x.astype(self.dtype), w.astype(self.dtype),
            window_strides=self.stride, padding=pad,
            rhs_dilation=self.dilation, feature_group_count=self.groups,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if self.use_bias:
            b = cx.param("bias", (self.features,), self.bias_init,
                         self.param_dtype)
            y = y + b.astype(self.dtype)
        return y


class Conv3DTranspose(Module):
    """Transposed 3-D conv (reference conv3d_transpose,
    operators/conv_transpose_op.cc). NDHWC."""

    def __init__(self, features: int, kernel_size, stride=1, padding="SAME",
                 use_bias: bool = True, kernel_init=None, dtype=jnp.float32,
                 param_dtype=jnp.float32):
        super().__init__()
        self.features = features
        self.kernel_size = _triple(kernel_size)
        self.stride = _triple(stride)
        self.padding = padding
        self.use_bias = use_bias
        self.kernel_init = kernel_init or I.glorot_uniform
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        cin = x.shape[-1]
        kd, kh, kw = self.kernel_size
        w = cx.param("weight", (kd, kh, kw, cin, self.features),
                     self.kernel_init, self.param_dtype)
        y = lax.conv_transpose(
            x.astype(self.dtype), w.astype(self.dtype),
            strides=self.stride, padding=self.padding,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if self.use_bias:
            b = cx.param("bias", (self.features,), I.zeros, self.param_dtype)
            y = y + b.astype(self.dtype)
        return y


def max_pool3d(x, window, stride=None, padding="VALID"):
    """Reference pool3d(pool_type='max') (operators/pool_op.cc). NDHWC."""
    wd, wh, ww = _triple(window)
    sd, sh, sw = _triple(stride if stride is not None else window)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, wd, wh, ww, 1),
                             (1, sd, sh, sw, 1), padding)


def avg_pool3d(x, window, stride=None, padding="VALID"):
    """Reference pool3d(pool_type='avg'). NDHWC."""
    wd, wh, ww = _triple(window)
    sd, sh, sw = _triple(stride if stride is not None else window)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, wd, wh, ww, 1),
                               (1, sd, sh, sw, 1), padding)
    return summed / (wd * wh * ww)


def lrn(x, n: int = 5, k: float = 1.0, alpha: float = 1e-4,
        beta: float = 0.75):
    """Local response normalisation across channels (reference lrn op,
    operators/lrn_op.cc). NHWC: window of `n` adjacent channels."""
    sq = jnp.square(x.astype(jnp.float32))
    half = n // 2
    # channel-axis sliding-window sum via padded reduce_window
    win = (1,) * (x.ndim - 1) + (n,)
    strides = (1,) * x.ndim
    pads = [(0, 0)] * (x.ndim - 1) + [(half, n - 1 - half)]
    denom = k + alpha * lax.reduce_window(sq, 0.0, lax.add, win, strides,
                                          pads)
    return (x.astype(jnp.float32) / jnp.power(denom, beta)).astype(x.dtype)


class DataNorm(Module):
    """Streaming feature normalisation without batch statistics coupling
    (reference data_norm op, operators/data_norm_op.cc: normalises by
    accumulated size/sum/squared-sum — used by CTR models where batch norm's
    batch coupling hurts).

    State: (count, sum, sumsq) accumulated per feature; output is
    (x - mean) / std with means/stds from the running totals.
    """

    def __init__(self, epsilon: float = 1e-4, param_dtype=jnp.float32):
        super().__init__()
        self.epsilon = epsilon
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        feat = x.shape[-1]
        count = cx.state("count", (), I.ones, self.param_dtype)
        total = cx.state("sum", (feat,), I.zeros, self.param_dtype)
        sumsq = cx.state("sumsq", (feat,), I.ones, self.param_dtype)
        mean = total / count
        var = jnp.maximum(sumsq / count - jnp.square(mean), 0.0)
        y = (x.astype(jnp.float32) - mean) * lax.rsqrt(var + self.epsilon)
        if cx.training:
            xf = x.astype(jnp.float32).reshape(-1, feat)
            cx.set_state("count", count + xf.shape[0])
            cx.set_state("sum", total + jnp.sum(xf, axis=0))
            cx.set_state("sumsq", sumsq + jnp.sum(jnp.square(xf), axis=0))
        return y.astype(x.dtype)


def max_pool2d(x, window, stride=None, padding="VALID"):
    """Reference fluid.layers.pool2d(pool_type='max'); NHWC."""
    wh, ww = _pair(window)
    sh, sw = _pair(stride if stride is not None else window)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, wh, ww, 1),
                             (1, sh, sw, 1), padding)


def avg_pool2d(x, window, stride=None, padding="VALID",
               count_include_pad: bool = True):
    wh, ww = _pair(window)
    sh, sw = _pair(stride if stride is not None else window)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, wh, ww, 1),
                               (1, sh, sw, 1), padding)
    if count_include_pad or padding == "VALID":
        return summed / (wh * ww)
    ones = jnp.ones(x.shape[:3] + (1,), x.dtype)
    counts = lax.reduce_window(ones, 0.0, lax.add, (1, wh, ww, 1),
                               (1, sh, sw, 1), padding)
    return summed / counts


def global_avg_pool2d(x):
    """pool2d(global_pooling=True) analog: NHWC → N,C."""
    return jnp.mean(x, axis=(1, 2))


class BatchNorm(Module):
    """Batch normalisation with running stats (reference batch_norm op,
    operators/batch_norm_op.cc; layers/nn.py batch_norm).

    Functional state: running mean/var live in the `state` collection and are
    returned via `apply(..., mutable=True)` during training. `axis` is the
    feature axis (NHWC → -1).
    """

    def __init__(self, momentum: float = 0.9, epsilon: float = 1e-5,
                 scale: bool = True, center: bool = True, axis: int = -1,
                 dtype=None, param_dtype=jnp.float32,
                 axis_name: Optional[str] = None,
                 fuse_relu: bool = False):
        super().__init__()
        self.momentum = momentum
        self.epsilon = epsilon
        self.scale = scale
        self.center = center
        self.axis = axis
        self.dtype = dtype
        self.param_dtype = param_dtype
        # If set, batch stats are psum-averaged over this mesh axis
        # (sync-BN — the multi-device analog of the reference's per-device BN).
        self.axis_name = axis_name
        # fuse_relu folds the activation INTO the layer and uses the
        # memory-efficient custom backward (nn/fused_bn.py): backward
        # reconstructs normalized activations from the output, so the
        # pre-BN tensor is never saved — the main HBM saver for conv+BN
        # towers (PERF_NOTES.md roofline).
        self.fuse_relu = fuse_relu

    def _update_ema(self, cx: Context, mean_rv, var_rv, mean, var) -> None:
        m = self.momentum
        cx.set_state("mean", (m * mean_rv + (1 - m) * mean)
                     .astype(self.param_dtype))
        cx.set_state("var", (m * var_rv + (1 - m) * var)
                     .astype(self.param_dtype))

    def forward(self, cx: Context, x, use_running_stats: Optional[bool] = None):
        feat = x.shape[self.axis]
        reduce_axes = tuple(i for i in range(x.ndim)
                            if i != (self.axis % x.ndim))
        shape = tuple(feat if i == (self.axis % x.ndim) else 1
                      for i in range(x.ndim))

        mean_rv = cx.state("mean", (feat,), I.zeros, self.param_dtype)
        var_rv = cx.state("var", (feat,), I.ones, self.param_dtype)

        use_running = (not cx.training) if use_running_stats is None \
            else use_running_stats
        if (self.fuse_relu and not use_running and self.scale
                and self.center and self.axis in (-1, x.ndim - 1)
                and self.axis_name is None):
            from paddle_tpu.nn.fused_bn import bn_relu_train
            g = cx.param("scale", (feat,), I.ones, self.param_dtype)
            b = cx.param("bias", (feat,), I.zeros, self.param_dtype)
            y, mean, var = bn_relu_train(x, g.astype(jnp.float32),
                                         b.astype(jnp.float32),
                                         float(self.epsilon))
            self._update_ema(cx, mean_rv, var_rv, mean, var)
            return y.astype(self.dtype or x.dtype)
        if use_running:
            mean, var = mean_rv, var_rv
        else:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            mean2 = jnp.mean(jnp.square(xf), axis=reduce_axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            self._update_ema(cx, mean_rv, var_rv, mean, var)

        inv = lax.rsqrt(var.astype(jnp.float32) + self.epsilon)
        y = (x.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
        if self.scale:
            g = cx.param("scale", (feat,), I.ones, self.param_dtype)
            y = y * g.reshape(shape)
        if self.center:
            b = cx.param("bias", (feat,), I.zeros, self.param_dtype)
            y = y + b.reshape(shape)
        if self.fuse_relu:
            # the layer owns its activation in fused mode; this branch is
            # the eval / non-fusable fallback with identical semantics
            y = jax.nn.relu(y)
        # dtype=None: match the input dtype (stats stay fp32 above). A bf16
        # activation stream stays bf16 end to end — upcasting here doubles
        # HBM traffic on every norm, the main MFU sink found in round 2.
        return y.astype(self.dtype or x.dtype)


class RMSNorm(Module):
    """x * rsqrt(mean(x^2) + epsilon) * scale over the last axis, computed
    in float32: LayerNorm without the mean and the offset."""

    def __init__(self, epsilon: float = 1e-5, dtype=None,
                 param_dtype=jnp.float32):
        super().__init__()
        self.epsilon = epsilon
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        scale = cx.param("scale", (x.shape[-1],), I.ones, self.param_dtype)
        y = xf * lax.rsqrt(var + self.epsilon) * scale.astype(jnp.float32)
        return y.astype(self.dtype or x.dtype)


class LayerNorm(Module):
    """Reference fluid.layers.layer_norm (operators/layer_norm_op)."""

    def __init__(self, epsilon: float = 1e-5, scale: bool = True,
                 center: bool = True, dtype=None,
                 param_dtype=jnp.float32):
        super().__init__()
        self.epsilon = epsilon
        self.scale = scale
        self.center = center
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        feat = x.shape[-1]
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.epsilon)
        if self.scale:
            y = y * cx.param("scale", (feat,), I.ones, self.param_dtype)
        if self.center:
            y = y + cx.param("bias", (feat,), I.zeros, self.param_dtype)
        return y.astype(self.dtype or x.dtype)


class GroupNorm(Module):
    """Reference fluid.layers.group_norm (operators/group_norm_op). NHWC."""

    def __init__(self, groups: int = 32, epsilon: float = 1e-5,
                 dtype=None, param_dtype=jnp.float32):
        super().__init__()
        self.groups = groups
        self.epsilon = epsilon
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, x):
        feat = x.shape[-1]
        g = self.groups
        orig = x.shape
        xf = x.astype(jnp.float32).reshape(orig[:-1] + (g, feat // g))
        axes = tuple(range(1, xf.ndim - 2)) + (xf.ndim - 1,)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
        y = ((xf - mean) * lax.rsqrt(var + self.epsilon)).reshape(orig)
        y = y * cx.param("scale", (feat,), I.ones, self.param_dtype)
        y = y + cx.param("bias", (feat,), I.zeros, self.param_dtype)
        return y.astype(self.dtype or x.dtype)


class Dropout(Module):
    """Reference fluid.layers.dropout (operators/dropout_op).

    Uses upscale-in-train convention (outputs scaled by 1/keep_prob during
    training, identity at inference).
    """

    def __init__(self, rate: float = 0.5):
        super().__init__()
        self.rate = rate

    def forward(self, cx: Context, x, deterministic: Optional[bool] = None):
        det = (not cx.training) if deterministic is None else deterministic
        if det or self.rate == 0.0:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(cx.rng(), keep, x.shape)
        return jnp.where(mask, x / keep, jnp.zeros_like(x))


class Embedding(Module):
    """Token embedding lookup (reference lookup_table op,
    operators/lookup_table_op.cc; fluid.layers.embedding).

    `padding_idx` rows return zeros (reference padding_idx attr). The
    distributed/sharded variant lives in paddle_tpu.parallel.embedding.
    """

    def __init__(self, num_embeddings: int, features: int,
                 padding_idx: Optional[int] = None, embedding_init=None,
                 dtype=jnp.float32, param_dtype=jnp.float32):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.features = features
        self.padding_idx = padding_idx
        self.embedding_init = embedding_init or I.normal(0.0, 0.02)
        self.dtype = dtype
        self.param_dtype = param_dtype

    def forward(self, cx: Context, ids):
        table = cx.param("weight", (self.num_embeddings, self.features),
                         self.embedding_init, self.param_dtype)
        out = jnp.take(table, ids, axis=0).astype(self.dtype)
        if self.padding_idx is not None:
            mask = (ids != self.padding_idx)[..., None]
            out = jnp.where(mask, out, jnp.zeros_like(out))
        return out

    def attend(self, cx: Context, x):
        """Tied-softmax projection: x @ table.T (for LM output heads).

        Self-scopes like Module.__call__ so the lookup resolves to THIS
        module's "weight" — called bare with the parent's cx it would
        otherwise silently create an independent parent-level param and
        break the tie (the bug this fixed in BertEncoder's MLM head)."""
        cx = cx.scope(self._name or type(self).__name__)
        table = cx.param("weight", (self.num_embeddings, self.features),
                         self.embedding_init, self.param_dtype)
        return jnp.matmul(x.astype(self.dtype),
                          table.T.astype(self.dtype))
