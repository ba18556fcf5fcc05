"""TRUE int8 inference: quantized COMPUTE, not simulated dequant.

The PTQ flow in quant/ptq.py matches the reference contrib/int8_inference
semantics (store int8, dequantize at compute) — on TPU that measures
simulation overhead (driver run of 2026-07-31: ptq_vs_bf16 = 0.81x). This module is the
path that makes int8 a WIN: matmuls and convolutions execute on the MXU
in int8 with int32 accumulation (`preferred_element_type`), which this
chip runs at ~1.5-1.7x the bf16 rate at ResNet-50 conv shapes and 1.49x
at the LM-head shape (measured, PERF_NOTES round 5; the 4k matmul probe
says up to 1.59x).

Scheme (per layer, symmetric):
- weights: per-output-channel abs-max scales, frozen offline by
  `freeze_int8` (the reference QuantizationFreezePass capability,
  quantization_pass.py:415 — but freezing to a REAL int8 execution path,
  not annotations);
- activations: dynamic per-tensor abs-max at runtime (one VPU pass),
  so no calibration data is needed and accuracy tracks the input
  distribution;
- y = (xq @ wq)_int32 * x_scale * w_scale / 127^2, bias in f32.

Usage:
    model, variables = V.resnet50(...), <trained float checkpoint>
    qmodel, qvars = freeze_int8(model, variables)
    logits = qmodel.apply(qvars, x, training=False)

`freeze_int8` deep-copies nothing: it rewrites the module tree in place
(like quant/layers.quantize_model) and returns transformed variables;
the float variables are left untouched.
"""

from __future__ import annotations

import copy
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.core.module import Context, Module, PARAMS, Variables
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import (Conv2D, Linear,
                                  normalize_padding)

QMAX = 127.0
_EMA = 0.9      # calibration act-scale momentum (matches quant/layers)


def _quant_with(x, scale):
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / scale * QMAX),
                  -QMAX, QMAX)
    return xq.astype(jnp.int8)


def _act_quant(layer, cx: Context, x):
    """Quantize an activation tensor to int8.

    Three modes:
    - calibration pass (layer.calibrating, set by freeze_int8 — runs
      the model in EVAL semantics so BN uses running stats, the same
      distribution inference will see): dynamic abs-max, and an EMA of
      it is written to the layer's `act_scale` state;
    - static (layer.static_act, set by freeze_int8 after calibration):
      the frozen `act_scale` — PURE ELEMENTWISE, so XLA fuses the
      round/clip/cast into the previous op's epilogue. The dynamic
      abs-max REDUCTION is a fusion barrier costing a full extra HBM
      round-trip per layer (measured: 0.78x vs 0.89x end-to-end on
      ResNet-50 bs16);
    - dynamic (no calibration): abs-max at runtime, no data needed.
    """
    xf = x.astype(jnp.float32)
    if getattr(layer, "calibrating", False):
        cur = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12)
        prev = cx.state("act_scale", (), I.zeros)
        cx.set_state("act_scale",
                     jnp.where(prev > 0, _EMA * prev + (1 - _EMA) * cur,
                               cur))
        return _quant_with(xf, cur), cur
    if getattr(layer, "static_act", False):
        scale = cx.state("act_scale", (), I.constant(1.0))
        scale = jnp.maximum(scale, 1e-12)
        return _quant_with(xf, scale), scale
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12)
    return _quant_with(xf, scale), scale


class Int8Linear(Linear):
    """Linear executing int8 x int8 -> int32 on the MXU. Params:
    `weight` int8 [in, out], `w_scale` f32 [out] (frozen), `bias` f32;
    state `act_scale` when calibrated (static_act)."""

    static_act = False
    calibrating = False

    @classmethod
    def from_float(cls, lin: Linear) -> "Int8Linear":
        q = cls(lin.features, use_bias=lin.use_bias,
                kernel_init=lin.kernel_init, bias_init=lin.bias_init,
                dtype=lin.dtype, param_dtype=lin.param_dtype)
        object.__setattr__(q, "_name", lin._name)
        return q

    def forward(self, cx: Context, x):
        in_features = x.shape[-1]
        w8 = cx.param("weight", (in_features, self.features),
                      I.constant(0.0), jnp.int8)
        ws = cx.param("w_scale", (self.features,), I.constant(1.0),
                      jnp.float32)
        xq, xs = _act_quant(self, cx, x)
        y32 = lax.dot_general(xq, w8, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
        y = y32.astype(jnp.float32) * (xs * ws / (QMAX * QMAX))
        if self.use_bias:
            b = cx.param("bias", (self.features,), self.bias_init,
                         self.param_dtype)
            y = y + b.astype(jnp.float32)
        return y.astype(self.dtype)


class Int8Conv2D(Conv2D):
    """Conv2D executing int8 x int8 -> int32 on the MXU. Params:
    `weight` int8 [kh, kw, cin/g, cout], `w_scale` f32 [cout], `bias`;
    state `act_scale` when calibrated (static_act)."""

    static_act = False
    calibrating = False

    @classmethod
    def from_float(cls, conv: Conv2D) -> "Int8Conv2D":
        q = cls(conv.features, conv.kernel_size, stride=conv.stride,
                padding=conv.padding, dilation=conv.dilation,
                groups=conv.groups, use_bias=conv.use_bias,
                kernel_init=conv.kernel_init, bias_init=conv.bias_init,
                dtype=conv.dtype, param_dtype=conv.param_dtype)
        object.__setattr__(q, "_name", conv._name)
        return q

    def forward(self, cx: Context, x):
        cin = x.shape[-1]
        kh, kw = self.kernel_size
        w8 = cx.param("weight",
                      (kh, kw, cin // self.groups, self.features),
                      I.constant(0.0), jnp.int8)
        ws = cx.param("w_scale", (self.features,), I.constant(1.0),
                      jnp.float32)
        xq, xs = _act_quant(self, cx, x)
        pad = normalize_padding(self.padding)
        y32 = lax.conv_general_dilated(
            xq, w8, window_strides=self.stride, padding=pad,
            rhs_dilation=self.dilation, feature_group_count=self.groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32)
        y = y32.astype(jnp.float32) * (xs * ws / (QMAX * QMAX))
        if self.use_bias:
            b = cx.param("bias", (self.features,), self.bias_init,
                         self.param_dtype)
            y = y + b.astype(jnp.float32)
        return y.astype(self.dtype)


def _rewrite(m: Module) -> Module:
    if type(m) is Linear:
        return Int8Linear.from_float(m)
    if type(m) is Conv2D:
        return Int8Conv2D.from_float(m)
    int8_compute_model(m)
    return m


def int8_compute_model(module: Module) -> Module:
    """In-place rewrite: every plain Linear/Conv2D becomes its Int8*
    twin (same scope names); other modules are recursed into. The
    traversal is quant.layers.swap_layers — one walker for both
    quantization rewrites (Module.__setattr__ re-registers children)."""
    from paddle_tpu.quant.layers import swap_layers
    return swap_layers(module, _rewrite)


def _freeze_params(m: Module, pdict: dict) -> dict:
    out = dict(pdict)
    for name, child in m.children().items():
        sub = pdict.get(name)
        if not isinstance(sub, dict):
            continue
        if isinstance(child, (Int8Linear, Int8Conv2D)):
            w = jnp.asarray(sub["weight"], jnp.float32)
            ws = jnp.maximum(
                jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1))), 1e-12)
            w8 = jnp.clip(jnp.round(w / ws * QMAX), -QMAX, QMAX)
            new = dict(sub)
            new["weight"] = w8.astype(jnp.int8)
            new["w_scale"] = ws
            out[name] = new
        else:
            out[name] = _freeze_params(child, sub)
    return out


def _set_flag(m: Module, attr: str, flag: bool) -> None:
    if isinstance(m, (Int8Linear, Int8Conv2D)):
        object.__setattr__(m, attr, flag)
    for child in m.children().values():
        _set_flag(child, attr, flag)


def freeze_int8(module: Module, variables: Variables, calib_batches=None
                ) -> Tuple[Module, Variables]:
    """Freeze a float model to the true-int8 execution path: rewrites
    the module tree (in place) and returns (module, variables) where
    every converted layer's `weight` is int8 with a per-output-channel
    `w_scale`. Other variables (biases, BN stats, ...) pass through.

    calib_batches: optional iterable of input tuples. When given, one
    calibration pass per batch collects per-layer EMA activation
    abs-max scales into state, and the frozen model uses those STATIC
    scales (the quantize becomes pure elementwise and fuses into the
    previous op's epilogue — measured faster end-to-end than the
    dynamic abs-max, whose reduction is a fusion barrier). Without
    calibration the model quantizes activations dynamically."""
    from paddle_tpu.core.module import STATE
    module = _rewrite(module)       # converts a bare Linear/Conv2D root
    if isinstance(module, (Int8Linear, Int8Conv2D)):
        # root layer: its params sit at the variables root
        holder = Module()
        holder._children["_root"] = module
        params = _freeze_params(
            holder, {"_root": variables.get(PARAMS, {})})["_root"]
    else:
        params = _freeze_params(module, variables.get(PARAMS, {}))
    out = {**variables, PARAMS: params}
    if calib_batches is not None:
        from paddle_tpu.quant.ptq import _merge
        _set_flag(module, "calibrating", True)
        n = 0
        try:
            for batch in calib_batches:
                args = (batch if isinstance(batch, (tuple, list))
                        else (batch,))
                if n == 0:
                    # materialize the new act_scale state entries
                    # (existing state — BN stats — wins over the fresh
                    # skeleton)
                    skel = module.init(jax.random.key(0), *args)
                    out = {**out, STATE: _merge(skel.get(STATE, {}),
                                                out.get(STATE, {}))}
                # EVAL semantics (training=False): BN uses running
                # stats, dropout off — calibration sees the exact
                # distribution inference will
                _, mut = module.apply(out, *args, training=False,
                                      mutable=True)
                out = {**out, STATE: mut[STATE]}
                n += 1
        finally:
            _set_flag(module, "calibrating", False)
        if n == 0:
            raise ValueError("freeze_int8 got an empty calib_batches — "
                             "pass None for dynamic activation scales")
        _set_flag(module, "static_act", True)
    return module, out


# -- host-side KV block quantization (engine/kvtier.py) ----------------------
# The host KV tier stores demoted cache blocks in int8 to double its
# effective byte budget. Same symmetric abs-max scheme as _quant_with,
# but pure numpy: demotion/revival are host-RAM traffic and must not
# touch the device (the engine's jit cache stays at exactly 1).

#: abs-max floor for KV block scales, device side. Matches the
#: quantized-collective floor (parallel/serve_collective.py): an
#: all-zeros block gets a tiny positive scale so 0 quantizes to exactly
#: 0 and dequantizes to exactly 0. The host helpers floor at 1e-12 for
#: historical reasons; both floors only engage below any representable
#: KV magnitude, so host and device scales agree bit-for-bit on real
#: content (tests/test_kvcompress.py pins it) and the three encodings —
#: host tier, wire, device pool — stay interchangeable.
KV_SCALE_FLOOR = 1e-30

#: f32 reciprocal of QMAX, rounded once. Dequant multiplies by
#: `scale * RQMAX` instead of dividing by QMAX: XLA rewrites division
#: by a constant into multiplication by its rounded reciprocal, so a
#: jitted `s / QMAX` and an eager one differ by 1 ulp. Spelling the
#: reciprocal out makes every dequant site — eager promote flush,
#: jitted promote lanes, the mixed ragged kernel's in-register dequant
#: — produce byte-identical fp, which is what lets the direct-read
#: step reproduce the promote path's output bit-for-bit.
RQMAX = float(np.float32(1.0) / np.float32(QMAX))


def quantize_block(x):
    """jit-safe per-block symmetric abs-max int8 quantization on
    DEVICE: reduces over the trailing (block_size, heads, head_dim)
    axes, so a 3-D single block yields a scalar scale and a 4-D
    [lanes, ...] batch (the engine's fixed-lane compress scatter)
    yields one scale per lane. Same scheme as quantize_host_int8 —
    scale = max|x| per block, q = round(x / scale * 127) — so a block
    quantized on device and one quantized on host carry identical
    payloads and interchange freely across the tier/wire/device
    encodings. Returns (int8 array, f32 scales of shape x.shape[:-3])."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=(-3, -2, -1)),
                        jnp.float32(KV_SCALE_FLOOR))
    q = jnp.clip(jnp.round(xf / scale[..., None, None, None] * QMAX),
                 -QMAX, QMAX)
    return q.astype(jnp.int8), scale


def dequantize_block(q, scale, dtype):
    """Inverse of quantize_block (device side): max abs error is
    scale / QMAX per element — one quantization step, the same bound
    the host tier documents. `scale` broadcasts over the trailing
    three axes (scalar for one block, [lanes] for a lane batch). The
    factor is `scale * RQMAX` (see RQMAX) so eager and jitted dequant
    — and the ragged kernel's in-register dequant — agree bit-for-bit."""
    s = jnp.asarray(scale, jnp.float32)[..., None, None, None]
    return (q.astype(jnp.float32) * (s * RQMAX)).astype(dtype)

def quantize_host_int8(x: np.ndarray) -> Tuple[np.ndarray, float]:
    """Per-tensor symmetric abs-max int8 quantization on the host.
    Returns (int8 array, float scale) with scale = max|x| (dequant is
    q * scale / QMAX, mirroring the device-side y32 rescale)."""
    xf = np.asarray(x, dtype=np.float32)
    scale = float(max(np.max(np.abs(xf)), 1e-12))
    q = np.clip(np.round(xf / scale * QMAX), -QMAX, QMAX)
    return q.astype(np.int8), scale


def dequantize_host_int8(q: np.ndarray, scale: float, dtype) -> np.ndarray:
    """Inverse of quantize_host_int8; max abs error is scale / QMAX
    per element (one quantization step)."""
    return (np.asarray(q, np.float32) * (scale / QMAX)).astype(dtype)
