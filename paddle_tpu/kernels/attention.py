"""Attention kernels: XLA reference path + Pallas flash attention on TPU.

The reference framework hand-fuses hot patterns in C++/CUDA (operators/fused/,
attention-adjacent fuse passes ir/attention_lstm_fuse_pass.cc); on TPU the
equivalent tier is Pallas kernels (see /opt/skills/guides/pallas_guide.md).

Layout convention: q/k/v are [B, T, H, Dh] (batch, time, heads, head_dim).
`mha` dispatches:
- Pallas flash attention (paddle_tpu.kernels.flash) when running on TPU and
  shapes are tile-friendly;
- an XLA einsum reference path otherwise (CPU tests, odd shapes). Both paths
  share semantics, so tests on the CPU mesh validate the TPU path's contract.

FLAGS_flash_attention=0 forces the reference path (debugging escape hatch,
like the reference's FLAGS_cudnn_deterministic).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.utils.flags import FLAGS

FLAGS.define("flash_attention", True,
             "Use the Pallas flash-attention kernel on TPU when applicable.")

NEG_INF = -1e9


def reference_attention(q, k, v, mask=None, scale: Optional[float] = None,
                        dropout_rng=None, dropout_rate: float = 0.0):
    """Plain XLA attention. q:[B,Tq,H,D] k/v:[B,Tk,Hkv,D] -> [B,Tq,H,D].

    Hkv may divide H (grouped-query / multi-query attention): the grouped
    einsum never materializes k/v repeated to H heads — at decode time
    the k/v cache read IS the bandwidth bill, which is the point of GQA.

    mask: broadcastable to [B, H, Tq, Tk] (with GQA, to
    [B, Hkv, G, Tq, Tk] after a group-dim insert — [B, 1or H, Tq, Tk]
    masks broadcast either way), True = attend.
    """
    d = q.shape[-1]
    h, h_kv = q.shape[2], k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if h != h_kv:
        if h % h_kv:
            raise ValueError(f"q heads {h} not a multiple of kv heads "
                             f"{h_kv}")
        g = h // h_kv
        b, tq = q.shape[:2]
        qg = q.reshape(b, tq, h_kv, g, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
        logits = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
        if mask is not None:
            m = mask
            if m.ndim == 4:  # [B, 1|H, Tq, Tk] -> group layout
                if m.shape[1] == h:
                    m = m.reshape(m.shape[0], h_kv, g, *m.shape[2:])
                else:
                    m = m[:, :, None]
            logits = jnp.where(m, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        if dropout_rate > 0.0 and dropout_rng is not None:
            keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                        probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
        probs = probs.astype(v.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return out.reshape(b, tq, h, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _on_tpu() -> bool:
    # a backend that fails to initialise raises here: it must not be
    # mistaken for "not a TPU" and served by the XLA reference path
    return jax.devices()[0].platform == "tpu"


def would_use_flash(q_shape, k_shape, has_mask: bool = False,
                    dropout_rate: float = 0.0) -> bool:
    """mha's flash-dispatch gate, exported so callers that must AGREE
    with the dispatch (the analytic MFU corrections in
    benchmark/models.py — the flash custom call scores 0 flops in XLA's
    cost analysis) evaluate the same predicate, not a copy.

    The kernel pads ragged sequence lengths to block multiples itself and
    (round 5) handles segment-id masking and attention dropout in-kernel,
    so the gate only excludes: shapes where XLA's dense attention is
    simply faster, head dims the MXU tiles badly, and arbitrary dense
    masks. `dropout_rate` is accepted for signature compatibility but no
    longer gates — dropout>0 does not change the dispatch. Measured on
    v5e (fwd+bwd, bf16, causal): XLA wins 3.6x at T=256; flash wins 1.9x
    at T=1024 and is the only feasible path at 16k+ (the [B,H,Tq,Tk]
    score tensor stops fitting) — so the gate is the kv length crossing
    512."""
    del dropout_rate  # in-kernel dropout: no longer affects dispatch
    return (FLAGS.get("flash_attention") and _on_tpu()
            and not has_mask
            and q_shape[1] >= 64 and k_shape[1] >= 512
            and q_shape[-1] % 32 == 0 and q_shape[-1] <= 256)


def mha(q, k, v, mask=None, scale: Optional[float] = None,
        dropout_rng=None, dropout_rate: float = 0.0, causal: bool = False,
        kv_len: Optional[int] = None, segment_ids=None):
    """Dispatching multi-head attention entry point used by model code.

    `causal`, `kv_len` (static right-padding length) and `segment_ids`
    ([B, T] int32 packed-batch ids, or a (q_seg, kv_seg) pair; tokens
    attend only where ids match) are forwarded to the flash kernel, which
    handles them block-wise — materializing them into a dense `mask` would
    force the XLA reference path. Dropout runs in-kernel on the flash path
    (same distribution as the reference path's bernoulli, different bits).
    An explicit `mask` (arbitrary pattern) always uses the reference path.
    """
    if would_use_flash(q.shape, k.shape, has_mask=mask is not None):
        from paddle_tpu.kernels import flash
        if k.shape[2] != q.shape[2]:
            # GQA prefill/training: the kernel wants equal head counts —
            # repeat kv heads (compute unchanged; the cache still stores
            # only Hkv heads, which is where GQA's decode win lives)
            if q.shape[2] % k.shape[2]:
                raise ValueError(f"q heads {q.shape[2]} not a multiple "
                                 f"of kv heads {k.shape[2]}")
            g = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        return flash.flash_attention(q, k, v, scale=scale, causal=causal,
                                     kv_len=kv_len, segment_ids=segment_ids,
                                     dropout_rate=dropout_rate,
                                     dropout_rng=dropout_rng)
    if segment_ids is not None:
        from paddle_tpu.kernels.flash import normalize_segment_ids
        q_seg, kv_seg = normalize_segment_ids(
            segment_ids, q.shape[0], q.shape[1], k.shape[1])
        smask = (q_seg[:, :, None] == kv_seg[:, None, :])[:, None]
        mask = smask if mask is None else jnp.logical_and(mask, smask)
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        cmask = (jnp.arange(t_k)[None, :] <= jnp.arange(t_q)[:, None]
                 )[None, None]
        mask = cmask if mask is None else jnp.logical_and(mask, cmask)
    if kv_len is not None:
        t_k = k.shape[1]
        pmask = (jnp.arange(t_k) < kv_len)[None, None, None, :]
        mask = pmask if mask is None else jnp.logical_and(mask, pmask)
    return reference_attention(q, k, v, mask=mask, scale=scale,
                               dropout_rng=dropout_rng,
                               dropout_rate=dropout_rate)
