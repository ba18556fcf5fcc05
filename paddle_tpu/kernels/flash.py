"""Flash attention in Pallas (TPU) — forward AND backward kernels.

The Pallas tier is this framework's analog of the reference's hand-fused
CUDA/JIT kernels (operators/fused/, operators/jit/): XLA fuses most things,
but attention's softmax-rescaling loop is the canonical case where a custom
kernel beats the compiler by keeping the [Tq, Tk] score matrix out of HBM.

Design (TPU-idiomatic, layout [BH, T, D]):
- Forward: grid (bh, q_blocks, k_blocks); the k dimension is sequential
  ("arbitrary" semantics) and K/V stream through VMEM one block at a time —
  VMEM holds O(block_q*D + block_k*D), never the full K/V. Online-softmax
  state (running max m, denom l, accumulator) lives in VMEM scratch that
  persists across the sequential k steps. Also emits the log-sum-exp
  residual (lane-broadcast, the standard TPU layout) for the backward pass.
- Backward: two recompute kernels wired through jax.custom_vjp (pallas_call
  has no autodiff rule). dq streams K/V blocks per q block; dk/dv streams
  Q/dO blocks per k block. Both recompute p = exp(s - lse) from the saved
  lse instead of storing the [Tq, Tk] probability matrix.

Structured masking (all handled block-wise, never as a dense [Tq, Tk]
tensor):
- `causal` + `kv_len` right-padding, as before;
- `segment_ids` — packed ragged batches (the reference's LoD→dense packing
  idiom, lod_tensor.h:44-58; SURVEY §5.7): tokens attend only within their
  own segment. Blocks whose q/kv segment ranges do not overlap are SKIPPED
  (block-sparse), so a packed batch of short documents costs
  ~sum(len_i^2), not T^2.
- `dropout_rate` — in-kernel attention dropout via a stateless integer
  hash (murmur3 finalizer) on (seed, batch*head, q_pos, k_pos). Using
  global positions makes the keep-mask identical in the forward and both
  backward kernels regardless of block shape, with no [Tq, Tk] mask
  materialized. The softmax denominator uses UNdropped probabilities
  (dropout applies after normalization, matching the XLA reference path's
  bernoulli-on-probs semantics); only the accumulator sees dropped ones.

Only arbitrary dense masks fall back to the XLA reference path in
kernels/attention.py.

On CPU (tests) runs in interpret mode so forward and backward numerics are
validated against reference_attention without TPU hardware.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128     # f32 lane width: m/l/lse scratch is lane-broadcast
SUBLANES = 8    # kv segment ids ride the sublane dim: [B, SUBLANES, Tk]

# Defaults are resolved adaptively in flash_attention() (None = choose by
# sequence length). Measured on v5e (bf16, causal, fwd+bwd): large square
# blocks win at moderate T ((512,512): 3.5x over (128,128) at T=1024,
# 4.8x over XLA dense); (256,512) wins at T>=4096. Small blocks
# under-fill the MXU and pay per-iteration scratch/loop overhead.
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None


def normalize_segment_ids(segment_ids, b: int, t_q: int, t_k: int):
    """Normalize the segment_ids argument shared by the flash and dense
    attention paths: a [B, T] array (self-attention, ids shared by q and
    kv) or a (q_seg [B, Tq], kv_seg [B, Tk]) pair -> (q_seg, kv_seg)
    int32, shape-checked. One helper so the two dispatch paths of the
    same semantic contract cannot drift."""
    if isinstance(segment_ids, (tuple, list)):
        q_seg, kv_seg = segment_ids
    else:
        q_seg = kv_seg = segment_ids
    q_seg = q_seg.astype(jnp.int32)
    kv_seg = kv_seg.astype(jnp.int32)
    if q_seg.shape != (b, t_q) or kv_seg.shape != (b, t_k):
        raise ValueError(
            f"segment_ids shapes {q_seg.shape}/{kv_seg.shape} do not "
            f"match q [{b},{t_q}] / kv [{b},{t_k}]")
    return q_seg, kv_seg


def _default_blocks(t_q: int, t_k: int):
    # v5e-measured: (512,512) best at T<=2048 (2.91 ms @1024/bs16);
    # (1024,1024) best at long T — the round-5 roofline sweep
    # (tools/flash_roofline.py, ceiling-relative): fwd 85.9% of the
    # same-day sustained-matmul rate at 16k vs 70.7% for the previous
    # (512,1024) default (arithmetic intensity 334 vs 204 FLOP/B —
    # comfortably compute-bound either way; the win is fewer grid steps
    # amortizing per-block scratch/loop overhead).
    if t_k > 2048:
        return 1024, 1024
    return 512, 512


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _smem_spec():
    """Whole-array scalar input (the dropout seed) in SMEM."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------
# Stateless in-kernel dropout: murmur3-finalizer hash of
# (seed, bh, q_pos, k_pos). Global positions => the keep-mask is identical
# across the forward and both backward kernels by construction, independent
# of block shape.
# --------------------------------------------------------------------------

def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _dropout_keep(seed, bh, q_start, k_start, shape, rate: float):
    """Boolean keep-mask [BQ, BK]; P(drop) = rate (to within 2^-32)."""
    qpos = (q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            ).astype(jnp.uint32)
    kpos = (k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            ).astype(jnp.uint32)
    key = _mix32(seed.astype(jnp.uint32)
                 + bh.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    u = _mix32((qpos * jnp.uint32(0x9E3779B1)
                + kpos * jnp.uint32(0x85EBCA77)) ^ key)
    return u >= jnp.uint32(rate * 4294967296.0)


def _block_mask(s, q_start, k_start, *, causal: bool, limit: Optional[int],
                q_seg=None, kv_seg=None):
    """Apply causal / length-bound / segment masking to a [BQ, BK] block.

    q_seg: [BQ, 1] int32; kv_seg: [1, BK] int32 (or both None)."""
    bq, bk = s.shape
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    if limit is not None:
        # Bounds every block: covers kv_len right-padding AND the ragged
        # final block when t_k % block_k != 0 (pl.ds clamping would
        # otherwise double-count tail rows).
        s = jnp.where(kpos < limit, s, NEG_INF)
    if q_seg is not None:
        s = jnp.where(q_seg == kv_seg, s, NEG_INF)
    return s


def _seg_block(qseg_ref, kseg_ref):
    """[BQ, 1] and [1, BK] segment-id slices from the lane/sublane-broadcast
    block refs (or (None, None))."""
    if qseg_ref is None:
        return None, None
    return qseg_ref[...][:, :1], kseg_ref[...][:1, :]


def _contributes(causal, q_start, k_start, block_q, q_seg, kv_seg):
    """Block-skip predicate: fully-above-diagonal causal blocks and blocks
    with no segment overlap contribute nothing to the online softmax (m, l,
    acc unchanged), so their compute is skipped. Segment skipping is what
    makes packed ragged batches cost ~sum(len_i^2) instead of T^2."""
    pred = True
    if causal:
        pred = k_start <= q_start + block_q - 1
    if q_seg is not None:
        overlap = jnp.any(q_seg == kv_seg)
        pred = overlap if pred is True else jnp.logical_and(pred, overlap)
    return pred


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, limit: Optional[int], want_lse: bool,
                has_segs: bool, dropout_rate: float):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    qseg_ref = next(it) if has_segs else None
    kseg_ref = next(it) if has_segs else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    o_ref = next(it)
    lse_ref = next(it) if want_lse else None
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)

    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_seg, kv_seg = _seg_block(qseg_ref, kseg_ref)

    @pl.when(_contributes(causal, q_start, k_start, block_q, q_seg, kv_seg))
    def _compute():
        # Matmul inputs stay in the storage dtype (bf16 on the training
        # path) so the MXU runs at bf16 rate; accumulation and all softmax
        # state are fp32 via preferred_element_type. Casting q/k/v to fp32
        # here ran the dots at fp32 rate — 4x slower on v5e (round-3 fix).
        q = q_ref[...]                                   # [BQ, D]
        k = k_ref[...]                                   # [BK, D]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK] f32
        s = _block_mask(s, q_start, k_start, causal=causal, limit=limit,
                        q_seg=q_seg, kv_seg=kv_seg)

        m_prev = m_scr[...][:, :1]                       # [BQ, 1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        # l (the softmax denominator) accumulates UNdropped p: dropout
        # applies to normalized probabilities, after the softmax.
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0, 0], bh, q_start, k_start,
                                 p.shape, dropout_rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        m = m_scr[...][:, :1]
        l = l_scr[...][:, :1]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        if lse_ref is not None:
            lse = m + jnp.log(jnp.maximum(l, 1e-30))
            lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _expand_segs(q_seg, kv_seg):
    """[B, Tq] / [B, Tk] int32 -> lane-broadcast [B, Tq, LANES] and
    sublane-broadcast [B, SUBLANES, Tk] (the standard TPU layouts for
    per-row / per-column scalars)."""
    b, tq = q_seg.shape
    tk = kv_seg.shape[1]
    qs = jax.lax.broadcast_in_dim(q_seg, (b, tq, LANES), (0, 1))
    ks = jax.lax.broadcast_in_dim(kv_seg, (b, SUBLANES, tk), (0, 2))
    return qs, ks


def _seg_specs(heads: int, block_q: int, block_k: int, *, q_axis, k_axis):
    """BlockSpecs for the expanded segment-id arrays. Segment ids are per
    BATCH element while the grid's axis 0 is the flattened batch*heads, so
    the index maps divide by `heads`. q_axis/k_axis pick which grid axis
    (1 or 2) indexes q blocks vs k blocks (the dkv kernel swaps them)."""
    def qmap(b, i, j):
        g = (b, i, j)
        return (b // heads, g[q_axis], 0)

    def kmap(b, i, j):
        g = (b, i, j)
        return (b // heads, 0, g[k_axis])

    return (pl.BlockSpec((None, block_q, LANES), qmap),
            pl.BlockSpec((None, SUBLANES, block_k), kmap))


def _fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len, block_q,
         block_k, interpret, want_lse, dropout_rate, heads):
    """q/k/v: [BH, T, D], T a multiple of the block size (flash_attention
    pads) -> (o [BH, Tq, D], lse [BH, Tq, LANES] f32 | None).

    want_lse=False (inference/eval) skips the lse residual output — it is
    only needed by the backward kernels and its HBM writes can exceed the
    attention output itself at small head dims."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    has_segs = q_seg is not None
    grid = (bh, pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, limit=kv_len, want_lse=want_lse,
        has_segs=has_segs, dropout_rate=dropout_rate)
    o_spec = pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0))
    o_shape = jax.ShapeDtypeStruct((bh, t_q, d), q.dtype)
    in_specs = [
        o_spec,
        pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    inputs = [q, k, v]
    if has_segs:
        qs, ks = _expand_segs(q_seg, kv_seg)
        qspec, kspec = _seg_specs(heads, block_q, block_k, q_axis=1,
                                  k_axis=2)
        in_specs += [qspec, kspec]
        inputs += [qs, ks]
    if dropout_rate > 0.0:
        in_specs.append(_smem_spec())
        inputs.append(seed)
    out_specs = [o_spec]
    out_shape = [o_shape]
    if want_lse:
        out_specs.append(
            pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, t_q, LANES), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _scratch((block_q, LANES)),
            _scratch((block_q, LANES)),
            _scratch((block_q, d)),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*inputs)
    return (out[0], out[1]) if want_lse else (out[0], None)


# --------------------------------------------------------------------------
# Backward: dq kernel (stream K/V per q block), dk/dv kernel (stream Q/dO
# per k block). Standard flash recompute: p = exp(q·kᵀ·scale − lse).
# With dropout, ds_ij = p_ij (keep_ij·dp_ij/(1-r) − delta_i) and dv uses
# g_ij = keep_ij·p_ij/(1-r) — the delta_i = Σ do·o trick still holds
# because o already includes the dropout.
# --------------------------------------------------------------------------

def _dq_kernel(*refs, scale: float, causal: bool, block_q: int,
               block_k: int, limit: Optional[int], has_segs: bool,
               dropout_rate: float):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, o_ref, lse_ref = next(it), next(it), next(it)
    qseg_ref = next(it) if has_segs else None
    kseg_ref = next(it) if has_segs else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dq_ref = next(it)
    dq_scr = next(it)

    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start, k_start = qi * block_q, ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_seg, kv_seg = _seg_block(qseg_ref, kseg_ref)

    @pl.when(_contributes(causal, q_start, k_start, block_q, q_seg, kv_seg))
    def _compute():
        # bf16 matmul inputs + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = jnp.max(lse_ref[...], axis=1, keepdims=True)  # lanes equal
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _block_mask(s, q_start, k_start, causal=causal, limit=limit,
                        q_seg=q_seg, kv_seg=kv_seg)
        p = jnp.exp(s - lse)                                # [BQ, BK] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BQ, BK]
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0, 0], bh, q_start, k_start,
                                 p.shape, dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        do_f = do.astype(jnp.float32)
        o = o_ref[...].astype(jnp.float32)
        delta = jnp.sum(do_f * o, axis=1, keepdims=True)    # [BQ, 1]
        ds = p * (dp - delta)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, limit: Optional[int], has_segs: bool,
                dropout_rate: float):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, o_ref, lse_ref = next(it), next(it), next(it)
    qseg_ref = next(it) if has_segs else None
    kseg_ref = next(it) if has_segs else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dk_ref, dv_ref = next(it), next(it)
    dk_scr, dv_scr = next(it), next(it)

    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    q_start, k_start = qi * block_q, ki * block_k

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_seg, kv_seg = _seg_block(qseg_ref, kseg_ref)

    @pl.when(_contributes(causal, q_start, k_start, block_q, q_seg, kv_seg))
    def _compute():
        # bf16 matmul inputs + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = jnp.max(lse_ref[...], axis=1, keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [BQ, BK]
        s = _block_mask(s, q_start, k_start, causal=causal, limit=limit,
                        q_seg=q_seg, kv_seg=kv_seg)
        p = jnp.exp(s - lse)
        keep = None
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0, 0], bh, q_start, k_start,
                                 p.shape, dropout_rate)
            g = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            g = p
        g_lo = g.astype(do.dtype)
        dv_scr[...] += jax.lax.dot_general(
            g_lo, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BK, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BQ, BK]
        if keep is not None:
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        do_f = do.astype(jnp.float32)
        o = o_ref[...].astype(jnp.float32)
        delta = jnp.sum(do_f * o, axis=1, keepdims=True)
        ds = p * (dp - delta)
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BK, D]

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, q_seg, kv_seg, seed, scale, causal,
              kv_len, block_q, block_k, interpret, dropout_rate, heads):
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    has_segs = q_seg is not None
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, limit=kv_len, has_segs=has_segs,
                  dropout_rate=dropout_rate)
    seg_inputs = []
    if has_segs:
        seg_inputs = list(_expand_segs(q_seg, kv_seg))
    seed_inputs = [seed] if dropout_rate > 0.0 else []

    q_spec = pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0))
    lse_spec = pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0))
    kj_spec = pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0))
    dq_in_specs = [q_spec, kj_spec, kj_spec, q_spec, q_spec, lse_spec]
    if has_segs:
        qspec, kspec = _seg_specs(heads, block_q, block_k, q_axis=1,
                                  k_axis=2)
        dq_in_specs += [qspec, kspec]
    if dropout_rate > 0.0:
        dq_in_specs.append(_smem_spec())
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k)),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v, do, o, lse, *seg_inputs, *seed_inputs)

    qj_spec = pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, j, 0))
    lsej_spec = pl.BlockSpec((None, block_q, LANES),
                             lambda b, i, j: (b, j, 0))
    ki_spec = pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, i, 0))
    dkv_in_specs = [qj_spec, ki_spec, ki_spec, qj_spec, qj_spec, lsej_spec]
    if has_segs:
        # dkv grid is (bh, k_blocks, q_blocks): q blocks ride grid axis 2
        qspec, kspec = _seg_specs(heads, block_q, block_k, q_axis=2,
                                  k_axis=1)
        dkv_in_specs += [qspec, kspec]
    if dropout_rate > 0.0:
        dkv_in_specs.append(_smem_spec())
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, pl.cdiv(t_k, block_k), pl.cdiv(t_q, block_q)),
        in_specs=dkv_in_specs,
        out_specs=[ki_spec, ki_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v, do, o, lse, *seg_inputs, *seed_inputs)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp wiring ([BH, T, D] core; segment ids stay [B, T] compact and
# are lane/sublane-expanded per pallas_call)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash_core(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                block_q, block_k, interpret, dropout_rate, heads):
    o, _ = _fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                block_q, block_k, interpret, want_lse=False,
                dropout_rate=dropout_rate, heads=heads)
    return o


def _flash_core_fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                    block_q, block_k, interpret, dropout_rate, heads):
    o, lse = _fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                  block_q, block_k, interpret, want_lse=True,
                  dropout_rate=dropout_rate, heads=heads)
    return o, (q, k, v, o, lse, q_seg, kv_seg, seed)


def _flash_core_bwd(scale, causal, kv_len, block_q, block_k, interpret,
                    dropout_rate, heads, res, do):
    q, k, v, o, lse, q_seg, kv_seg, seed = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, q_seg, kv_seg, seed, scale,
                           causal, kv_len, block_q, block_k, interpret,
                           dropout_rate, heads)
    return dq, dk, dv, None, None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, mask=None, scale: Optional[float] = None,
                    causal: bool = False, kv_len: Optional[int] = None,
                    segment_ids=None, dropout_rate: float = 0.0,
                    dropout_rng=None,
                    block_q: Optional[int] = DEFAULT_BLOCK_Q,
                    block_k: Optional[int] = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """q: [B, Tq, H, D]; k/v: [B, Tk, H, D] -> [B, Tq, H, D]. Differentiable.

    segment_ids: packed-ragged-batch masking — either a [B, T] int32 array
    (self-attention; ids shared by q and kv) or a (q_seg [B, Tq],
    kv_seg [B, Tk]) pair. Tokens attend only where ids are EQUAL; ids must
    be >= 0 (internal padding uses -1). Blocks with no segment overlap are
    skipped entirely (block-sparse). Every real token must be able to
    attend at least one position (with causal self-attention the diagonal
    guarantees this); a fully-masked row yields finite garbage, not NaN.

    dropout_rate: in-kernel attention dropout (needs dropout_rng when > 0).
    The keep pattern is a deterministic function of (rng, batch*head,
    q_pos, k_pos) — NOT bit-identical to the XLA reference path's
    bernoulli draw, but the same distribution and exactly reproduced in
    the backward kernels.

    mask: only None supported here (use causal/kv_len/segment_ids);
    callers with arbitrary masks must use the reference path —
    kernels/attention.py dispatches accordingly.
    """
    if mask is not None:
        raise ValueError("flash_attention handles causal/kv_len/segment_ids "
                         "only; arbitrary masks use the reference path")
    if dropout_rate >= 1.0:
        raise ValueError("dropout_rate must be < 1.0")
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    q_seg = kv_seg = None
    if segment_ids is not None:
        q_seg, kv_seg = normalize_segment_ids(segment_ids, b, t_q, t_k)

    seed = None
    if dropout_rate > 0.0:
        if dropout_rng is None:
            dropout_rate = 0.0  # eval: dropout is a no-op without an rng
        else:
            seed = jax.random.randint(dropout_rng, (1, 1), 0, 2**31 - 1,
                                      dtype=jnp.int32)

    if block_q is None or block_k is None:
        if interpret:
            # interpret mode (CPU tests): per-block python interpretation
            # cost scales with block area; small blocks keep CI fast and
            # the numerics are block-size-independent
            dq, dk = 128, 128
        else:
            dq, dk = _default_blocks(t_q, t_k)
        block_q = block_q if block_q is not None else dq
        block_k = block_k if block_k is not None else dk

    # Pad sequence dims to block multiples: Pallas clamps a ragged tail
    # block's *start index*, silently overlapping the previous block, so
    # padding + masking via kv_len is the only correct treatment. Autodiff
    # through pad/slice zero-pads the cotangents for the backward kernels.
    # Segment ids pad with -1: real ids are >= 0 so real rows never attend
    # the pad tail, while pad q rows match pad kv columns (keeps their
    # denominators non-degenerate; those rows are sliced off below).
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    pad_q = -t_q % block_q
    pad_k = -t_k % block_k
    if pad_k and kv_len is None and kv_seg is None:
        kv_len = t_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if q_seg is not None:
            q_seg = jnp.pad(q_seg, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if kv_seg is not None:
            kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad_k)),
                             constant_values=-1)

    def to_bhtd(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(-1, x.shape[1], d)

    o = _flash_core(to_bhtd(q), to_bhtd(k), to_bhtd(v), q_seg, kv_seg, seed,
                    scale, causal, kv_len, block_q, block_k, interpret,
                    dropout_rate, h)
    o = jnp.transpose(o.reshape(b, h, t_q + pad_q, d), (0, 2, 1, 3))
    return o[:, :t_q] if pad_q else o
