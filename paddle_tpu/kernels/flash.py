"""Flash attention in Pallas (TPU) — forward AND backward kernels.

The Pallas tier is this framework's analog of the reference's hand-fused
CUDA/JIT kernels (operators/fused/, operators/jit/): XLA fuses most things,
but attention's softmax-rescaling loop is the canonical case where a custom
kernel beats the compiler by keeping the [Tq, Tk] score matrix out of HBM.

Design (TPU-idiomatic, layout [BH, T, D]):
- Forward (`flash_fwd`): grid (bh, q_blocks, k_blocks); the k dimension is
  sequential ("arbitrary" semantics) and K/V stream through VMEM one block
  at a time — VMEM holds O(block_q*D + block_k*D), never the full K/V.
  Online-softmax state (running max m, denom l, accumulator) lives in VMEM
  scratch that persists across the sequential k steps. Also emits the
  log-sum-exp residual (lane-broadcast, the standard TPU layout) for the
  backward pass.
- Backward, wired through jax.custom_vjp (pallas_call has no autodiff
  rule), recomputing p = exp(s - lse) from the saved lse instead of storing
  the [Tq, Tk] probability matrix. One kernel body, two schedules, chosen
  from the shapes at trace time (`_bwd_impl`): ONE PASS (`flash_bwd`) where
  a head's dq fits in VMEM — s, p, dp computed once a tile feed dq, dk and
  dv; and TWO KERNELS (`flash_dq` streams K/V blocks per q block,
  `flash_dkv` streams Q/dO blocks per k block) for longer sequences.
- Inside a fetched block every kernel computes strips that stop at the
  diagonal (`_plan`): at (1024, 1024) blocks and T = 1,024 the forward
  computes 56% of a head's T^2 and the one pass 62.5%, where whole
  (512, 512) blocks computed 75%; the causal iota/compare/select runs on
  the crossed part of a strip alone.

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
  global positions makes the keep-mask identical in the forward and every
  backward schedule regardless of block and strip shape, with no [Tq, Tk] mask
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

# Defaults are resolved in flash_attention() (None = `_default_blocks`).
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
    # One block a head where the sequence allows: a block is computed in
    # strips that stop at the diagonal (`_plan`), so its size sets the
    # grid steps and the DMAs, not the computed share. Longer sequences
    # take the largest block that divides them, so that nothing is padded.
    # v5e, bf16, 16 heads of 64, a layer, forward + backward in ms (my chip
    # runs, PR 34, tools/flash_roofline.py on [BH, T, D] operands):
    #   bs 8 x 1,024 causal (the training cell): 0.58-0.66 + 0.84 at
    #     (1024, 1024), 0.96 + 1.10-1.27 at (512, 512); parent 1.08 + 1.70
    #   bs 4 x 2,048 causal: 1.09 + 1.39 at (1024, 1024), 1.38 + 1.80 at
    #     (512, 512); parent 1.69 + 2.84
    #   bs 8 x 1,024 not causal, documents of 384, 128, 256, 256 packed a
    #     row: 1.05 + 1.37 at (1024, 1024), 1.01 + 1.18 at (512, 512),
    #     which skips documents by 512 rows; parent 1.11 + 1.78
    # At T > 2,048 (1024, 1024) was the round-5 sweep's choice too (not
    # re-measured since the kernels walk strips).
    def side(t):
        if t <= 1024:
            return 1024         # the caller caps a block at the sequence
        return next((b for b in (1024, 512, 256) if t % b == 0), 1024)

    return side(t_q), side(t_k)


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
# across the forward and the backward by construction, independent of block
# and strip shape.
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
    if not causal and limit is None and q_seg is None:
        return s
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


def _seg_block(qseg_ref, kseg_ref, rows=slice(None), cols=slice(None)):
    """[R, 1] and [1, C] segment-id slices from the lane/sublane-broadcast
    block refs (or (None, None)); the whole block unless rows/cols say."""
    if qseg_ref is None:
        return None, None
    return qseg_ref[rows, :][:, :1], kseg_ref[:, cols][:1, :]


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
# The schedule inside a fetched block. A grid step fetches a large block
# (it amortises the step and its DMAs) and computes it in strips, each
# one tile: a strip of query rows against every key of the block that one
# of them may see (forward and dq: the softmax state and dq are per row),
# or a strip of key columns against every query that may see one of them
# (one-pass backward and dk/dv: those are per column). A strip ends
# at the diagonal, to the other side's grain, so the computed share of a
# head's T^2 follows the lower triangle, and only the part of a tile that
# the diagonal crosses pays for the causal iota, compare and select.
# --------------------------------------------------------------------------

# (sub_q, sub_k) of strips of rows ("q"), then of strips of keys ("k"). A
# strip of 128 query rows ends at the diagonal to 128 keys; a strip of
# keys is 256 wide and starts at the diagonal to 128 rows. The launchers
# take the pair as `strips` (static; None is this), so a test can give a
# small block strips of its own.
STRIPS = ((128, 128), (128, 256))


def _strip_sizes(block_q: int, block_k: int, by: str, strips=None):
    """The strips' (sub_q, sub_k) for `by`, each side halved down to the
    lane width until it divides the block's, else the block's own side
    (small test blocks)."""
    def fit(sub, side):
        while side % sub and sub > LANES:
            sub //= 2
        return sub if side % sub == 0 else side

    sub_q, sub_k = (strips or STRIPS)[by == "k"]
    return fit(sub_q, block_q), fit(sub_k, block_k)


def _plan(block_q, block_k, sub_q, sub_k, kind, by):
    """Static list of a block's tiles, one a strip: (rows, cols, crossed),
    slices within the block, `crossed` the part of the tile's long side
    that needs the causal mask (a slice within the tile) or None.

    by "q": strips of sub_q rows, long side the keys; by "k": strips of
    sub_k columns, long side the queries. kind "all": no causal mask (not
    causal, or the block lies wholly under the diagonal); "diag": the
    block's corner is on the diagonal (q_start == k_start): a strip
    stops where the diagonal leaves it; "any": a causal block at an
    unknown offset: every strip whole, masked."""
    tiles = []
    if by == "q":
        for lo in range(0, block_q, sub_q):
            hi = lo + sub_q
            seen, clear = block_k, 0 if kind == "any" else block_k
            if kind == "diag":
                # keys < hi are seen by some row, keys <= lo by every row
                seen = min(block_k, -(-hi // sub_k) * sub_k)
                clear = (lo + 1) // sub_k * sub_k
            tiles.append((slice(lo, hi), slice(0, seen),
                          slice(clear, seen) if clear < seen else None))
    else:
        for lo in range(0, block_k, sub_k):
            hi = lo + sub_k
            first, clear = 0, block_q if kind == "any" else 0
            if kind == "diag":
                # rows >= lo see some key, rows >= hi - 1 see every key
                first = lo // sub_q * sub_q
                clear = min(block_q, -(-(hi - 1) // sub_q) * sub_q)
            tiles.append((slice(first, block_q), slice(lo, hi),
                          slice(0, clear - first) if clear > first
                          else None))
    return tiles


def _walk(causal, q_start, k_start, block_q, block_k, by, strips, body):
    """Emit body(tiles) for the block at (q_start, k_start). A causal
    block that `_contributes` let through is either wholly under the
    diagonal or crossed by it; with square blocks a crossed block's
    corner is on the diagonal (q_start == k_start), which makes its
    lower triangle a static list."""
    plan = functools.partial(
        _plan, block_q, block_k,
        *_strip_sizes(block_q, block_k, by, strips), by=by)
    if not causal:
        body(plan(kind="all"))
        return
    under = k_start + block_k - 1 <= q_start
    pl.when(under)(lambda: body(plan(kind="all")))
    pl.when(jnp.logical_not(under))(lambda: body(
        plan(kind="diag" if block_q == block_k else "any")))


def _tile_mask(s, q0, k0, crossed, by, *, limit, q_seg, kv_seg):
    """Mask a tile whose first element is (q0, k0): the length and
    segment masks over all of it (their rule: every tile when they are
    given), the causal mask over `crossed` alone."""
    s = _block_mask(s, q0, k0, causal=False, limit=limit, q_seg=q_seg,
                    kv_seg=kv_seg)
    if crossed is None:
        return s
    axis = 1 if by == "q" else 0
    lo, hi, n = crossed.start, crossed.stop, s.shape[axis]
    part = functools.partial(jax.lax.slice_in_dim, s, axis=axis)
    mid = _block_mask(part(lo, hi), q0 + (0 if axis else lo),
                      k0 + (lo if axis else 0), causal=True, limit=None)
    parts = [mid]
    if lo > 0:
        parts.insert(0, part(0, lo))
    if hi < n:
        parts.append(part(hi, n))
    return mid if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _sub_rows(x, lanes_col):
    """x [R, C] minus a per-row scalar held lane-broadcast as [R, LANES]:
    whole vregs where C is a multiple of the lane width."""
    c = x.shape[1]
    if c % LANES == 0:
        return x - (lanes_col if c == LANES
                    else jnp.tile(lanes_col, (1, c // LANES)))
    return x - lanes_col[:, :1]


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, limit: Optional[int], want_lse: bool,
                has_segs: bool, dropout_rate: float, strips):
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

    def body(tiles):
        # one tile a strip of rows: its state is read and written once
        for rows, cols, crossed in tiles:
            q0, k0 = q_start + rows.start, k_start + cols.start
            # Matmul inputs stay in the storage dtype (bf16 on the
            # training path) so the MXU runs at bf16 rate; accumulation
            # and all softmax state are fp32 via preferred_element_type.
            # Casting q/k/v to fp32 here ran the dots at fp32 rate — 4x
            # slower on v5e (round-3 fix).
            q = q_ref[rows, :]                               # [SQ, D]
            k = k_ref[cols, :]                               # [C, D]
            v = v_ref[cols, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [SQ, C] f32
            q_seg, kv_seg = _seg_block(qseg_ref, kseg_ref, rows, cols)
            s = _tile_mask(s, q0, k0, crossed, "q", limit=limit,
                           q_seg=q_seg, kv_seg=kv_seg)
            m_prev = m_scr[rows, :][:, :1]                   # [SQ, 1]
            l_prev = l_scr[rows, :][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            # l (the softmax denominator) accumulates UNdropped p:
            # dropout applies to normalized probabilities, after the
            # softmax.
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            if dropout_rate > 0.0:
                keep = _dropout_keep(seed_ref[0, 0], bh, q0, k0, p.shape,
                                     dropout_rate)
                p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
            acc_scr[rows, :] = alpha * acc_scr[rows, :] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows, :] = jnp.broadcast_to(m_new, (q.shape[0], LANES))
            l_scr[rows, :] = jnp.broadcast_to(l_new, (q.shape[0], LANES))

    @pl.when(_contributes(causal, q_start, k_start, block_q,
                          *_seg_block(qseg_ref, kseg_ref)))
    def _compute():
        _walk(causal, q_start, k_start, block_q, block_k, "q", strips,
              body)

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
    (1 or 2) indexes q blocks vs k blocks (the backward's k-major grids
    swap them)."""
    def qmap(b, i, j):
        g = (b, i, j)
        return (b // heads, g[q_axis], 0)

    def kmap(b, i, j):
        g = (b, i, j)
        return (b // heads, 0, g[k_axis])

    return (pl.BlockSpec((None, block_q, LANES), qmap),
            pl.BlockSpec((None, SUBLANES, block_k), kmap))


# A model calls these once a layer with the same shapes: under jit the
# kernel body (a strip a tile, unrolled) is traced and lowered once a
# program, not once a layer.
_STATIC = ("scale", "causal", "kv_len", "block_q", "block_k", "interpret",
           "dropout_rate", "heads", "strips")


@functools.partial(jax.jit, static_argnames=_STATIC + ("want_lse",))
def _fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len, block_q,
         block_k, interpret, want_lse, dropout_rate, heads, strips=None):
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
        has_segs=has_segs, dropout_rate=dropout_rate, strips=strips)
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
        name="flash_fwd",
    )(*inputs)
    return (out[0], out[1]) if want_lse else (out[0], None)


# --------------------------------------------------------------------------
# Backward. Standard flash recompute: p = exp(q·kᵀ·scale − lse), and with
# dropout ds_ij = p_ij (keep_ij·dp_ij/(1-r) − delta_i), dv from
# g_ij = keep_ij·p_ij/(1-r) — the delta_i = Σ do·o trick still holds
# because o already includes the dropout.
#
# One kernel body, three schedules (`outputs`):
# - "all", the one-pass backward (`flash_bwd`): grid (bh, k blocks,
#   q blocks). s, p and dp of a tile are computed once and feed dq, dk
#   and dv: five products and one exponent pass. dk/dv accumulate in f32
#   scratch over the q blocks of a k block; dq accumulates in an f32
#   scratch that holds the whole head's [t_q, d] and is written once, on
#   the last k block; delta is computed once a q block, on the first.
# - "dq" (`flash_dq`: grid (bh, q blocks, k blocks)) and "dkv"
#   (`flash_dkv`: grid (bh, k blocks, q blocks)): the two-kernel path for
#   sequences whose dq does not fit in VMEM; each recomputes s, p, dp.
# --------------------------------------------------------------------------

# What the one-pass backward keeps in VMEM for a whole head beside its
# blocks: dq's f32 scratch and delta, each t_q x 128 lanes x 4 B (a d of
# 64 pads to a vreg's 128 lanes), and dq's output block in the storage
# dtype, double-buffered: t_q x 128 x (4 + 4 + 2 x 2) B = 1.5 KiB a
# query row at bf16: 1.5 MiB at t_q 1,024, 6 MiB at 4,096, 24 MiB at
# 16,384. Beside it at (1024, 1024) blocks, bf16, d <= 128: q, k, v, do
# and o double-buffered 2.5 MiB, lse 1, dk and dv out 1, their f32
# scratch 1, and a [1024, 256] strip's s, p and dp in f32 3: 8.5 MiB.
# The compiler gives a kernel 16 MiB of VMEM unasked, so the head's
# share may be 6 MiB (14.5 in all). The boundary compiles for a v5e:
# t_q 4,096 at d 64 and 128 in bf16 (with segments and dropout too),
# 3,072 at d 128 in f32, 2,048 at d 256 in bf16
# (tests/test_chip_compile.py holds the first).
ONE_PASS_VMEM_BYTES = 6 * 1024 * 1024


def _one_pass_fits(t_q: int, d: int, itemsize: int) -> bool:
    lanes = -(-d // LANES) * LANES
    return t_q * lanes * (4 + 4 + 2 * itemsize) <= ONE_PASS_VMEM_BYTES


def _bwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, limit: Optional[int], has_segs: bool,
                dropout_rate: float, outputs: str, strips):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, o_ref, lse_ref = next(it), next(it), next(it)
    qseg_ref = next(it) if has_segs else None
    kseg_ref = next(it) if has_segs else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    want_dq, want_dkv = outputs != "dkv", outputs != "dq"
    dq_ref = next(it) if want_dq else None
    dk_ref, dv_ref = (next(it), next(it)) if want_dkv else (None, None)
    dq_scr = next(it) if want_dq else None
    dk_scr, dv_scr = (next(it), next(it)) if want_dkv else (None, None)
    delta_scr = next(it)

    if outputs == "dq":           # q-major grid
        bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        nk = pl.num_programs(2)
    else:                         # k-major grid
        bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        nk, nq = pl.num_programs(1), pl.num_programs(2)
    q_start, k_start = qi * block_q, ki * block_k
    # the one-pass scratches hold the whole head: this block's rows in them
    head_rows = pl.multiple_of(q_start, block_q) if outputs == "all" else 0

    def _delta():
        delta = jnp.sum(do_ref[...].astype(jnp.float32)
                        * o_ref[...].astype(jnp.float32),
                        axis=1, keepdims=True)              # [BQ, 1]
        delta_scr[pl.ds(head_rows, block_q), :] = jnp.broadcast_to(
            delta, (block_q, LANES))

    if want_dq:
        @pl.when(ki == 0)
        def _init_q():
            dq_scr[pl.ds(head_rows, block_q), :] = jnp.zeros(
                (block_q, dq_scr.shape[1]), jnp.float32)
            _delta()                                # once a q block

    if want_dkv:
        @pl.when(qi == 0)
        def _init_k():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

    by = "q" if outputs == "dq" else "k"

    def body(tiles):
        # s, p, dp and ds of a tile once; the products that need them. A
        # strip of keys is one tile, so its dk and dv come from one
        # product each; so does a strip of rows' dq on the q-major grid.
        # The one pass adds a tile's dq into the head's scratch.
        for rows, cols, crossed in tiles:
            q0, k0 = q_start + rows.start, k_start + cols.start
            # bf16 matmul inputs + fp32 accumulation (see _fwd_kernel note)
            q = q_ref[rows, :]
            k = k_ref[cols, :]
            v = v_ref[cols, :]
            do = do_ref[rows, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [R, C]
            q_seg, kv_seg = _seg_block(qseg_ref, kseg_ref, rows, cols)
            s = _tile_mask(s, q0, k0, crossed, by, limit=limit,
                           q_seg=q_seg, kv_seg=kv_seg)
            p = jnp.exp(_sub_rows(s, lse_ref[rows, :]))         # lanes equal
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [R, C]
            g = p
            if dropout_rate > 0.0:
                keep = _dropout_keep(seed_ref[0, 0], bh, q0, k0, p.shape,
                                     dropout_rate)
                g = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
                dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
            head = pl.ds(head_rows + rows.start, rows.stop - rows.start)
            ds = (p * _sub_rows(dp, delta_scr[head, :])).astype(q.dtype)
            if want_dkv:
                dv_scr[cols, :] += jax.lax.dot_general(
                    g.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [C, D]
                dk_scr[cols, :] += jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [C, D]
            if want_dq:
                dq_scr[head, :] += jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [R, D]

    @pl.when(_contributes(causal, q_start, k_start, block_q,
                          *_seg_block(qseg_ref, kseg_ref)))
    def _compute():
        if outputs == "dkv":
            _delta()      # q blocks are the inner axis: once a grid cell
        _walk(causal, q_start, k_start, block_q, block_k, by, strips,
              body)

    if want_dq:
        @pl.when(ki == nk - 1)
        def _finalize_q():
            rows = pl.ds(head_rows, block_q)
            dq_ref[rows, :] = (scale * dq_scr[rows, :]).astype(dq_ref.dtype)

    if want_dkv:
        @pl.when(qi == nq - 1)
        def _finalize_k():
            dk_ref[...] = (scale * dk_scr[...]).astype(dk_ref.dtype)
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("outputs",) + _STATIC)
def _bwd_call(outputs, q, k, v, o, lse, do, q_seg, kv_seg, seed, scale,
              causal, kv_len, block_q, block_k, interpret, dropout_rate,
              heads, strips=None):
    """One backward Pallas call; `outputs` as `_bwd_kernel` has it."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    has_segs = q_seg is not None
    nq, nk = pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k)
    # grid axes 1 and 2: which of them walks q blocks, which k blocks
    q_axis, k_axis = (1, 2) if outputs == "dq" else (2, 1)

    def block_of(axis):
        return lambda *g: (g[0], g[axis], 0)

    q_spec = pl.BlockSpec((None, block_q, d), block_of(q_axis))
    k_spec = pl.BlockSpec((None, block_k, d), block_of(k_axis))
    lse_spec = pl.BlockSpec((None, block_q, LANES), block_of(q_axis))
    in_specs = [q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec]
    inputs = [q, k, v, do, o, lse]
    if has_segs:
        in_specs += _seg_specs(heads, block_q, block_k, q_axis=q_axis,
                               k_axis=k_axis)
        inputs += _expand_segs(q_seg, kv_seg)
    if dropout_rate > 0.0:
        in_specs.append(_smem_spec())
        inputs.append(seed)

    # dq's accumulator and delta cover the rows dq stays in VMEM for: the
    # whole head in the one pass (its output block too), else a q block
    held = t_q if outputs == "all" else block_q
    out_specs, out_shape, scratch = [], [], []
    if outputs != "dkv":
        out_specs.append(
            pl.BlockSpec((None, t_q, d), lambda b, i, j: (b, 0, 0))
            if outputs == "all" else q_spec)
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch.append(_scratch((held, d)))
    if outputs != "dq":
        out_specs += [k_spec, k_spec]
        out_shape += [jax.ShapeDtypeStruct(k.shape, k.dtype),
                      jax.ShapeDtypeStruct(v.shape, v.dtype)]
        scratch += [_scratch((block_k, d)), _scratch((block_k, d))]
    scratch.append(_scratch((held, LANES)))
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, limit=kv_len, has_segs=has_segs,
            dropout_rate=dropout_rate, outputs=outputs, strips=strips),
        grid=(bh, nq, nk) if outputs == "dq" else (bh, nk, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(
            "parallel", "arbitrary" if outputs == "all" else "parallel",
            "arbitrary"),
        interpret=interpret,
        name={"all": "flash_bwd", "dq": "flash_dq",
              "dkv": "flash_dkv"}[outputs],
    )(*inputs)


def _bwd_one_pass(*args):
    """dq, dk, dv from one Pallas call (`flash_bwd`); the arguments are
    `_bwd_impl`'s."""
    return tuple(_bwd_call("all", *args))


def _bwd_two_kernels(*args):
    """dq, dk, dv from two Pallas calls (`flash_dq`, `flash_dkv`), each
    of which recomputes the scores: for a head whose dq the one pass
    cannot hold in VMEM."""
    (dq,) = _bwd_call("dq", *args)
    dk, dv = _bwd_call("dkv", *args)
    return dq, dk, dv


def _bwd_impl(q, k, v, o, lse, do, q_seg, kv_seg, seed, scale, causal,
              kv_len, block_q, block_k, interpret, dropout_rate, heads,
              strips=None):
    """The backward for [BH, T, D] operands; which schedule runs follows
    from the shapes alone, at trace time."""
    one_pass = _one_pass_fits(q.shape[1], q.shape[2], q.dtype.itemsize)
    return (_bwd_one_pass if one_pass else _bwd_two_kernels)(
        q, k, v, o, lse, do, q_seg, kv_seg, seed, scale, causal, kv_len,
        block_q, block_k, interpret, dropout_rate, heads, strips)


# --------------------------------------------------------------------------
# custom_vjp wiring ([BH, T, D] core; segment ids stay [B, T] compact and
# are lane/sublane-expanded per pallas_call)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14))
def _flash_core(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                block_q, block_k, interpret, dropout_rate, heads,
                strips=None):
    o, _ = _fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                block_q, block_k, interpret, want_lse=False,
                dropout_rate=dropout_rate, heads=heads, strips=strips)
    return o


def _flash_core_fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                    block_q, block_k, interpret, dropout_rate, heads,
                    strips):
    o, lse = _fwd(q, k, v, q_seg, kv_seg, seed, scale, causal, kv_len,
                  block_q, block_k, interpret, want_lse=True,
                  dropout_rate=dropout_rate, heads=heads, strips=strips)
    return o, (q, k, v, o, lse, q_seg, kv_seg, seed)


def _flash_core_bwd(scale, causal, kv_len, block_q, block_k, interpret,
                    dropout_rate, heads, strips, res, do):
    q, k, v, o, lse, q_seg, kv_seg, seed = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, q_seg, kv_seg, seed, scale,
                           causal, kv_len, block_q, block_k, interpret,
                           dropout_rate, heads, strips)
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
    # A block is no longer than its sequence rounded up to the lane width:
    # a length such as 1,000 pads to one aligned 1,024 block under kv_len,
    # so the strips apply and no tile is [1000, 1000].
    block_q = min(block_q, -(-t_q // LANES) * LANES)
    block_k = min(block_k, -(-t_k // LANES) * LANES)
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
