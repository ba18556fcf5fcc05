"""Ragged paged attention: the serving step's one attention launch, its
XLA reference, and the pool row both read.

Online inference (engine/) keeps each sequence's KV history as a list
of fixed-size token blocks inside one shared pool per layer, so
admission and eviction never copy KV state and a ragged batch wastes at
most block_size-1 slots a sequence ("Ragged Paged Attention", arxiv
2604.15464). Attention then gathers K/V through the block table instead
of slicing a dense [B, Tmax] cache. Three things live here, and nothing
else reads a pool:

- the pool's ROW ("The pool's row" below): how a token's K and V, or its
  one latent, lie in a pool's lanes, with the functions that pack,
  unpack and scatter rows. The models' steps write through `write_kv` /
  `write_latent`; the kernel DMAs the same rows as they lie;
  engine/paged_cache.py allocates pools of that row and keeps the
  policy above it.
- `ragged_paged_attention` — the entry point. Decode rows (one query)
  and prefill chunks (a window of queries) of a step ride ONE flat
  packing and one Pallas launch; `ragged_paged_attention_tp` is the same
  launch as a shard_map island over a "tp" mesh axis. Pallas kernel on
  the TPU, the reference elsewhere; `PTPU_PAGED_KERNEL` forces a tier
  (`_resolve_dispatch`), which is how the CPU tests run the whole engine
  through the interpreted kernel.
- `ragged_paged_attention_reference` — pure-XLA gather + dense masked
  attention: the numerics oracle, and the tier off the TPU.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.attention import reference_attention

NEG_INF = -1e9
# the TPU's lane count: a pool row is a multiple of it, and the
# online-softmax m/l scratch is lane-broadcast over it, as in flash.py
LANES = 128
# Mirror of quant.int8_compute's QMAX reciprocal (importing it would pull
# nn.layers into the kernel module). The in-place dequant below must stay
# bit-identical to dequantize_block: x = (q_int8 -> f32) * (scale * RQMAX),
# then cast to the fp pool dtype — that identity is what makes direct int8
# reads produce the same bytes as the promote-then-read path. Multiplying
# by the pre-rounded reciprocal (rather than dividing by 127) keeps eager
# and jitted dequant bit-equal: XLA rewrites constant division into
# reciprocal multiplication, eager mode does not.
_QMAX = 127.0
_RQMAX = float(np.float32(1.0) / np.float32(_QMAX))


@functools.lru_cache(maxsize=1)
def _device_platform() -> str:
    """The default device's platform, resolved once per process.
    jax.devices() takes a lock and rebuilds the device list on every
    call — too heavy for a per-dispatch check on the serve hot path."""
    return jax.devices()[0].platform


def _resolve_dispatch(use_kernel: Optional[bool],
                      interpret: Optional[bool]) -> tuple:
    """Kernel/reference/interpret tier selection for
    `ragged_paged_attention`. Explicit caller arguments win; with
    use_kernel=None the PTPU_PAGED_KERNEL env var can force a tier (so
    the FULL engine path can run through the kernel in interpret mode,
    not just unit tests):

    - "kernel":    Pallas kernel, interpret off-TPU
    - "interpret": Pallas kernel in interpret mode everywhere
    - "reference": XLA reference everywhere
    """
    if use_kernel is None:
        mode = os.environ.get("PTPU_PAGED_KERNEL", "").strip().lower()
        if mode == "reference":
            return False, False
        if mode == "interpret":
            return True, True
        if mode == "kernel":
            use_kernel = True
        elif mode:
            raise ValueError(
                f"PTPU_PAGED_KERNEL={mode!r}: expected "
                "kernel | reference | interpret")
    on_tpu = _device_platform() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if not use_kernel:
        return False, False
    if interpret is None:
        interpret = not on_tpu
    return True, interpret


# ---------------------------------------------------------------------------
# The pool's row. One pool a layer, [num_blocks, block_size, lanes]; a
# token's row is one of two layouts, and this section is the only place
# that states either:
#
# - K/V: each kv head's K in lanes [0, hd) of the head's `head_lanes(hd)`
#   lanes and its V in lanes [hd, 2*hd), zero-padded up to whole
#   128-lane tiles (hd 64 -> 128 lanes a head, hd 128 -> 256); heads side
#   by side, lanes = Hkv * head_lanes(hd). Under tensor parallelism the
#   row shards over its heads, P(None, None, "tp"). The int8 pools take
#   the same rule; their scales stay per block.
# - latent (what a latent-attention model caches): one entry a token and
#   no head axis, k_dim values in `latent_lanes(k_dim)` lanes (576 ->
#   640), the scores contracting all of them and the first v_dim lanes
#   doubling as the value (read with `value_lanes=(0, v_dim)`).
#
# With a lane-dense minor dimension the TPU's default device layout of a
# pool is plain row-major, which is what the step's flat scatter
# (`write_kv` / `write_latent`) and the ragged kernel's per-block DMA
# both use: the step updates a donated pool IN PLACE. (A
# [blocks, bs, Hkv, 64] pool's default layout puts the blocks in the
# lanes, and every step transposed each pool into a padded row-major
# temporary and back.)
# ---------------------------------------------------------------------------


def head_lanes(head_dim: int) -> int:
    """Lanes one kv head takes in a pool row: K then V side by side,
    padded up to whole 128-lane tiles."""
    return -(-2 * head_dim // LANES) * LANES


def _row_heads(rows, head_dim: int):
    """Pool rows [..., Hkv * head_lanes(hd)] -> [..., Hkv, head_lanes(hd)],
    each head's [k | v | pad]: the lanes split at whole 128-lane tiles."""
    return rows.reshape(rows.shape[:-1] + (-1, head_lanes(head_dim)))


def pack_kv(k, v):
    """Per-head k and v, each [..., Hkv, hd], as pool rows
    [..., Hkv * head_lanes(hd)] (numpy in, numpy out; jax in, jax
    out)."""
    xp = np if isinstance(k, np.ndarray) else jnp
    hd = k.shape[-1]
    pad = head_lanes(hd) - 2 * hd
    parts = [k, v] + ([xp.zeros(k.shape[:-1] + (pad,), k.dtype)]
                      if pad else [])
    rows = xp.concatenate(parts, axis=-1)
    return rows.reshape(k.shape[:-2] + (-1,))


def unpack_kv(rows, head_dim: int):
    """Inverse of pack_kv: pool rows [..., Hkv * head_lanes(hd)] ->
    (k, v), each [..., Hkv, hd]."""
    heads = _row_heads(rows, head_dim)
    return heads[..., :head_dim], heads[..., head_dim:2 * head_dim]


def _write_rows(pool, slots, rows):
    nb, bs, lanes = pool.shape
    return pool.reshape(nb * bs, lanes).at[slots].set(
        rows.astype(pool.dtype)).reshape(pool.shape)


def write_kv(pool, slots, k, v):
    """The step's write: token i's k/v [T, Hkv, hd] land in the pool's
    flat row `slots[i]` (block_id * block_size + offset). One scatter
    of whole rows; on a donated pool it runs in place."""
    return _write_rows(pool, slots, pack_kv(k, v))


def latent_lanes(k_dim: int) -> int:
    """Lanes of a latent row: its k_dim values padded up to whole
    128-lane tiles."""
    return -(-k_dim // LANES) * LANES


def pack_latent(latent, lanes: int):
    """Latent entries [..., k_dim] as pool rows [..., lanes]."""
    xp = np if isinstance(latent, np.ndarray) else jnp
    pad = lanes - latent.shape[-1]
    if not pad:
        return latent
    return xp.concatenate(
        [latent, xp.zeros(latent.shape[:-1] + (pad,), latent.dtype)],
        axis=-1)


def write_latent(pool, slots, latent):
    """`write_kv` for a latent pool: token i's entry [T, k_dim] lands in
    flat row `slots[i]`."""
    return _write_rows(pool, slots, pack_latent(latent, pool.shape[-1]))


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


# ---------------------------------------------------------------------------
# Ragged paged attention: ONE launch for a mixed prefill+decode batch.
#
# The serve engine packs every row of a step — decode rows (one query
# token) and prefill chunks (a window of C query tokens) — into a
# single flat query array q: [T, H, D]. Each row occupies a contiguous
# segment aligned to TILE_Q tokens; slack positions inside a row's last
# tile and whole unused tiles are padding. Per-TILE metadata maps the
# packing back to sequences:
#
# - tile_rows [NT] int32: which metadata row each query tile belongs to
#   (the engine points pad tiles at a "null row" whose context_len is 0
#   and whose block table is all scratch block 0: such a tile reaches
#   no key, walks no span and writes zeros).
# - tile_offs [NT] int32: the tile's token offset WITHIN its row's
#   segment, so a query's absolute position is
#   q_starts[row] + tile_off + (index inside the tile).
# - block_tables [R, MB], context_lens [R], q_starts [R]: per-row pool
#   block tables, chunk-end positions (start + q_len; 1 for the null
#   row), and first-query positions. A decode row is simply q_len=1:
#   q_start = ctx - 1.
#
# Pool operand: the engine's pool as it lies in HBM, in the row of the
# section above: [NB, BS, Hkv * W], W = head_lanes(D) lanes a head
# holding [k | v | pad]; one DMA fetches a block's K and V together.
# With q zero-padded to W lanes, q'.[k|v]^T = q.k^T, and p.[k|v]
# carries p.v in lanes [D, 2D): no sub-tile lane slicing per block, and
# a 128-deep contraction where head_dim 64 half-filled it.
#
# Grid: one cell a query tile. A SPAN is S consecutive table entries —
# S read off the pool's shape by `ragged_span`, never an option — and a
# tile walks the spans it has work in, in a loop, ONE online-softmax
# update over S * BS keys each. Which spans those are is reckoned in
# XLA before the call (`_tile_walk`: the tile's reach, its window) and
# prefetched with the metadata, so a tile steps over no span and a tile
# that reaches no key (a pad tile) walks none. The pool never rides a
# BlockSpec: it stays in HBM (memory_space ANY) and a walked span
# copies its blocks itself, one DMA a block into a [2, S, BS, lanes]
# scratch, the next walked span's in flight while this one computes
# (`_ragged_cell`). A grid cell costs the scalar core about 0.1 us an
# index map it evaluates (PERF.md §6, PR 28): a grid of tiles x spans
# paid it for every span a step's tiles had no work in.
#
# A LATENT pool (one entry a token, no head axis: `value_lanes=(0, V)`)
# rides the same grid, walk and DMA: every one of the H query
# heads reads the one cached row (groups = H), q is [q~ | q_rope] padded
# to the row's lanes, the scores contract the whole row and the
# accumulator keeps lanes [0, V) — a query width and a value width that
# differ and overlap. With one kv head the (query, head) pairs are
# flattened to rows outside the kernel, so each span is two plain 2-D
# matmuls and no packed operand is reshaped in it.
#
# Masking is absolute-position causal AND context-bounded
# (kv_pos <= q_pos, kv_pos < ctx; a gathered slot's logical position
# IS its index in block-table order, and a masked score sits at NEG_INF
# and underflows to an exact 0 after the softmax's max-shift, which is
# what the engine's exact batching-invariance tests lean on), so decode
# rows, mid-prompt chunks and pad queries all fall out of one rule: a
# pad query in a row's last tile attends a finite prefix (never
# sampled), kv position 0 is visible to every query of a tile that
# reaches a key, and a tile that reaches none reads zeros.
# ---------------------------------------------------------------------------


def _gather_mixed(pool, q_pool, k_scales, v_scales, ids, d: int):
    """Dense mixed-tier gather for the reference oracle: fp pool rows
    where the (bias-decoded) table entry is non-negative, per-block
    dequantized int8 rows where it is negative. ids: [...] raw table
    entries. Dequant is the dequantize_block identity —
    (int8 -> f32) * (scale / QMAX), cast to the fp pool dtype — so a
    direct read returns exactly the bytes a promote would have
    scattered. Returns (k, v), each [..., BS, Hkv, D]."""
    neg = ids < 0
    fp_ids = jnp.where(neg, 0, ids)
    q_ids = jnp.where(neg, -ids - 1, 0)
    sel = neg[..., None, None, None]
    out = []
    for dense, q8, scales in zip(
            unpack_kv(pool[fp_ids], d), unpack_kv(q_pool[q_ids], d),
            (k_scales, v_scales)):
        deq = (q8.astype(jnp.float32)
               * (scales[q_ids] * _RQMAX)[..., None, None, None]
               ).astype(pool.dtype)
        out.append(jnp.where(sel, deq, dense))
    return tuple(out)


def ragged_paged_attention_reference(q, kv_pool, block_tables,
                                     context_lens, q_starts, tile_rows,
                                     tile_offs,
                                     scale: Optional[float] = None,
                                     groups: int = 1,
                                     kvq_pool=None,
                                     k_scales=None, v_scales=None,
                                     value_lanes=None,
                                     window: Optional[int] = None,
                                     block_mask=None):
    """XLA oracle for the ragged layout: expand tile metadata to
    per-token rows and run the dense gather + masked attention.
    q: [T, H, D] flat-packed; kv_pool: [NB, BS, Hkv * W] (the section
    comment above); returns [T, H, D].

    Gathers [T, MB*BS, Hkv, D] — every token re-gathers its row's
    blocks — but it is the off-TPU dispatch tier where T stays small
    (CPU smoke + tests), and XLA's masked softmax keeps it exactly
    batch-invariant.

    With kvq_pool (+[NQ] per-block k_scales/v_scales) the table
    entries are bias-encoded: id >= 0 reads the fp pool, id < 0 reads
    int8 slot -id-1 and dequantizes in place.

    `value_lanes=(0, V)`: a latent pool [NB, BS, lanes] — one row a
    token for all H heads, keys its lanes [0, D), values its lanes
    [0, V); returns [T, H, V].

    `window`: a query sees the `window` newest positions up to its own
    (its own counts), whatever the table holds behind them.

    `block_mask` [T, MB] bool: the table entries each query may see
    (block-sparse attention: the keys of the others are masked out)."""
    t, h, d = q.shape
    nb, bs, _ = kv_pool.shape
    hkv = h // groups
    nt = tile_rows.shape[0]
    if t % nt:
        raise ValueError(f"flat length {t} not a multiple of {nt} tiles")
    tq = t // nt
    mb = block_tables.shape[1]
    row_of = jnp.repeat(tile_rows, tq)                       # [T]
    qpos = (jnp.repeat(q_starts[tile_rows] + tile_offs, tq)
            + jnp.tile(jnp.arange(tq, dtype=jnp.int32), nt))  # [T]
    bt = block_tables[row_of]                                # [T, MB]
    if value_lanes is not None:
        v_off, v_dim = _latent_value(value_lanes, h, groups, kvq_pool)
        rows = kv_pool[bt].reshape(t, mb * bs, -1)
        kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)
        mask = ((kv_pos[None, :] <= qpos[:, None])
                & (kv_pos[None, :] < context_lens[row_of][:, None]))
        s_ = jnp.einsum("thd,tkd->thk", q.astype(rows.dtype),
                        rows[..., :d]).astype(jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None], s_, NEG_INF), axis=-1)
        return jnp.einsum("thk,tkv->thv", p.astype(rows.dtype),
                          rows[..., v_off:v_off + v_dim]).astype(q.dtype)
    if kvq_pool is None:
        k, v = unpack_kv(kv_pool[bt], d)
    else:
        k, v = _gather_mixed(kv_pool, kvq_pool, k_scales, v_scales, bt, d)
    k = k.reshape(t, mb * bs, hkv, d)
    v = v.reshape(t, mb * bs, hkv, d)
    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)
    ctx = context_lens[row_of]
    mask = ((kv_pos[None, :] <= qpos[:, None])
            & (kv_pos[None, :] < ctx[:, None]))
    if window is not None:
        mask = mask & (kv_pos[None, :] > qpos[:, None] - window)
    if block_mask is not None:
        mask = mask & jnp.repeat(block_mask, bs, axis=1)
    mask = mask[:, None, None, :]
    return reference_attention(q[:, None].astype(k.dtype), k, v, mask=mask,
                               scale=scale)[:, 0].astype(q.dtype)


def _latent_value(value_lanes, h: int, groups: int, kvq_pool):
    """(value offset, value width) of a latent pool, or why it cannot
    be read."""
    if h != groups:
        raise ValueError(
            f"a latent pool holds one row a token for all heads: groups "
            f"must be the head count {h}, got {groups}")
    if kvq_pool is not None:
        raise ValueError("a latent pool has no int8 tier "
                         "(engine/paged_cache.py refuses it)")
    v_off, v_dim = value_lanes
    if v_off != 0:
        raise ValueError("a latent row's value is its first lanes")
    return v_off, v_dim


def _heads_to_kv_major(x, hkv: int, groups: int):
    """[TQ, H, X] -> [Hkv, TQ*G, X]: the batched-over-kv-heads operand
    layout of the two matmuls below. With one query head per kv head
    (MHA) it is a plain transpose; going through the grouped 4-D shape
    there would insert a unit second-minor dimension, a shape cast the
    TPU compiler cannot lay out for packed (bf16) operands."""
    if groups == 1:
        return jnp.transpose(x, (1, 0, 2))
    tq, _, last = x.shape
    return x.reshape(tq, hkv, groups, last).transpose(1, 0, 2, 3) \
            .reshape(hkv, tq * groups, last)


def _kv_major_to_rows(x, tq: int, groups: int):
    """Inverse of _heads_to_kv_major, flattened: [Hkv, TQ*G, X] ->
    [TQ*H, X] (the scratch row order)."""
    hkv, _, last = x.shape
    if groups == 1:
        return jnp.transpose(x, (1, 0, 2)).reshape(tq * hkv, last)
    return x.reshape(hkv, tq, groups, last).transpose(1, 0, 2, 3) \
            .reshape(tq * hkv * groups, last)


def _block_heads(rows, d: int):
    """Pool rows [K, Hkv * W] at head_dim d -> [Hkv, K, W]: the row's
    heads (`_row_heads`), then kv heads lead (the batched matmuls'
    operand layout)."""
    return jnp.transpose(_row_heads(rows, d), (1, 0, 2))


def _ragged_tile_update(q, kv, q0, ctx, k0, m_scr, l_scr, acc_scr, *,
                        scale: float, groups: int, window=None,
                        block_sel=None):
    """Online-softmax update for one query tile against one span of kv
    blocks — shared by the fp-only and mixed-precision ragged kernels. q:
    [TQ, H, W], zero beyond lane D; kv: [Hkv, K, W], the span's K keys
    from absolute position k0 on, each head's [k | v | pad]; scratch
    rows are flattened TQ*H, the accumulator W lanes wide with p.v in
    lanes [D, 2D). Over a latent pool q comes flattened, [TQ*H, W]
    against the one kv "head" [1, K, W], and the accumulator keeps the
    row's leading value lanes only."""
    hkv, keys, _ = kv.shape
    flat = q.ndim == 2        # one kv head: rows are (query, head) pairs
    if flat:
        h = groups
        tq = q.shape[0] // h
        s = jax.lax.dot_general(
            q, kv[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [TQ*H, K]
    else:
        tq, h, _ = q.shape
        # batch over kv heads: [Hkv, TQ*G, W] x [Hkv, K, W]; q's zero
        # lanes drop v out of the contraction
        qg = _heads_to_kv_major(q, hkv, groups)
        s = jax.lax.dot_general(
            qg, kv, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, TQ*G, K]
        s = _kv_major_to_rows(s, tq, groups)            # [TQ*H, K]
    qpos = q0 + jax.lax.broadcasted_iota(
        jnp.int32, (tq, h, keys), 0).reshape(tq * h, keys)
    kpos = k0 + jax.lax.broadcasted_iota(
        jnp.int32, (tq, h, keys), 2).reshape(tq * h, keys)
    seen = (kpos <= qpos) & (kpos < ctx)
    if window is not None:
        # a query whose keys all lie in later spans takes weights of 1
        # on this one; its next span's max-shift multiplies them by an
        # exact 0 (every query reaches its own position or the context's
        # last, `window` >= the tile's width)
        seen = seen & (kpos > qpos - window)
    if block_sel is not None:
        # block-sparse: block_sel [TQ, S], 1 where the query may see
        # the span's block. Spread to (query, head) rows and to keys by
        # two products with 0/1 matrices (no relayout): a query with no
        # block in this span takes weights of 1 on it, as under a
        # window, and its first kept key multiplies them by an exact 0
        # (the first block is always kept)
        blocks = block_sel.shape[1]
        rows = (jax.lax.broadcasted_iota(jnp.int32, (tq * h, tq), 0) // h
                == jax.lax.broadcasted_iota(jnp.int32, (tq * h, tq), 1))
        cols = (jax.lax.broadcasted_iota(jnp.int32, (blocks, keys), 1)
                // (keys // blocks)
                == jax.lax.broadcasted_iota(jnp.int32, (blocks, keys), 0))
        kept = jnp.dot(jnp.dot(rows.astype(jnp.float32), block_sel,
                               preferred_element_type=jnp.float32),
                       cols.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        seen = seen & (kept > 0.5)
    s = jnp.where(seen, s, NEG_INF)

    m_prev = m_scr[...][:, :1]                      # [TQ*H, 1]
    l_prev = l_scr[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                          # [TQ*H, K]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    if flat:    # the value lanes lead the row: whole 128-lane tiles
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[0][:, :acc_scr.shape[1]],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [TQ*H, V]
    else:
        pg = _heads_to_kv_major(p.reshape(tq, h, keys), hkv, groups)
        pv = _kv_major_to_rows(jax.lax.dot_general(
            pg.astype(kv.dtype), kv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32), tq, groups)  # [TQ*H, W]
    acc_scr[...] = alpha * acc_scr[...] + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _ragged_finalize(o_ref, l_scr, acc_scr, v_off=None):
    """Normalize the accumulator's v lanes into the output tile
    [TQ, H, D] (lanes [D, 2D) of a head's [k | v]; over a latent pool
    lanes [v_off, v_off + V) into [TQ*H, V])."""
    d = o_ref.shape[-1]
    v_off = d if v_off is None else v_off
    l = l_scr[...][:, :1]
    o_ref[...] = (acc_scr[...][:, v_off:v_off + d] / jnp.maximum(l, 1e-30)
                  ).reshape(o_ref.shape).astype(o_ref.dtype)


# What a span may hold. A walked span costs about a microsecond before
# it has touched a key (PERF.md §6, PR 28), so it takes as many blocks
# as pay: up to _SPAN_KEYS keys, and no more than _SPAN_BYTES of pool
# blocks (two spans are in flight, and the update's own relayout of a
# span is as large again). The sweep on the chip put the best span
# at 8 blocks for 16-token blocks of 2,048 and 2,560 lanes (16 is
# slower) and at 4 for 128-token blocks of 640 lanes.
_SPAN_KEYS = 512
_SPAN_BYTES = 768 << 10


def ragged_span(block_size: int, lanes: int, itemsize: int,
                max_blocks: int) -> int:
    """How many consecutive block-table entries one span of the ragged
    kernel covers: a tile walks its work a span at a time. Read off the
    pool's shape ([*, block_size, lanes] of `itemsize` bytes, `lanes`
    whatever "The pool's row" makes them — a tensor-parallel shard's
    own) and the table's width: the largest power of two within
    _SPAN_KEYS keys, _SPAN_BYTES of blocks, and the table."""
    fits = min(_SPAN_KEYS // block_size,
               _SPAN_BYTES // (block_size * lanes * itemsize), max_blocks)
    return 1 << (max(fits, 1).bit_length() - 1)


def _ragged_cell(cl_ref, qs_ref, tr_ref, to_ref, walk_ref, q_ref,
                 span_copies, load_span, o_ref, m_scr, l_scr, acc_scr, bufs,
                 cnt, *, scale: float, span: int, tile_q: int, groups: int,
                 v_off=None, window=None, sel_ref=None):
    """One query tile, the grid's one cell a tile, shared by every
    ragged kernel. It walks the spans it has work in, walk_ref[0, t] up
    to walk_ref[1, t] (`_tile_walk`), in order, one online-softmax
    update each; a tile with none walks nothing and writes zeros. The
    pools stay in HBM: a walked span waits for its blocks in one of two
    VMEM buffers and, before it computes, starts the copies of the NEXT
    walked span into the other — the tile's next span, else the first
    span of the next tile that has work, walk_ref[2, t + 1] (a pad tile
    between them walks nothing, so that tile is found by index, not as
    t + 1).

    `span_copies(row, j, slot)` lists span j of table row `row` as
    (place in the span, whether this copy serves the place, the DMA
    into buffer `slot`); `load_span(row, j, slot)` gives the span's
    keys as [Hkv, K, W] ([1, K, W] over a latent pool). Online-softmax
    scratch is flattened to (TQ*H, ·) rows.

    With a `window` (a query sees its `window` newest positions, its
    own among them) a tile's walk starts at the span that holds the
    oldest key its FIRST query sees, and within that span the blocks
    wholly behind it are not copied."""
    t, nt = pl.program_id(0), pl.num_programs(0)
    bs = bufs[0].shape[2]
    span_keys = span * bs

    def tile(ti):
        """(table row, context, first query position, keys reached,
        oldest key seen) of query tile ti. It attends positions below
        its row's context, cut at the causal edge of its LAST query
        (q0 + tile_q - 1): blocks at and past that reach have no work
        for it (`_tile_walk` and the engine's `attn_cells` count spans
        by the same rule)."""
        row = tr_ref[ti]
        ctx = cl_ref[row]
        q0 = qs_ref[row] + to_ref[ti]
        oldest = 0 if window is None else jnp.maximum(q0 - (window - 1), 0)
        return row, ctx, q0, jnp.minimum(ctx, q0 + tile_q), oldest

    def move(go, ti, sj, slot):
        """Start (go) or await the copies of tile ti's span sj: its
        blocks within the tile's reach and not wholly behind its
        window, no others."""
        row, _, _, reach, oldest = tile(ti)
        blocks = -(-reach // bs) - sj * span
        for i, serves, copy in span_copies(row, sj, slot):
            wanted = (i < blocks) & serves
            if window is not None:
                wanted = wanted & (i >= oldest // bs - sj * span)

            @pl.when(wanted)
            def _():
                copy.start() if go else copy.wait()

    def start_tile(ti, slot):
        """Start the copies of tile ti's first span, if ti is a tile
        (nt: no tile with work is left)."""
        @pl.when(ti < nt)
        def _():
            move(True, ti, walk_ref[0, ti], slot)

    @pl.when(t == 0)
    def _first():
        # a span's blocks past its tile's reach are never copied: what
        # the buffers hold there must be finite (masked scores give an
        # exact 0 weight, and 0 times a stale NaN would not be 0)
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        cnt[0] = 0
        start_tile(walk_ref[2, 0], 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    row, ctx, q0, _, _ = tile(t)
    last = walk_ref[1, t] - 1

    def walk(j, carry):
        # the update's mask cuts the span the context or the causal
        # edge ends in, block boundary or not
        slot = cnt[0] % 2

        @pl.when(j < last)
        def _next_span():
            move(True, t, j + 1, 1 - slot)

        @pl.when(j == last)
        def _next_tile():
            start_tile(walk_ref[2, t + 1], 1 - slot)

        move(False, t, j, slot)
        _ragged_tile_update(q_ref[...], load_span(row, j, slot), q0, ctx,
                            j * span_keys, m_scr, l_scr, acc_scr,
                            scale=scale, groups=groups, window=window,
                            block_sel=(None if sel_ref is None
                                       else sel_ref[0, j]))
        cnt[0] += 1
        return carry

    jax.lax.fori_loop(walk_ref[0, t], last + 1, walk, 0)
    _ragged_finalize(o_ref, l_scr, acc_scr, v_off)


def _tile_walk(context_lens, q_starts, tile_rows, tile_offs, *,
               tile_q: int, span_keys: int, window=None):
    """What each query tile walks, in XLA from the call's metadata:
    int32 [3, NT + 1], at column t the tile's first span with work and
    the span past its last (a span has work if its first key is within
    the tile's reach and, under a window, its last is not behind the
    oldest key the tile's first query sees), and at column i the first
    tile at or after i that walks any span (NT where none does; column
    NT is NT)."""
    nt = tile_rows.shape[0]
    ctx = context_lens[tile_rows]
    q0 = q_starts[tile_rows] + tile_offs
    end = -(-jnp.minimum(ctx, q0 + tile_q) // span_keys)
    first = (jnp.zeros_like(end) if window is None else
             jnp.minimum(jnp.maximum(q0 - (window - 1), 0) // span_keys,
                         end))
    works = jnp.where(end > first, jnp.arange(nt), nt)
    after = jax.lax.cummin(jnp.append(works, nt), reverse=True)
    return jnp.stack([jnp.append(first, 0), jnp.append(end, 0),
                      after]).astype(jnp.int32)


def _block_copy(pool_ref, block, buf, sem, slot, place):
    """The DMA of one pool block into its place of buffer `slot`, on
    the slot's semaphore: built alike to start it and to await it."""
    return pltpu.make_async_copy(pool_ref.at[block], buf.at[slot, place],
                                 sem.at[slot])


def _span_entries(bt_ref, row, j, span: int):
    """(place, table entry) of each block of span j of table row `row`;
    places past the table's width repeat its last entry (no tile
    reaches them: a context fits its table)."""
    last = bt_ref.shape[1] - 1
    return [(i, bt_ref[row, jnp.minimum(j * span + i, last)])
            for i in range(span)]


def _ragged_kernel(bt_ref, cl_ref, qs_ref, tr_ref, to_ref, walk_ref, q_ref,
                   pool_ref, o_ref, m_scr, l_scr, acc_scr, buf, sem, cnt, *,
                   span: int, groups: int, **cell):
    """q_ref: [TQ, H, W] — one tile of the flat packing, lane-padded
    ([TQ*H, W] over a latent pool); pool_ref: the whole pool, in HBM;
    buf: [2, span, BS, Hkv * W], the two spans in flight."""

    def span_copies(row, j, slot):
        return [(i, True, _block_copy(pool_ref, e, buf, sem, slot, i))
                for i, e in _span_entries(bt_ref, row, j, span)]

    def load_span(row, j, slot):
        rows = buf[slot].reshape(-1, buf.shape[-1])     # [span * BS, lanes]
        return (rows[None] if q_ref.ndim == 2 else
                _block_heads(rows, o_ref.shape[-1]))

    _ragged_cell(cl_ref, qs_ref, tr_ref, to_ref, walk_ref, q_ref,
                 span_copies, load_span, o_ref, m_scr, l_scr, acc_scr,
                 (buf,), cnt, span=span, groups=groups, **cell)


def _ragged_kernel_selected(bt_ref, cl_ref, qs_ref, tr_ref, to_ref,
                            walk_ref, q_ref, sel_ref, pool_ref, *rest,
                            **cell):
    """`_ragged_kernel` with one more operand: sel_ref [1, spans, TQ,
    span], the tile's block selection a span."""
    _ragged_kernel(bt_ref, cl_ref, qs_ref, tr_ref, to_ref, walk_ref, q_ref,
                   pool_ref, *rest, sel_ref=sel_ref, **cell)


def _ragged_kernel_mixed(bt_ref, cl_ref, qs_ref, tr_ref, to_ref, walk_ref,
                         ksc_ref, vsc_ref, q_ref, pool_ref, qpool_ref,
                         o_ref, m_scr, l_scr, acc_scr, buf, qbuf, sem, cnt,
                         *, span: int, groups: int, **cell):
    """Mixed-precision variant: a block table entry is bias-encoded
    (id >= 0 -> fp pool block id; id < 0 -> int8 pool slot -id-1). A
    block is copied from the pool of the tier that serves it, into
    that tier's buffer, and the kernel dequantizes the int8 block in
    registers with the per-block k and v scales from scalar prefetch,
    each over its own lanes of a head. The dequant is bit-identical to
    quant.dequantize_block, which is what pins direct-read output to
    the promote path's bytes."""
    d = o_ref.shape[-1]

    def span_copies(row, j, slot):
        copies = []
        for i, e in _span_entries(bt_ref, row, j, span):
            copies += [(i, e >= 0, _block_copy(pool_ref, jnp.maximum(e, 0),
                                               buf, sem, slot, i)),
                       (i, e < 0, _block_copy(qpool_ref,
                                              jnp.maximum(-e - 1, 0), qbuf,
                                              sem, slot, i))]
        return copies

    def load_span(row, j, slot):
        blocks = []
        for i, e in _span_entries(bt_ref, row, j, span):
            is8 = e < 0
            s8 = jnp.where(is8, -e - 1, 0)
            fp = _block_heads(buf[slot, i], d)              # [Hkv, BS, W]
            q8 = _block_heads(qbuf[slot, i].astype(jnp.float32), d)
            lane = jax.lax.broadcasted_iota(jnp.int32, q8.shape, 2)
            sc = jnp.where(lane < d, ksc_ref[s8] * _RQMAX,
                           vsc_ref[s8] * _RQMAX)
            blocks.append(jnp.where(is8, (q8 * sc).astype(fp.dtype), fp))
        return jnp.concatenate(blocks, axis=1)

    _ragged_cell(cl_ref, qs_ref, tr_ref, to_ref, walk_ref, q_ref,
                 span_copies, load_span, o_ref, m_scr, l_scr, acc_scr,
                 (buf, qbuf), cnt, span=span, groups=groups, **cell)


# jitted so that a model's layers, which call it at one set of shapes,
# trace and lower the kernel once between them (set-up time: a span's
# unrolled copies make the body long to trace)
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "groups",
                                             "span", "value_lanes",
                                             "window", "name"))
def _ragged_kernel_call(q, kv_pool, block_tables, context_lens,
                        q_starts, tile_rows, tile_offs, scale,
                        interpret: bool, groups: int, span: int,
                        kvq_pool=None, k_scales=None, v_scales=None,
                        value_lanes=None, window=None, name=None,
                        block_mask=None):
    t, h, d = q.shape
    nb, bs, lanes = kv_pool.shape
    mb = block_tables.shape[1]
    nt = tile_rows.shape[0]
    if t % nt:
        raise ValueError(f"flat length {t} not a multiple of {nt} tiles")
    tq = t // nt
    if h % groups:
        raise ValueError(f"q heads {h} not a multiple of groups {groups}")
    if window is not None and window < tq:
        raise ValueError(
            f"window {window} narrower than the query tile {tq}: a pad "
            "query behind its context would see no key")
    latent = value_lanes is not None
    if latent:
        v_off, out_d = _latent_value(value_lanes, h, groups, kvq_pool)
        w, acc_w = latent_lanes(d), latent_lanes(out_d)
        if lanes != w or out_d > d:
            raise ValueError(
                f"pool rows of {lanes} lanes do not hold a latent of {d} "
                f"values whose first {out_d} are the value")
    else:
        v_off, out_d = None, d
        w = acc_w = head_lanes(d)
        if w * (h // groups) != lanes:
            raise ValueError(
                f"pool rows of {lanes} lanes do not hold {h // groups} kv "
                f"heads of [k | v] at head_dim {d}")
    mixed = kvq_pool is not None
    spans = -(-mb // span)
    selected = block_mask is not None
    if selected:
        if mixed or latent or window is not None or h != groups:
            raise ValueError(
                "a block selection is read over one kv head's K/V pool, "
                "with no window, int8 tier or latent row")
        # [T, MB] -> a tile's [spans, TQ, span]: a walked span takes its
        # selection by a leading index
        sel = jnp.pad(block_mask.astype(jnp.float32),
                      ((0, 0), (0, spans * span - mb)))
        sel = sel.reshape(nt, tq, spans, span).transpose(0, 2, 1, 3)
    # q in the head's full lane width: zero lanes meet v in the
    # contraction
    q = jnp.pad(q, ((0, 0), (0, 0), (0, w - d)))
    if latent:      # rows are (query, head) pairs: a free reshape here
        q = q.reshape(t * h, w)
        q_block, o_block = (tq * h, w), (tq * h, out_d)
        out_shape = (t * h, out_d)
    else:
        q_block, o_block = (tq, h, w), (tq, h, d)
        out_shape = (t, h, d)
    zeros = (0,) * (len(q_block) - 1)

    def _q_map(ti, *prefetched):
        return (ti,) + zeros

    # the pools stay where they lie; the kernel copies the blocks it
    # needs itself (`_ragged_cell`), two spans in flight a pool
    pools = (kv_pool, kvq_pool) if mixed else (kv_pool,)
    # block_tables, ctx_lens, q_starts, tiles x2, the walk (+ k/v scales)
    num_prefetch = 8 if mixed else 6
    kernel_fn = (_ragged_kernel_mixed if mixed else
                 _ragged_kernel_selected if selected else
                 functools.partial(_ragged_kernel, v_off=v_off))
    sel_specs = ([pl.BlockSpec((1, spans, tq, span),
                               lambda ti, *prefetched: (ti, 0, 0, 0))]
                 if selected else [])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(nt,),
        in_specs=[pl.BlockSpec(q_block, _q_map)] + sel_specs
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec(o_block, _q_map),
        scratch_shapes=[
            _scratch((tq * h, LANES)),
            _scratch((tq * h, LANES)),
            _scratch((tq * h, acc_w)),
        ] + [pltpu.VMEM((2, span, bs, lanes), p.dtype) for p in pools]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(kernel_fn, scale=scale, span=span,
                               tile_q=tq, groups=groups,
                               **({} if window is None else
                                  {"window": window}))
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        # tiles in order: a tile's last span starts the copies of the
        # next tile with work
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name or ("ragged_latent_attention" if latent else None),
    )
    scalars = (block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
               q_starts.astype(jnp.int32), tile_rows.astype(jnp.int32),
               tile_offs.astype(jnp.int32))
    scalars += (_tile_walk(*scalars[1:], tile_q=tq, span_keys=span * bs,
                           window=window),)
    if mixed:
        return call(*scalars, k_scales.astype(jnp.float32),
                    v_scales.astype(jnp.float32), q, kv_pool, kvq_pool)
    if selected:
        return call(*scalars, q, sel, kv_pool)
    out = call(*scalars, q, kv_pool)
    return out.reshape(t, h, out_d) if latent else out


def ragged_paged_attention(q, kv_pool, block_tables, context_lens,
                           q_starts, tile_rows, tile_offs,
                           scale: Optional[float] = None,
                           use_kernel: Optional[bool] = None,
                           interpret: Optional[bool] = None,
                           groups: int = 1,
                           kvq_pool=None, k_scales=None, v_scales=None,
                           value_lanes=None, window: Optional[int] = None,
                           name: Optional[str] = None, block_mask=None):
    """Mixed prefill+decode attention over the flat ragged packing —
    the engine's single-step entry point. q: [T, H, D]; kv_pool: one
    layer's pool as the cache lays it out, [NB, BS, Hkv * W]; `groups`
    is H / Hkv (the same on every tensor-parallel shard). Dispatch
    tiers: Pallas kernel on TPU, XLA reference elsewhere,
    PTPU_PAGED_KERNEL / explicit flags override (`_resolve_dispatch`).

    When the engine's compressed tier is live it passes the int8 pool
    (kvq_pool [NQ, BS, Hkv * W]) and per-block scales ([NQ] f32 each
    for k and v), and bias-encodes int8-resident blocks into
    block_tables (id < 0 -> slot -id-1): those blocks are read in
    place — dequantized per block inside the gather — instead of being
    promoted to fp first. The signature is shape-stable across fp-only
    / mixed / all-int8 batches so the jit cache stays at one entry
    (TP004).

    `value_lanes=(0, V)` reads a LATENT pool [NB, BS, lanes] (the
    section comment above): q [T, H, D] is each head's absorbed query,
    `groups` = H, and the result is [T, H, V], the attended latent.

    `window=W` is sliding-window attention: a query sees positions
    (p - W, p]. The table is still indexed by logical block, so the
    cache may give the blocks behind the window back while the sequence
    lives: the kernel neither copies them nor lets them in, whatever
    their entries hold. A differential-attention layer arrives here as
    head_dim = 2 x its key width: a pair of key heads side by side is
    one key "head", the pair's two value heads its value, and each
    query head of the pair zero on the other's lanes. `name` is the
    name the Pallas call carries into a device trace.

    `block_mask` [T, MB] bool is BLOCK-SPARSE attention: query i sees
    table entry b of its row only where block_mask[i, b] (and the first
    entry always, the caller's promise: no softmax row is empty). The
    pool holds one kv head (groups = H); a caller whose kv heads select
    apart keeps a pool a head. A row whose every query keeps the same
    few blocks is better served by a COMPACTED table (the kept entries
    in order, the context shortened to match: without positions the
    kernel cannot tell), which costs the kernel nothing; the mask is for
    rows whose queries differ, and its tiles still copy their spans
    whole."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if window is not None and (value_lanes is not None
                               or kvq_pool is not None):
        raise ValueError("a window is read over a K/V pool with no int8 "
                         "tier beside it")
    use_kernel, interpret = _resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return ragged_paged_attention_reference(
            q, kv_pool, block_tables, context_lens, q_starts,
            tile_rows, tile_offs, scale=scale, groups=groups,
            kvq_pool=kvq_pool, k_scales=k_scales, v_scales=v_scales,
            value_lanes=value_lanes, window=window, block_mask=block_mask)
    _, bs, lanes = kv_pool.shape
    span = ragged_span(bs, lanes, kv_pool.dtype.itemsize,
                       block_tables.shape[1])
    return _ragged_kernel_call(q, kv_pool, block_tables,
                               context_lens, q_starts, tile_rows, tile_offs,
                               scale, interpret, groups, span,
                               kvq_pool=kvq_pool,
                               k_scales=k_scales, v_scales=v_scales,
                               value_lanes=value_lanes, window=window,
                               name=name, block_mask=block_mask)


# -- tensor-parallel wrappers (engine tp_size knob, ENGINE.md) ------------
#
# The ragged kernel derives its head counts from its INPUT shapes and
# `groups`, so it runs unmodified on per-shard slices: shard q over
# heads and the pool rows over their kv heads on the "tp" mesh axis and
# each chip computes attention for its own contiguous head block. With
# both H and Hkv divisible by tp, shard s's q-head block
# [s·H/tp, (s+1)·H/tp) maps exactly onto its kv-head block (the local
# `head // groups` lookup is unchanged: groups = H/Hkv is the same
# locally), so GQA groups stay device-local and NO collective runs
# inside attention. Block tables / context lens / packing metadata are
# tiny int32 operands — replicated.


def ragged_paged_attention_tp(mesh, q, kv_pool, block_tables,
                              context_lens, q_starts, tile_rows, tile_offs,
                              scale: Optional[float] = None,
                              use_kernel: Optional[bool] = None,
                              interpret: Optional[bool] = None,
                              groups: int = 1,
                              kvq_pool=None, k_scales=None, v_scales=None):
    """`ragged_paged_attention` as an explicit shard_map island over
    the "tp" axis of `mesh` — q [T, H, D] sharded on H, pool rows
    sharded on their heads, everything else replicated; output
    [T, H, D] stays sharded on H (the downstream out_proj is
    row-parallel over the same axis). The int8 pool shards exactly
    like the fp pool; per-block scales are head-independent scalars,
    replicated."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    heads, rows = P(None, "tp", None), P(None, None, "tp")
    quant = () if kvq_pool is None else (kvq_pool, k_scales, v_scales)

    def body(q_, pool, bt, cl, qs, tr, to, *quant_):
        kvq, ks, vs = quant_ or (None,) * 3
        return ragged_paged_attention(q_, pool, bt, cl, qs, tr, to,
                                      scale=scale, use_kernel=use_kernel,
                                      interpret=interpret, groups=groups,
                                      kvq_pool=kvq, k_scales=ks,
                                      v_scales=vs)

    f = shard_map(body, mesh=mesh,
                  in_specs=(heads, rows, P(), P(), P(), P(), P())
                  + ((rows, P(), P()) if quant else ()),
                  out_specs=heads, check_vma=False)
    return f(q, kv_pool, block_tables, context_lens, q_starts,
             tile_rows, tile_offs, *quant)
