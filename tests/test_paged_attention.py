"""Ragged paged attention vs the dense oracle.

Reference bar: the block-table gather (kernels/paged_attention.py) must
be numerically indistinguishable from dense attention over the same
tokens — both the pure-XLA reference path and the Pallas kernel (run in
interpret mode, same CPU-validation policy as tests/test_flash_selfcheck.py).
Ragged shapes are the point: single-token sequences, lengths landing
exactly on block boundaries, and mixed depths in one batch.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.attention import reference_attention
from paddle_tpu.kernels.paged_attention import (
    head_lanes, latent_lanes, pack_kv, pack_latent, ragged_paged_attention,
    ragged_paged_attention_reference, ragged_span, unpack_kv)

pytestmark = pytest.mark.serve


def _pools_from_dense(k, v, block_size, num_blocks=None, seed=3):
    """Scatter dense [B, T, Hkv, D] k/v into shuffled block pools and
    return (k_pool, v_pool, block_tables). Shuffling the block ids is
    deliberate: contiguous tables would hide gather/index bugs."""
    b, t, hkv, d = k.shape
    mb = -(-t // block_size)
    num_blocks = num_blocks or (b * mb + 1)
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, num_blocks))[:b * mb]
    tables = ids.reshape(b, mb).astype(np.int32)
    k_pool = np.zeros((num_blocks, block_size, hkv, d), k.dtype)
    v_pool = np.zeros((num_blocks, block_size, hkv, d), v.dtype)
    kp = np.zeros((b, mb * block_size, hkv, d), k.dtype)
    vp = np.zeros((b, mb * block_size, hkv, d), v.dtype)
    kp[:, :t], vp[:, :t] = np.asarray(k), np.asarray(v)
    for i in range(b):
        for j in range(mb):
            k_pool[tables[i, j]] = kp[i, j * block_size:(j + 1) * block_size]
            v_pool[tables[i, j]] = vp[i, j * block_size:(j + 1) * block_size]
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables)


# -- ragged mixed prefill+decode ------------------------------------------

def _ragged_case(rows, h, hkv, d, bs, tq, tmax=None, seed=0,
                 extra_pad_tiles=1):
    """Build a flat-packed mixed batch. `rows` is a list of
    (context_len, q_len): each row's queries are the window
    [ctx - q_len, ctx) of its sequence — q_len=1 is a decode row,
    q_len=ctx a whole prompt, anything between a mid-prompt chunk.
    `tmax` (default: the longest context) is how many tokens the block
    tables are wide enough for. Returns the ragged operands (the pool
    in the cache's layout: K and V of a head side by side in one
    lane-dense row) plus the dense k/v and per-row dense queries for
    the oracle."""
    b = len(rows)
    tmax = tmax or max(ctx for ctx, _ in rows)
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal((b, tmax, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, tmax, hkv, d)), jnp.float32)
    k_pool, v_pool, tables = _pools_from_dense(k, v, bs)
    mb = tables.shape[1]
    nt = sum(-(-qlen // tq) for _, qlen in rows) + extra_pad_tiles
    t_flat = nt * tq
    qflat = np.zeros((t_flat, h, d), np.float32)
    tile_rows = np.full((nt,), b, np.int32)      # default: null row
    tile_offs = np.zeros((nt,), np.int32)
    bt = np.zeros((b + 1, mb), np.int32)
    bt[:b] = np.asarray(tables)
    cl = np.ones((b + 1,), np.int32)
    qs = np.zeros((b + 1,), np.int32)
    qrows, spans = [], []
    cursor = 0
    for i, (ctx, qlen) in enumerate(rows):
        cl[i], qs[i] = ctx, ctx - qlen
        qi = rng.standard_normal((qlen, h, d)).astype(np.float32)
        qrows.append(qi)
        qflat[cursor:cursor + qlen] = qi
        spans.append((cursor, qlen))
        for t in range(-(-qlen // tq)):
            tile_rows[cursor // tq + t] = i
            tile_offs[cursor // tq + t] = t * tq
        cursor += -(-qlen // tq) * tq
    kv_pool = pack_kv(k_pool, v_pool)
    assert kv_pool.shape == (k_pool.shape[0], bs, hkv * head_lanes(d))
    args = (jnp.asarray(qflat), kv_pool, jnp.asarray(bt),
            jnp.asarray(cl), jnp.asarray(qs), jnp.asarray(tile_rows),
            jnp.asarray(tile_offs))
    return args, k, v, qrows, spans


def _ragged_dense_oracle(k, v, qrows, rows):
    """Per-row causal dense attention over the same tokens: query at
    absolute position p attends k[:p+1]."""
    outs = []
    for i, (ctx, qlen) in enumerate(rows):
        qi = jnp.asarray(qrows[i])[None]             # [1, C, H, D]
        kv_pos = jnp.arange(k.shape[1])
        qpos = jnp.arange(ctx - qlen, ctx)
        mask = ((kv_pos[None, :] <= qpos[:, None])
                & (kv_pos[None, :] < ctx))[None, None]
        outs.append(reference_attention(qi, k[i:i + 1], v[i:i + 1],
                                        mask=mask)[0])
    return outs


RAGGED_MIXED_CASES = [
    # (rows [(ctx, qlen)], H, Hkv, D, block_size, tile_q)
    ([(5, 1), (8, 1), (1, 1)], 4, 4, 8, 4, 4),        # all decode rows
    ([(7, 1), (10, 6), (4, 4)], 4, 4, 8, 4, 4),       # decode + chunks
    ([(9, 9), (13, 5), (6, 1)], 4, 4, 8, 4, 4),       # whole-prompt + mid
    ([(7, 3), (11, 1)], 8, 2, 16, 4, 4),              # GQA 4:1
    ([(12, 5), (3, 1)], 4, 1, 8, 8, 4),               # MQA
    ([(16, 16)], 4, 4, 8, 4, 8),                      # block-aligned, tq 8
    # the serving cells' head shapes: 128 lanes a head, rows of 2,048
    # and 2,560 lanes; and GQA at 256 lanes a head
    ([(21, 1), (37, 9)], 16, 16, 64, 16, 8),          # GPT-2 medium
    ([(33, 17), (5, 1)], 20, 20, 64, 16, 8),          # GPT-2 large
    ([(19, 3), (40, 1)], 8, 2, 128, 16, 8),           # GQA 4:1 x 128
]


def _decode_case(tmax, h, hkv, d, context_lens, bs):
    """A batch of single-token sequences — every row a decode row
    `(len, 1)`, its one query at position len - 1 — under block tables
    wide enough for `tmax` tokens."""
    return ([(n, 1) for n in context_lens], h, hkv, d, bs, 4, tmax)


# every step of a decoding batch is this shape
DECODE_CASES = [
    _decode_case(16, 4, 4, 8, [1, 1, 1], 4),         # all single-token
    _decode_case(16, 4, 4, 8, [4, 8, 16], 4),        # exact block boundaries
    _decode_case(13, 4, 4, 8, [1, 4, 7, 13], 4),     # mixed depths, odd T
    _decode_case(9, 8, 2, 16, [3, 9], 4),            # GQA 4:1
    _decode_case(12, 4, 1, 8, [5, 12], 8),           # MQA
]
RAGGED_CASES = [c + (None,) for c in RAGGED_MIXED_CASES] + DECODE_CASES


@pytest.mark.parametrize("rows,h,hkv,d,bs,tq,tmax", RAGGED_CASES)
def test_ragged_reference_matches_dense(rows, h, hkv, d, bs, tq, tmax):
    args, k, v, qrows, spans = _ragged_case(rows, h, hkv, d, bs, tq, tmax)
    got = ragged_paged_attention_reference(*args, groups=h // hkv)
    want = _ragged_dense_oracle(k, v, qrows, rows)
    for i, (off, qlen) in enumerate(spans):
        np.testing.assert_allclose(got[off:off + qlen], want[i],
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("rows,h,hkv,d,bs,tq,tmax", RAGGED_CASES)
def test_ragged_kernel_matches_reference(rows, h, hkv, d, bs, tq, tmax):
    """The ragged Pallas kernel in interpret mode vs the XLA oracle and
    the dense one on mixed batches — decode rows, mid-prompt chunks,
    pad slack and GQA head groups in one launch."""
    args, k, v, qrows, spans = _ragged_case(rows, h, hkv, d, bs, tq, tmax)
    got = ragged_paged_attention(*args, use_kernel=True, interpret=True,
                                 groups=h // hkv)
    want = ragged_paged_attention_reference(*args, groups=h // hkv)
    dense = _ragged_dense_oracle(k, v, qrows, rows)
    assert bool(jnp.isfinite(got).all())    # pad queries/tiles stay finite
    for i, (off, qlen) in enumerate(spans):
        np.testing.assert_allclose(got[off:off + qlen],
                                   want[off:off + qlen],
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got[off:off + qlen], dense[i],
                                   atol=1e-5, rtol=1e-5)


def test_ragged_dispatcher_reference_on_cpu(monkeypatch):
    """With defaults and no TPU the entry point IS the XLA reference,
    bit for bit (no interpret overhead in CPU serving)."""
    monkeypatch.delenv("PTPU_PAGED_KERNEL", raising=False)
    args, *_ = _ragged_case([(7, 1), (10, 6), (4, 4)], 8, 2, 16, 4, 4)
    got = ragged_paged_attention(*args, groups=4)
    want = ragged_paged_attention_reference(*args, scale=16 ** -0.5,
                                            groups=4)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_ragged_pad_rows_are_inert():
    """Pad tiles (null metadata row) and within-segment pad queries
    must not perturb real rows: packing the same rows with extra pad
    tiles yields bit-identical real segments."""
    rows = [(7, 1), (10, 6)]
    a1, *_ , spans1 = _ragged_case(rows, 4, 4, 8, 4, 4, extra_pad_tiles=1)
    a2, *_ , spans2 = _ragged_case(rows, 4, 4, 8, 4, 4, extra_pad_tiles=3)
    g1 = ragged_paged_attention(*a1, use_kernel=True, interpret=True)
    g2 = ragged_paged_attention(*a2, use_kernel=True, interpret=True)
    for (o1, n1), (o2, n2) in zip(spans1, spans2):
        np.testing.assert_allclose(g1[o1:o1 + n1], g2[o2:o2 + n2],
                                   atol=0, rtol=0)


def test_env_override_dispatch(monkeypatch):
    """PTPU_PAGED_KERNEL forces the tier when callers use defaults;
    explicit flags still win."""
    rows = [(5, 1), (9, 4)]
    args, *_ = _ragged_case(rows, 4, 4, 8, 4, 4)
    ref = ragged_paged_attention_reference(*args)
    monkeypatch.setenv("PTPU_PAGED_KERNEL", "interpret")
    got = ragged_paged_attention(*args)      # defaults -> kernel interpret
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    monkeypatch.setenv("PTPU_PAGED_KERNEL", "reference")
    got = ragged_paged_attention(*args)
    np.testing.assert_allclose(got, ref, atol=0, rtol=0)
    monkeypatch.setenv("PTPU_PAGED_KERNEL", "bogus")
    with pytest.raises(ValueError, match="PTPU_PAGED_KERNEL"):
        ragged_paged_attention(*args)


# -- mixed precision: int8-resident blocks read in place -------------------

def _quantize_some_blocks(args, which="odd"):
    """Move a deterministic subset of referenced fp blocks into int8
    side pools and bias-encode their table entries (-slot-1). Returns
    (mixed_args, promoted_args): the same batch expressed as a mixed
    fp/int8 read and as the promote-then-step equivalent where each
    quantized block is dequantized back into the fp pool — the ISSUE's
    bar is that these two produce byte-identical output."""
    from paddle_tpu.quant.int8_compute import dequantize_block, \
        quantize_block
    (qf, kv_pool, bt, cl, qs, tr, to) = args
    k_pool, v_pool = unpack_kv(kv_pool, qf.shape[-1])
    bt = np.asarray(bt).copy()
    # referenced (row, j) entries with full blocks only: quantizing a
    # block that the row writes into would be invalid upstream, but at
    # kernel level any referenced block is fair game — pick by parity.
    picks = []
    seen = set()
    for i in range(bt.shape[0] - 1):
        blocks = -(-int(cl[i]) // k_pool.shape[1])
        for j in range(blocks):
            b = int(bt[i, j])
            if b in seen:
                continue
            seen.add(b)
            if (which == "odd" and j % 2 == 1) or which == "all":
                picks.append(b)
    kq, vq, ksc, vsc = [], [], [], []
    k_pro, v_pro = np.asarray(k_pool).copy(), np.asarray(v_pool).copy()
    slot_of = {}
    for b in picks:
        q1, s1 = quantize_block(k_pool[b][None])
        q2, s2 = quantize_block(v_pool[b][None])
        slot_of[b] = len(kq)
        kq.append(np.asarray(q1[0]))
        ksc.append(float(s1[0]))
        vq.append(np.asarray(q2[0]))
        vsc.append(float(s2[0]))
        k_pro[b] = np.asarray(dequantize_block(q1, s1, k_pool.dtype)[0])
        v_pro[b] = np.asarray(dequantize_block(q2, s2, v_pool.dtype)[0])
    if not picks:                     # degenerate: keep pools non-empty
        kq.append(np.zeros(k_pool.shape[1:], np.int8))
        vq.append(np.zeros(v_pool.shape[1:], np.int8))
        ksc.append(1.0)
        vsc.append(1.0)
    bt_mixed = bt.copy()
    for i in range(bt.shape[0]):
        for j in range(bt.shape[1]):
            b = int(bt[i, j])
            if b in slot_of:
                bt_mixed[i, j] = -(slot_of[b] + 1)
    qkw = dict(kvq_pool=jnp.asarray(pack_kv(np.stack(kq), np.stack(vq))),
               k_scales=jnp.asarray(ksc, jnp.float32),
               v_scales=jnp.asarray(vsc, jnp.float32),
               groups=qf.shape[1] * head_lanes(qf.shape[-1])
               // kv_pool.shape[-1])
    mixed = ((qf, kv_pool, jnp.asarray(bt_mixed), cl, qs, tr, to), qkw)
    promoted = ((qf, jnp.asarray(pack_kv(k_pro, v_pro)),
                 jnp.asarray(bt), cl, qs, tr, to), qkw)
    return mixed, promoted, len(picks)


@pytest.mark.parametrize("rows,h,hkv,d,bs,tq", RAGGED_MIXED_CASES)
def test_ragged_mixed_reference_bit_exact_vs_promote(rows, h, hkv, d,
                                                     bs, tq):
    """Direct int8 reads through the XLA reference == dequantize the
    same blocks into the fp pool first, BYTE-identical: the in-kernel
    dequant is the same f32 math as the promote path."""
    args, *_ = _ragged_case(rows, h, hkv, d, bs, tq)
    (margs, qkw), (pargs, _), n = _quantize_some_blocks(args)
    got = ragged_paged_attention_reference(*margs, **qkw)
    want = ragged_paged_attention_reference(*pargs, groups=h // hkv)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,h,hkv,d,bs,tq", RAGGED_MIXED_CASES)
def test_ragged_mixed_kernel_bit_exact_vs_promote(rows, h, hkv, d, bs, tq):
    """Same bar for the Pallas kernel (interpret mode): the mixed grid
    must reproduce the promote-then-fp-step kernel output bit-for-bit."""
    args, *_ = _ragged_case(rows, h, hkv, d, bs, tq)
    (margs, qkw), (pargs, _), n = _quantize_some_blocks(args)
    got = ragged_paged_attention(*margs, use_kernel=True, interpret=True,
                                 **qkw)
    want = ragged_paged_attention(*pargs, use_kernel=True, interpret=True,
                                  groups=h // hkv)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("which", ["odd", "all"])
def test_ragged_mixed_kernel_matches_reference(which):
    """Mixed kernel vs mixed reference at the usual numeric bar,
    including the all-int8 extreme."""
    rows = [(9, 9), (13, 5), (6, 1)]
    args, *_ = _ragged_case(rows, 4, 4, 8, 4, 4)
    (margs, qkw), _, n = _quantize_some_blocks(args, which=which)
    assert n > 0
    got = ragged_paged_attention(*margs, use_kernel=True, interpret=True,
                                 **qkw)
    want = ragged_paged_attention_reference(*margs, **qkw)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_ragged_fp_only_through_mixed_signature_bit_exact():
    """A batch with NO negative table entries through the mixed
    signature == the fp-only path, bit-for-bit, in both tiers — the
    engine always passes qpools once compression is on, so fp-only
    batches must not pay a numeric (or recompile) cost."""
    rows = [(7, 1), (10, 6), (4, 4)]
    args, *_ = _ragged_case(rows, 4, 4, 8, 4, 4)
    qkw = dict(kvq_pool=jnp.zeros((2,) + args[1].shape[1:], jnp.int8),
               k_scales=jnp.ones((2,), jnp.float32),
               v_scales=jnp.ones((2,), jnp.float32))
    ref_fp = ragged_paged_attention_reference(*args)
    ref_mx = ragged_paged_attention_reference(*args, **qkw)
    assert np.array_equal(np.asarray(ref_fp), np.asarray(ref_mx))
    ker_fp = ragged_paged_attention(*args, use_kernel=True, interpret=True)
    ker_mx = ragged_paged_attention(*args, use_kernel=True, interpret=True,
                                    **qkw)
    assert np.array_equal(np.asarray(ker_fp), np.asarray(ker_mx))


def test_env_override_dispatch_covers_mixed(monkeypatch):
    """PTPU_PAGED_KERNEL steers the mixed path through the same three
    tiers as the fp-only path."""
    rows = [(9, 9), (6, 1)]
    args, *_ = _ragged_case(rows, 4, 4, 8, 4, 4)
    (margs, qkw), _, n = _quantize_some_blocks(args)
    assert n > 0
    ref = ragged_paged_attention_reference(*margs, **qkw)
    monkeypatch.setenv("PTPU_PAGED_KERNEL", "interpret")
    got = ragged_paged_attention(*margs, **qkw)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    monkeypatch.setenv("PTPU_PAGED_KERNEL", "reference")
    got = ragged_paged_attention(*margs, **qkw)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


# -- a span of pool blocks a grid cell -------------------------------------

@pytest.mark.parametrize("bs,lanes,itemsize,mb,want", [
    (16, 2048, 2, 64, 8),     # gpt2m-chat: 128 keys, 512 KB a cell
    (16, 2560, 2, 64, 8),     # gpt2l-docs: 640 KB
    (128, 640, 2, 72, 4),     # glm47f-docs8k's latent pool: 512 keys
    (16, 512, 2, 64, 32),     # medium's tp=4 shard: 512 keys, 512 KB
    (16, 640, 2, 64, 32),     # large's tp=4 shard
    (512, 2048, 2, 16, 1),    # a block that is a cell's keys already
    (128, 4096, 2, 16, 1),    # ... or a cell's bytes
    (16, 2048, 2, 3, 2),      # never past the table
    (4, 128, 4, 10, 8),       # the tests' shapes: the table bounds it
], ids=["gpt2m", "gpt2l", "latent128", "gpt2m_tp4", "gpt2l_tp4",
        "keys_full", "bytes_full", "short_table", "tiny"])
def test_span_is_read_off_the_pool_shape(bs, lanes, itemsize, mb, want):
    assert ragged_span(bs, lanes, itemsize, mb) == want


# Rows that a span makes new, at 4-token blocks and 4-query tiles; with
# a span of 4 blocks (16 keys) they are: a context that ends in a
# span's first block (18), in its last (31), exactly on a span's edge
# (16, 32), a chunk whose tiles' causal edges fall inside a span (38/10:
# queries 28..37), a whole prompt of one span; the table is 10 wide, no
# multiple of 2 or 4. Pad tiles lie as the engine lays them, on the null
# row, which holds no key (context 0): two before the first row's tiles
# and, after each row's in turn, 0, 3, 1, 0 and 2, so a walked span's
# next copies jump over several to the next tile with work.
SPAN_ROWS = [(18, 1), (31, 1), (32, 1), (38, 10), (16, 16)]
PADS_AFTER = (0, 3, 1, 0, 2)


def _pads_between(args, spans, tq):
    """The flat packing of `_ragged_case` laid out anew with pad tiles
    between and after its rows' (PADS_AFTER; no row: pads alone).
    Returns (args, spans, the pad tiles)."""
    q, pool, bt, cl, qs, tr, to = args
    null = bt.shape[0] - 1
    order, moved = [None, None], []       # an old tile a new one; None a pad
    for (off, qlen), after in zip(spans, PADS_AFTER):
        moved.append((len(order) * tq, qlen))
        order += list(range(off // tq, off // tq + -(-qlen // tq)))
        order += [None] * after
    tiles = np.asarray(q).reshape(-1, tq, *q.shape[1:])
    q = np.concatenate([np.zeros_like(tiles[0]) if k is None else tiles[k]
                        for k in order])
    tr = [null if k is None else int(tr[k]) for k in order]
    to = [0 if k is None else int(to[k]) for k in order]
    cl = np.asarray(cl).copy()
    cl[null] = 0
    pads = [i for i, k in enumerate(order) if k is None]
    return ((jnp.asarray(q), pool, bt, jnp.asarray(cl), qs,
             jnp.asarray(tr, jnp.int32), jnp.asarray(to, jnp.int32)),
            moved, pads)


def _compacted(args, spans, bs, seed=5):
    """Block-sparse operands as `SparseAttention.ragged_step` builds
    them: a decode row reads a random few of its blocks (the first and
    its own always) through a compacted table, its context shortened to
    match and its mask all true; a chunk's queries each keep a random
    few blocks of the whole table (the first always). Returns (args,
    block_mask [T, MB])."""
    q, pool, bt, cl, qs, tr, to = args
    rng = np.random.default_rng(seed)
    bt, cl, qs = (np.asarray(a).copy() for a in (bt, cl, qs))
    tr = np.asarray(tr)
    tq = q.shape[0] // tr.shape[0]
    mask = rng.random((q.shape[0], bt.shape[1])) < 0.5
    mask[:, 0] = True
    for off, qlen in spans:
        if qlen != 1:
            continue
        i = int(tr[off // tq])
        ctx, n = int(cl[i]), -(-int(cl[i]) // bs)
        kept = sorted({0, n - 1} | set(np.flatnonzero(rng.random(n) < 0.4)))
        bt[i] = 0
        bt[i, :len(kept)] = np.asarray(args[2])[i, kept]
        cl[i] = (len(kept) - 1) * bs + ctx - (ctx - 1) // bs * bs
        qs[i] = cl[i] - 1
        mask[off:off + tq] = True
    return ((q, pool, jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(qs),
             jnp.asarray(tr), to), jnp.asarray(mask))


def _span_case(kind, span, monkeypatch):
    from paddle_tpu.kernels import paged_attention as pa
    bs, tq = 4, 4
    h, hkv, d = {"mha": (4, 4, 8), "gqa": (8, 2, 16), "mixed": (4, 4, 8),
                 "latent": (4, 1, 20), "window": (4, 4, 8),
                 "sparse": (4, 1, 8), "pads": (4, 4, 8)}[kind]
    args, *_, spans = _ragged_case(SPAN_ROWS, h, hkv, d, bs, tq)
    args, spans, pads = _pads_between(
        args, [] if kind == "pads" else spans, tq)
    kw = dict(groups=h // hkv)
    if kind == "latent":      # one row a token, its first 16 the value
        k_pool, _ = unpack_kv(args[1], d)
        args = (args[0], jnp.asarray(pack_latent(np.asarray(k_pool[:, :, 0]),
                                                 latent_lanes(d))), *args[2:])
        kw.update(value_lanes=(0, 16), scale=0.3)
    if kind == "window":      # the 38-token row's last tile sees 26 on
        kw.update(window=11)
    if kind == "sparse":
        args, mask = _compacted(args, spans, bs)
        kw.update(block_mask=mask)
    # S is read off the pool's shape: steered here, in the test, by the
    # keys a span may hold
    monkeypatch.setattr(pa, "_SPAN_KEYS", span * bs)
    assert args[2].shape[1] == 10
    assert pa.ragged_span(bs, args[1].shape[2], 4, 10) == span
    return args, kw, spans, pads


def _pad_rows(got, pads, tq):
    """The outputs of the pad tiles, which walk nothing."""
    return np.asarray(got).reshape(-1, tq, *got.shape[1:])[pads]


@pytest.mark.parametrize("span", [1, 2, 4, 8])
@pytest.mark.parametrize("kind", ["mha", "gqa", "latent", "window", "sparse",
                                  "pads"])
def test_ragged_kernel_over_spans_matches_reference(kind, span, monkeypatch):
    """The kernel in interpret mode against the XLA oracle where a span
    covers `span` blocks: 1 (a block already large), two that leave the
    table's tenth entry in a span of its own or in a short last span,
    and 8 (one short span and a second one mostly past the table). Pad
    tiles lie between and after the rows' (all of them under "pads")
    and write zeros; under a window a tile's walk starts at the span
    its first query's oldest key lies in (the 7th of 10 at a span of one
    block); under a block mask decode rows read compacted tables beside
    a chunk's masked full ones."""
    args, kw, spans, pads = _span_case(kind, span, monkeypatch)
    got = ragged_paged_attention(*args, use_kernel=True, interpret=True, **kw)
    want = ragged_paged_attention_reference(*args, **{"scale": None, **kw})
    assert bool(jnp.isfinite(got).all())    # pad queries/tiles stay finite
    assert pads and not _pad_rows(got, pads, 4).any()
    for off, qlen in spans:
        np.testing.assert_allclose(got[off:off + qlen], want[off:off + qlen],
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("span", [1, 2, 4, 8])
def test_ragged_mixed_kernel_over_spans(span, monkeypatch):
    """The int8 tier through the same spans: each block of a span is
    dequantized by its own entry's scales (odd table entries are int8
    here, so every span mixes tiers), at the oracle's tolerance and
    bit for bit against promoting the blocks first."""
    args, kw, spans, pads = _span_case("mixed", span, monkeypatch)
    (margs, qkw), (pargs, _), n = _quantize_some_blocks(args)
    assert n > 0
    got = ragged_paged_attention(*margs, use_kernel=True, interpret=True,
                                 **qkw)
    want = ragged_paged_attention_reference(*margs, **qkw)
    for off, qlen in spans:
        np.testing.assert_allclose(got[off:off + qlen], want[off:off + qlen],
                                   atol=1e-5, rtol=1e-5)
    promoted = ragged_paged_attention(*pargs, use_kernel=True,
                                      interpret=True, **kw)
    assert np.array_equal(np.asarray(got), np.asarray(promoted))
    assert not _pad_rows(got, pads, 4).any()
