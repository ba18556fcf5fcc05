"""Ring attention: sequence/context parallelism over the mesh "sp" axis.

This is the TPU-native long-context capability the reference lacks
(SURVEY.md §5.7 flags it as the north-star extension: the reference's
long-sequence story is LoD ragged batching only). Design follows the
ring-attention pattern: shard the sequence axis across devices; Q stays
resident; K/V blocks rotate around the ring via `ppermute` over ICI while
each device accumulates online-softmax partial results — full attention
semantics with O(T/sp) memory per device and compute/communication overlap.

Built on shard_map + lax.ppermute (the same collectives the reference's
NCCL op-handles map to, §5.8) — no custom comm backend needed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

NEG_INF = -1e30


def _block_attend(q, k, v, scale, causal, q_block_idx, k_block_idx,
                  block_len):
    """Partial attention of local q against one rotating k/v block.
    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]. Returns (m, l, acc) pieces.
    Global positions: q_pos = q_block_idx*block_len + i, likewise k."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_block_idx * block_len + jnp.arange(tq)
        kpos = k_block_idx * block_len + jnp.arange(tk)
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)              # [B,H,Tq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return m, l, acc


def ring_attention_inner(q_l, k_l, v_l, axis: str, sp: int,
                         scale: Optional[float] = None,
                         causal: bool = False):
    """The ring-attention body for callers ALREADY inside a shard_map
    whose mesh includes `axis` (e.g. a pipeline stage): q_l/k_l/v_l are
    the local [B, T/sp, H, D] sequence shards; K/V blocks rotate around
    the ring via ppermute while online-softmax partials merge. Returns
    the local output shard [B, T/sp, H, D]."""
    d = q_l.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    my = lax.axis_index(axis)
    block_len = q_l.shape[1]
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(step, carry):
        k_cur, v_cur, m, l, acc = carry
        # the block currently held arrived from (my - step) mod sp
        k_idx = (my - step) % sp
        bm, bl, bacc = _block_attend(q_l, k_cur, v_cur, scale, causal,
                                     my, k_idx, block_len)
        # online-softmax merge of (m,l,acc) with block partials
        m_new = jnp.maximum(m, bm)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(bm - m_new)
        l_new = l * c1 + bl * c2
        # acc layout [B,Tq,H,D]; coefficients are [B,H,Tq,1]
        def fix(c):
            return jnp.transpose(c, (0, 2, 1, 3))   # -> [B,Tq,H,1]
        acc_new = acc * fix(c1).astype(acc.dtype) \
            + bacc * fix(c2).astype(acc.dtype)
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        return k_nxt, v_nxt, m_new, l_new, acc_new

    b, tq, h, _ = q_l.shape
    m0 = jnp.full((b, h, tq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq, 1), jnp.float32)
    a0 = jnp.zeros_like(q_l, shape=(b, tq, h, d))
    _, _, m, l, acc = lax.fori_loop(
        0, sp, body, (k_l, v_l, m0, l0, a0))
    denom = jnp.transpose(jnp.maximum(l, 1e-30), (0, 2, 1, 3))
    return (acc / denom.astype(acc.dtype)).astype(q_l.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   scale: Optional[float] = None, causal: bool = False):
    """Full attention over sequence sharded on `axis`.

    q/k/v: global [B, T, H, D] arrays (sharded or shardable on T). Returns
    [B, T, H, D] with the same sharding. Must be called under jit (it uses
    shard_map internally; `ring_attention_inner` is the body, reusable
    from other shard_map contexts such as pipeline stages).
    """
    sp = mesh.shape[axis]
    spec = P(None, axis, None, None)

    def local_fn(q_l, k_l, v_l):
        return ring_attention_inner(q_l, k_l, v_l, axis, sp,
                                    scale=scale, causal=causal)

    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Ring x flash: each ring block runs the Pallas flash kernel (VMEM-bounded
# score blocks) instead of a dense einsum, merged across ring steps in
# (o, lse) space. Backward is a ring-level custom_vjp that replays the
# rotation and calls the flash backward kernels per block with the GLOBAL
# lse — p_blk = exp(s_blk - lse_global) is exactly the full softmax
# restricted to the block, so per-block dq/dk/dv sum to the true gradient.
#
# Causal load balance: with contiguous sharding, device j skips ring steps
# s > j entirely (half the ring idles). `zigzag=True` assigns each device
# the chunk pair (j, 2*sp-1-j) — every device then computes exactly one
# full half-block plus the diagonal work per step, the standard zig-zag
# schedule. Helpers zigzag_shard/zigzag_unshard reorder the sequence.
# ---------------------------------------------------------------------------

def _to_bhtd(x):
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _from_bhtd(x, b, h):
    bh, t, d = x.shape
    return jnp.transpose(x.reshape(b, h, t, d), (0, 2, 1, 3))


def _divisor_block(t: int, cap: int) -> int:
    """Largest divisor of t that is <= cap, preferring lane-aligned
    (multiple-of-8) divisors. The flash kernels require T to be an exact
    multiple of the block size (Pallas clamps a ragged tail block's start,
    silently overlapping the previous block), and the ring path calls the
    kernels directly without flash_attention's pad+mask treatment — so
    blocks must divide the local length exactly."""
    divs = set()
    for d in range(1, int(t ** 0.5) + 1):
        if t % d == 0:
            divs.add(d)
            divs.add(t // d)
    ok = [c for c in divs if c <= cap]
    aligned = [c for c in ok if c % 8 == 0]
    return max(aligned) if aligned else max(ok)


def _blk_sizes(t_q, t_k, interpret: bool):
    from paddle_tpu.kernels import flash as FL
    if interpret:
        cq, ck = 128, 128       # CPU-test interpret cost scales with area
    else:
        cq, ck = FL._default_blocks(t_q, t_k)
    return _divisor_block(t_q, cq), _divisor_block(t_k, ck)


def _flash_block_fwd(q, k, v, scale, causal, interpret):
    """One ring block via the flash forward kernel.
    q/k/v: [B, T, H, D] -> (o [B,T,H,D] f32-accurate, lse [BH, T, 1])."""
    from paddle_tpu.kernels import flash as FL
    b, t_q, h, d = q.shape
    bq, bk = _blk_sizes(t_q, k.shape[1], interpret)
    o, lse = FL._fwd(_to_bhtd(q), _to_bhtd(k), _to_bhtd(v), None, None,
                     None, scale, causal, None, bq, bk, interpret,
                     want_lse=True, dropout_rate=0.0, heads=h)
    return _from_bhtd(o, b, h), lse[:, :, :1]


def _flash_block_bwd(q, k, v, o, lse_lanes, do, scale, causal, interpret):
    """Flash backward kernels for one (q, k-block) pair given GLOBAL o/lse.
    All [B, T, H, D]; lse_lanes [BH, T, LANES]. Returns dq, dk, dv."""
    from paddle_tpu.kernels import flash as FL
    b, t_q, h, d = q.shape
    bq, bk = _blk_sizes(t_q, k.shape[1], interpret)
    dq, dk, dv = FL._bwd_impl(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), _to_bhtd(o), lse_lanes,
        _to_bhtd(do), None, None, None, scale, causal, None, bq, bk,
        interpret, 0.0, h)
    return (_from_bhtd(dq, b, h), _from_bhtd(dk, b, h),
            _from_bhtd(dv, b, h))


def _merge(acc_o, acc_lse, o_blk, lse_blk):
    """Combine normalized partial attentions: weights exp(lse - max)."""
    m = jnp.maximum(acc_lse, lse_blk)
    w1 = jnp.exp(acc_lse - m)                     # [BH, T, 1]
    w2 = jnp.exp(lse_blk - m)
    denom = jnp.maximum(w1 + w2, 1e-30)

    def btH(w, like):
        # [BH, T, 1] weight -> [B, T, H, 1] matching the o layout
        b_, t, h, _ = like.shape
        return jnp.transpose(w.reshape(b_, h, t, 1), (0, 2, 1, 3))

    new_o = (acc_o * btH(w1, acc_o) + o_blk * btH(w2, acc_o)) \
        / btH(denom, acc_o)
    new_lse = m + jnp.log(denom)
    return new_o, new_lse


def zigzag_shard(x, sp: int, axis: int = 1):
    """Reorder the sequence so contiguous device chunks hold the zig-zag
    pair (j, 2*sp-1-j). x: [..., T, ...] with T % (2*sp) == 0."""
    t = x.shape[axis]
    chunk = t // (2 * sp)
    order = []
    for j in range(sp):
        order.extend([j, 2 * sp - 1 - j])
    idx = jnp.concatenate([jnp.arange(c * chunk, (c + 1) * chunk)
                           for c in order])
    return jnp.take(x, idx, axis=axis)


def zigzag_unshard(x, sp: int, axis: int = 1):
    """Inverse of zigzag_shard."""
    t = x.shape[axis]
    chunk = t // (2 * sp)
    order = []
    for j in range(sp):
        order.extend([j, 2 * sp - 1 - j])
    inv = np.argsort(np.asarray(order))
    idx = jnp.concatenate([jnp.arange(int(c) * chunk, (int(c) + 1) * chunk)
                           for c in inv])
    return jnp.take(x, idx, axis=axis)


def ring_flash_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                         scale: Optional[float] = None,
                         causal: bool = False, zigzag: bool = False,
                         interpret: Optional[bool] = None):
    """Ring attention with per-block Pallas flash kernels.

    q/k/v: [B, T, H, D] sharded (or shardable) on T over `axis`. With
    `zigzag=True` (causal only), callers must pass zigzag_shard'ed inputs
    (and unshard the output) — chunk pairing balances causal work across
    the ring. Differentiable (ring-level custom_vjp).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    sp = mesh.shape[axis]
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    spec = P(None, axis, None, None)
    if zigzag and not causal:
        raise ValueError("zigzag sharding only applies to causal attention")

    from paddle_tpu.kernels.flash import LANES

    fwd_perm = [(i, (i + 1) % sp) for i in range(sp)]

    def local_fn(q_l, k_l, v_l):

        @functools.partial(jax.custom_vjp)
        def ring_core(q_l, k_l, v_l):
            return _ring_fwd(q_l, k_l, v_l)[0]

        def _visible(step, half):
            # computed fresh per use: custom_vjp rules must not close over
            # tracers, and axis_index is a tracer inside shard_map
            my = lax.axis_index(axis)
            # contiguous: visible iff my >= step (diag handled causally)
            # zigzag halves: a-half visible iff my >= step; b-half iff
            # my < step (see schedule derivation in module docstring)
            if half == "a":
                return my >= step
            if half == "b":
                return my < step
            return None

        def _ring_fwd(q_l, k_l, v_l):
            b, t_l, h, _ = q_l.shape
            bh = b * h
            neg = jnp.full((bh, t_l, 1), NEG_INF, jnp.float32)
            acc_o = jnp.zeros(q_l.shape, jnp.float32)
            acc_lse = neg
            k_cur, v_cur = k_l, v_l
            if zigzag:
                half = t_l // 2
                qa, qb = q_l[:, :half], q_l[:, half:]
                acc = {"oa": jnp.zeros(qa.shape, jnp.float32),
                       "la": jnp.full((bh, half, 1), NEG_INF, jnp.float32),
                       "ob": jnp.zeros(qa.shape, jnp.float32),
                       "lb": jnp.full((bh, half, 1), NEG_INF, jnp.float32)}
                for step in range(sp):
                    ka, kb = k_cur[:, :half], k_cur[:, half:]
                    va, vb = v_cur[:, :half], v_cur[:, half:]
                    if step == 0:
                        o1, l1 = _flash_block_fwd(qa, ka, va, scale, True,
                                                  interpret)
                        acc["oa"], acc["la"] = _merge(acc["oa"], acc["la"],
                                                      o1, l1)
                        o2, l2 = _flash_block_fwd(qb, kb, vb, scale, True,
                                                  interpret)
                        acc["ob"], acc["lb"] = _merge(acc["ob"], acc["lb"],
                                                      o2, l2)
                        o3, l3 = _flash_block_fwd(qb, ka, va, scale, False,
                                                  interpret)
                        acc["ob"], acc["lb"] = _merge(acc["ob"], acc["lb"],
                                                      o3, l3)
                    else:
                        # balanced step: device does exactly one of
                        # full(qa, ka) [my >= step] or full(qb, kb)
                        # [my < step] — select BOTH sides of the pair
                        vis_a = _visible(step, "a")
                        q_sel = jnp.where(vis_a, qa, qb)
                        k_sel = jnp.where(vis_a, ka, kb)
                        v_sel = jnp.where(vis_a, va, vb)
                        o1, l1 = _flash_block_fwd(q_sel, k_sel, v_sel,
                                                  scale, False, interpret)
                        # merge into the selected q half only
                        na, nla = _merge(acc["oa"], acc["la"], o1, l1)
                        nb, nlb = _merge(acc["ob"], acc["lb"], o1, l1)
                        acc["oa"] = jnp.where(vis_a, na, acc["oa"])
                        acc["la"] = jnp.where(vis_a, nla, acc["la"])
                        acc["ob"] = jnp.where(vis_a, acc["ob"], nb)
                        acc["lb"] = jnp.where(vis_a, acc["lb"], nlb)
                        o3, l3 = _flash_block_fwd(qb, ka, va, scale, False,
                                                  interpret)
                        acc["ob"], acc["lb"] = _merge(acc["ob"], acc["lb"],
                                                      o3, l3)
                    if step != sp - 1:
                        k_cur = lax.ppermute(k_cur, axis, fwd_perm)
                        v_cur = lax.ppermute(v_cur, axis, fwd_perm)
                acc_o = jnp.concatenate([acc["oa"], acc["ob"]], axis=1)
                acc_lse = jnp.concatenate([acc["la"], acc["lb"]], axis=1)
            else:
                for step in range(sp):
                    if causal:
                        if step == 0:
                            o_blk, lse_blk = _flash_block_fwd(
                                q_l, k_cur, v_cur, scale, True, interpret)
                        else:
                            o_blk, lse_blk = _flash_block_fwd(
                                q_l, k_cur, v_cur, scale, False, interpret)
                            vis = _visible(step, "a")
                            lse_blk = jnp.where(vis, lse_blk, NEG_INF)
                    else:
                        o_blk, lse_blk = _flash_block_fwd(
                            q_l, k_cur, v_cur, scale, False, interpret)
                    acc_o, acc_lse = _merge(acc_o, acc_lse, o_blk, lse_blk)
                    if step != sp - 1:
                        k_cur = lax.ppermute(k_cur, axis, fwd_perm)
                        v_cur = lax.ppermute(v_cur, axis, fwd_perm)
            out = acc_o.astype(q_l.dtype)
            return out, (q_l, k_l, v_l, out, acc_lse)

        def _ring_fwd_rule(q_l, k_l, v_l):
            out, res = _ring_fwd(q_l, k_l, v_l)
            return out, res

        def _ring_bwd_rule(res, do):
            q_l, k_l, v_l, out, lse = res
            b, t_l, h, _ = q_l.shape
            lse_lanes = jnp.broadcast_to(lse, lse.shape[:2] + (LANES,))
            dq = jnp.zeros(q_l.shape, jnp.float32)
            k_cur, v_cur = k_l, v_l
            dk_cur = jnp.zeros(k_l.shape, jnp.float32)
            dv_cur = jnp.zeros(v_l.shape, jnp.float32)
            if zigzag:
                half = t_l // 2
                qa, qb = q_l[:, :half], q_l[:, half:]
                oa, ob = out[:, :half], out[:, half:]
                doa, dob = do[:, :half], do[:, half:]
                la = lse_lanes[:, :half]
                lb = lse_lanes[:, half:]
                for step in range(sp):
                    ka, kb = k_cur[:, :half], k_cur[:, half:]
                    va, vb = v_cur[:, :half], v_cur[:, half:]
                    dka, dkb = dk_cur[:, :half], dk_cur[:, half:]
                    dva, dvb = dv_cur[:, :half], dv_cur[:, half:]
                    if step == 0:
                        g1 = _flash_block_bwd(qa, ka, va, oa, la, doa,
                                              scale, True, interpret)
                        g2 = _flash_block_bwd(qb, kb, vb, ob, lb, dob,
                                              scale, True, interpret)
                        g3 = _flash_block_bwd(qb, ka, va, ob, lb, dob,
                                              scale, False, interpret)
                        dq = dq.at[:, :half].add(g1[0])
                        dq = dq.at[:, half:].add(g2[0] + g3[0])
                        dka = dka + g1[1] + g3[1]
                        dva = dva + g1[2] + g3[2]
                        dkb = dkb + g2[1]
                        dvb = dvb + g2[2]
                    else:
                        vis_a = _visible(step, "a")
                        q_sel = jnp.where(vis_a, qa, qb)
                        k_sel = jnp.where(vis_a, ka, kb)
                        v_sel = jnp.where(vis_a, va, vb)
                        o_sel = jnp.where(vis_a, oa, ob)
                        do_sel = jnp.where(vis_a, doa, dob)
                        l_sel = jnp.where(vis_a, la, lb)
                        g1 = _flash_block_bwd(q_sel, k_sel, v_sel, o_sel,
                                              l_sel, do_sel, scale, False,
                                              interpret)
                        dq = dq.at[:, :half].add(
                            jnp.where(vis_a, g1[0], 0.0))
                        dq = dq.at[:, half:].add(
                            jnp.where(vis_a, 0.0, g1[0]))
                        dka = dka + jnp.where(vis_a, g1[1], 0.0)
                        dkb = dkb + jnp.where(vis_a, 0.0, g1[1])
                        dva = dva + jnp.where(vis_a, g1[2], 0.0)
                        dvb = dvb + jnp.where(vis_a, 0.0, g1[2])
                        g3 = _flash_block_bwd(qb, ka, va, ob, lb, dob,
                                              scale, False, interpret)
                        dq = dq.at[:, half:].add(g3[0])
                        dka = dka + g3[1]
                        dva = dva + g3[2]
                    dk_cur = jnp.concatenate([dka, dkb], axis=1)
                    dv_cur = jnp.concatenate([dva, dvb], axis=1)
                    if step != sp - 1:
                        k_cur = lax.ppermute(k_cur, axis, fwd_perm)
                        v_cur = lax.ppermute(v_cur, axis, fwd_perm)
                        dk_cur = lax.ppermute(dk_cur, axis, fwd_perm)
                        dv_cur = lax.ppermute(dv_cur, axis, fwd_perm)
            else:
                for step in range(sp):
                    is_diag = causal and step == 0
                    g = _flash_block_bwd(q_l, k_cur, v_cur, out, lse_lanes,
                                         do, scale, is_diag, interpret)
                    if causal and step > 0:
                        vis = (_visible(step, "a")).astype(jnp.float32)
                        g = tuple(x * vis for x in g)
                    dq = dq + g[0]
                    dk_cur = dk_cur + g[1]
                    dv_cur = dv_cur + g[2]
                    if step != sp - 1:
                        k_cur = lax.ppermute(k_cur, axis, fwd_perm)
                        v_cur = lax.ppermute(v_cur, axis, fwd_perm)
                        dk_cur = lax.ppermute(dk_cur, axis, fwd_perm)
                        dv_cur = lax.ppermute(dv_cur, axis, fwd_perm)
            # after sp-1 rotations the k/dk buffers sit one hop short of
            # home; one more hop completes the cycle
            dk_cur = lax.ppermute(dk_cur, axis, fwd_perm)
            dv_cur = lax.ppermute(dv_cur, axis, fwd_perm)
            return (dq.astype(q_l.dtype), dk_cur.astype(k_l.dtype),
                    dv_cur.astype(v_l.dtype))

        ring_core.defvjp(_ring_fwd_rule, _ring_bwd_rule)
        return ring_core(q_l, k_l, v_l)

    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses-style sequence parallelism (SURVEY §7 M8 "head-sharding
# alternative"): instead of rotating K/V around a ring, all_to_alls
# reshape the sharding — tokens-sharded [B, T/sp, H, D] becomes
# heads-sharded [B, T, H/sp, D], each device runs FULL attention over its
# head group (flash kernel, no cross-device softmax state), and the
# output is all_to_all'd back. Communication is 4 all_to_alls of the
# activations (q/k/v in, o out) vs the ring's sp-1 K/V ppermutes; sp must
# divide the head count. Preferable to the ring when heads >= sp and the
# full sequence fits per-device memory after head partitioning.
# ---------------------------------------------------------------------------

def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      scale: Optional[float] = None, causal: bool = False,
                      interpret: Optional[bool] = None):
    """All-to-all sequence parallelism. q/k/v: [B, T, H, D] sharded on T
    over `axis`; H % mesh.shape[axis] == 0. Returns [B, T, H, D] with the
    same sharding. Differentiable (all_to_all is linear; jax autodiff
    transposes it)."""
    d = q.shape[-1]
    h = q.shape[2]
    sp = mesh.shape[axis]
    if h % sp != 0:
        raise ValueError(f"heads {h} not divisible by sp axis {sp}")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    spec = P(None, axis, None, None)

    def local_fn(q_l, k_l, v_l):
        # [B, T/sp, H, D] -> all_to_all over heads -> [B, T, H/sp, D]
        def seq_to_heads(x):
            # split heads into sp groups along axis 2, concat seq chunks
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def heads_to_seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        qh = seq_to_heads(q_l)          # [B, T, H/sp, D]
        kh = seq_to_heads(k_l)
        vh = seq_to_heads(v_l)
        from paddle_tpu.kernels import flash as FL
        t = qh.shape[1]
        bq, bk = _blk_sizes(t, t, interpret)
        b, _, hh, _ = qh.shape
        o = FL._flash_core(_to_bhtd(qh), _to_bhtd(kh), _to_bhtd(vh),
                           None, None, None, scale, causal, None, bq, bk,
                           interpret, 0.0, hh)
        o = _from_bhtd(o, b, hh)
        return heads_to_seq(o)          # [B, T/sp, H, D]

    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
