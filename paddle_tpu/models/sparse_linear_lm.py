"""A decoder whose layers are of two kinds, read a layer from the
configuration's `mixer_types`, beside `CausalLM`, `LatentMoELM` and
`HybridLM`: block-sparse softmax attention and lightning linear
attention (MiniCPM4 / InfLLM-v2, arXiv:2506.07900; Lightning
Attention-2, arXiv:2401.04658).

    x0 = scale_emb . E[tok]
    h = x + c . mixer_i(RMSNorm(x));  x = h + c . FFN(RMSNorm(h))
    c = scale_depth / sqrt(depth_base)
    logits = W_head (RMSNorm(x) / (model_dim / dim_model_base))

RMSNorm with a learned scale, FFN(y) = W2 (up . silu(gate)) with
[gate | up] = W1 y, no biases, an untied head. The mixer of layer i:

- "minicpm4": q = W_q y (H heads), [k | v] = W_kv y (Hkv heads),
  RMSNorm over each head of q and k with a learned scale, no positions,
  causal softmax attention at 1/sqrt(hd), query heads g.G .. g.G+G-1
  over kv head g; o . sigmoid(W_gate y), then W_o. WHICH keys a query at
  position t sees once t >= dense_len (below it: all): with compressed
  keys K~_j = mean(k[s j : s j + 2 s]) over the windows complete at t,
  p = softmax_j(q . K~_j / sqrt(hd)) a query head, summed over a kv
  group's heads; a block of `block` tokens scores the maximum of p over
  the windows that overlap it; kept are the first `init_blocks` blocks,
  the blocks that hold the `local` newest positions, and of the rest the
  `topk` best. The softmax runs over the kept blocks' keys only.
- "lightning-attn": [q | k | v] = W y (H_l heads), RMSNorm over each
  head of q and k, rotary over the whole head (rotate-half),
  S_t = lambda_h S_{t-1} + k_t^T v_t from 0, o_t = (q_t / sqrt(hd)) S_t,
  lambda_h = exp(-s_h (1 - l / (depth_base - 1) + 1e-5)),
  s_h = 2^(-8 (h + 1) / H_l), l the layer's index in the published
  stack (`first_layer` + i); RMSNorm over the concatenated heads, then
  . sigmoid(W_gate y), then W_o.

Served through the engine's one ragged step, the model DECLARES what
each layer keeps (`cache_layout`, ENGINE.md "Cache kinds"): a sparse
layer one paged pool a kv head (the heads select apart, so each is read
through its own table) and an INDEX pool under the same block tables,
one row of compressed keys a `stride` tokens, written as windows
complete; a lightning layer recurrent state, [H_l, hd, hd] float32 a
slot. A decode row past `dense_len` reads its kept blocks through a
compacted table; a chunk's rows, whose queries select apart, read the
whole table under a block mask (kernels/paged_attention.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import lightning_attention as lightning
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.models.shared_layers import PackedGatedFFN, dense, rotate
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, RMSNorm

KINDS = ("minicpm4", "lightning-attn")
NEG = -1e30


def block_scores(p, rows: int):
    """p [..., J'] by logical index row (window j lies at j' = j + 1,
    `rows` a block) -> [..., J' / rows]: a block's maximum over the
    windows that overlap it, its own rows and the next block's first."""
    view = p.reshape(p.shape[:-1] + (-1, rows))
    nxt = jnp.concatenate([view[..., 1:, 0], jnp.zeros_like(view[..., :1, 0])],
                          axis=-1)
    return jnp.maximum(view.max(axis=-1), nxt)


def kept_blocks(score, t, sel: dict):
    """Which blocks the query at position t keeps: score [..., NB] (a
    block's, higher is better), t [...] int32. Bool [..., NB]."""
    nb = score.shape[-1]
    b = jnp.arange(nb, dtype=jnp.int32)
    t = t[..., None]
    cur = t // sel["block"]
    lo = jnp.maximum(t - (sel["local"] - 1), 0) // sel["block"]
    local = (b >= lo) & (b <= cur)
    init = b < sel["init_blocks"]
    rest = (b < lo) & ~init
    s = jnp.where(rest, score, -jnp.inf)
    k = sel["topk"]
    if nb < k:
        s_k = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, k - nb)],
                      constant_values=-jnp.inf)
    else:
        s_k = s
    kth = jax.lax.top_k(s_k, k)[0][..., -1:]
    keep = init | local | (rest & (s >= kth))
    return jnp.where(t < sel["dense_len"], b <= cur, keep)


def window_limit(t, sel: dict):
    """The last logical index row (j' = j + 1) complete at position t:
    window j ends at stride . j + kernel - 1 <= t."""
    return (t + 1 - sel["kernel"]) // sel["stride"] + 1


class SparseAttention(Module):
    """The block-sparse mixer. `kv_row` is what one pool's row holds:
    one kv head."""

    def __init__(self, model_dim, num_heads, num_kv_heads, head_dim, sel,
                 eps, dtype, param_dtype):
        super().__init__()
        self.model_dim, self.num_heads = model_dim, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.groups = num_heads // num_kv_heads
        self.sel = dict(sel)
        self.dtype, self.param_dtype = dtype, param_dtype
        self.scale = 1.0 / math.sqrt(head_dim)
        self.rows = sel["block"] // sel["stride"]     # index rows a block
        self.q_norm = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)
        self.k_norm = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)

    def _project(self, cx: Context, y):
        """y [..., d] -> q [..., H, hd], k, v [..., Hkv, hd] (q and k
        normed), the gate [..., H hd]."""
        hd, h, kvh = self.head_dim, self.num_heads, self.num_kv_heads
        lead = y.shape[:-1]
        q = dense(cx, "q", y, h * hd, self.dtype, self.param_dtype)
        kv = dense(cx, "kv", y, 2 * kvh * hd, self.dtype, self.param_dtype)
        q = self.q_norm(cx, q.reshape(lead + (h, hd)))
        k = self.k_norm(cx, kv[..., :kvh * hd].reshape(lead + (kvh, hd)))
        v = kv[..., kvh * hd:].reshape(lead + (kvh, hd))
        gate = dense(cx, "gate", y, h * hd, self.dtype, self.param_dtype)
        return q, k, v, gate

    def _finish(self, cx: Context, att, gate):
        o = att.reshape(gate.shape).astype(self.dtype) * jax.nn.sigmoid(gate)
        return dense(cx, "o", o, self.model_dim, self.dtype,
                     self.param_dtype)

    def _selection(self, q, kbar, t, limit):
        """q [N, TQ, H, hd]; kbar [N, J', Hkv, hd], a tile's compressed
        keys by logical index row; t [N, TQ] query positions. Bool
        [N, Hkv, TQ, NB]: the blocks each query keeps, a kv head."""
        n, tq = t.shape
        g, kvh = self.groups, self.num_kv_heads
        qg = q.reshape(n, tq, kvh, g, self.head_dim)
        s = jnp.einsum("nqkgd,njkd->nkqgj", qg, kbar,
                       preferred_element_type=jnp.float32) * self.scale
        j = jnp.arange(kbar.shape[1], dtype=jnp.int32)
        there = (j >= 1) & (j <= jnp.minimum(window_limit(t, self.sel),
                                              limit)[..., None])  # [N,TQ,J']
        there = there[:, None, :, None, :]
        s = jnp.where(there, s, NEG)
        p = jnp.where(there, jax.nn.softmax(s, axis=-1), 0.0).sum(axis=3)
        return kept_blocks(block_scores(p, self.rows), t[:, None, :],
                           self.sel)

    def forward(self, cx: Context, y):
        """Whole sequences y [B, T, d] from position 0: the published
        form as masks over the full score matrix."""
        with jax.named_scope("sparse_attention"):
            q, k, v, gate = self._project(cx, y)
            b, t = y.shape[:2]
            sel, g = self.sel, self.groups
            stride, kern = sel["stride"], sel["kernel"]
            pos = jnp.arange(t, dtype=jnp.int32)
            nb = -(-t // sel["block"])
            jn = nb * self.rows              # logical index rows, j' = j + 1
            kf = k.astype(jnp.float32)
            starts = (jnp.arange(jn) - 1) * stride
            ok = (starts >= 0) & (starts + kern <= t)
            at = jnp.clip(starts[:, None] + jnp.arange(kern)[None, :], 0,
                          t - 1)
            kbar = jnp.where(ok[None, :, None, None],
                             kf[:, at].mean(axis=2), 0.0).astype(self.dtype)
            keep = self._selection(
                q, kbar, jnp.broadcast_to(pos, (b, t)),
                jnp.asarray(jn, jnp.int32))               # [B, Hkv, T, NB]
            seen = jnp.repeat(keep, sel["block"], axis=-1)[..., :t] \
                & (pos[None, :] <= pos[:, None])
            qg = q.reshape(b, t, self.num_kv_heads, g, self.head_dim)
            s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, k,
                           preferred_element_type=jnp.float32) * self.scale
            a = jax.nn.softmax(jnp.where(seen[:, :, None], s, -jnp.inf),
                               axis=-1)
            att = jnp.einsum("bkgqj,bjkd->bqkgd", a.astype(v.dtype), v)
            return self._finish(cx, att, gate)

    def ragged_step(self, cx: Context, y, pools, index, pk, batch):
        """y [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`); `pools` this layer's paged pools,
        one a kv head; `index` its index pool; `pk` what the selection
        needs of the packing (`SparseLinearLM._packing`), over which the
        selection and the kernel run. Returns (output, pools, index)."""
        sel, hd, g = self.sel, self.head_dim, self.groups
        q, k, v, gate = self._project(cx, y)
        nt, tq = batch.tile_rows.shape[0], batch.tq
        t = nt * tq
        pools = [paged.write_kv(pool, batch.slots, k[:, i:i + 1],
                                v[:, i:i + 1])
                 for i, pool in enumerate(pools)]
        q = batch.packing.expand(q)
        with jax.named_scope("sparse_select"):
            # the windows this step completes: their keys' mean, from the
            # pool (the chunk's own rows are in it now), a kv head. A
            # window's keys lie in two blocks at most: whole blocks are
            # gathered and weighed, never single rows
            # (whole rows too: the mean runs over the values' lanes as
            # well and they are dropped after it, so that the gather
            # takes blocks as they lie and the pool is not laid out anew)
            rows = jnp.concatenate(
                [jnp.einsum("wbr,wbrd->wd", pk["win_weights"],
                            pool[pk["win_blocks"]].astype(jnp.float32)
                            )[:, :hd] for pool in pools],
                axis=-1)                                   # [W, Hkv hd]
            flat = index.reshape(-1, index.shape[-1])
            index = flat.at[pk["win_dest"]].set(
                rows.astype(index.dtype)).reshape(index.shape)
            # each tile's compressed keys by logical index row
            kbar = index[pk["tile_tables"]].reshape(
                nt, -1, self.num_kv_heads, hd)
            keep = self._selection(q.reshape(nt, tq, self.num_heads, hd),
                                   kbar, pk["tile_pos"], pk["tile_limit"])
        outs = []
        with jax.named_scope("sparse_attention"):
            for i, pool in enumerate(pools):
                mine = keep[:, i]                          # [NT, TQ, NB]
                # a decode row past dense_len: its kept blocks in order,
                # the context shortened to match
                first = mine[pk["first_tile"], 0]          # [R, NB]
                order = jnp.argsort(~first, axis=-1, stable=True)
                count = first.sum(axis=-1).astype(jnp.int32)
                packed = jnp.where(
                    jnp.arange(first.shape[-1])[None, :] < count[:, None],
                    jnp.take_along_axis(batch.block_tables, order, axis=-1),
                    0)
                short = pk["compact"]
                ctx = batch.context_lens
                held = (count - 1) * sel["block"] \
                    + ctx - (ctx - 1) // sel["block"] * sel["block"]
                table = jnp.where(short[:, None], packed, batch.block_tables)
                ctx_i = jnp.where(short, held, ctx)
                starts = jnp.where(short, held - 1, batch.q_starts)
                mask = mine.reshape(t, -1) | short[pk["row_of"]][:, None]
                outs.append(paged.ragged_paged_attention(
                    q[:, i * g:(i + 1) * g], pool, table, ctx_i, starts,
                    batch.tile_rows, batch.tile_offs, scale=self.scale,
                    groups=g, block_mask=mask,
                    name="ragged_sparse_attention"))
            att = jnp.concatenate(outs, axis=1)            # [T, H, hd]
            out = self._finish(cx, batch.packing.compact(att), gate)
        return out, pools, index


class LightningAttention(Module):
    """The linear-attention mixer. `state_shapes` is what one sequence
    keeps."""

    def __init__(self, model_dim, num_heads, head_dim, layer: int,
                 depth_base: int, theta, eps, dtype, param_dtype):
        super().__init__()
        self.model_dim, self.num_heads = model_dim, num_heads
        self.head_dim, self.theta = head_dim, float(theta)
        self.dtype, self.param_dtype = dtype, param_dtype
        self.scale = 1.0 / math.sqrt(head_dim)
        slopes = [2.0 ** (-8.0 * (h + 1) / num_heads)
                  for h in range(num_heads)]
        self.log_decay = tuple(
            -s * (1.0 - layer / (depth_base - 1) + 1e-5) for s in slopes)
        self.state_shapes = (
            ("state", (num_heads, head_dim, head_dim),
             jnp.dtype(jnp.float32)),)
        self.q_norm = RMSNorm(eps, dtype=jnp.float32, param_dtype=param_dtype)
        self.k_norm = RMSNorm(eps, dtype=jnp.float32, param_dtype=param_dtype)
        self.out_norm = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)

    def _project(self, cx: Context, y, positions):
        """y [..., T, d] -> q (scaled), k, v [..., T, H, hd] float32,
        the gate."""
        h, hd = self.num_heads, self.head_dim
        lead = y.shape[:-1]
        qkv = dense(cx, "qkv", y, 3 * h * hd, self.dtype, self.param_dtype)
        q, k, v = (qkv[..., i * h * hd:(i + 1) * h * hd].reshape(
            lead + (h, hd)) for i in range(3))
        q = rotate(self.q_norm(cx, q), positions, self.theta) * self.scale
        k = rotate(self.k_norm(cx, k), positions, self.theta)
        gate = dense(cx, "gate", y, h * hd, self.dtype, self.param_dtype)
        return q, k, v.astype(jnp.float32), gate

    def _finish(self, cx: Context, o, gate):
        o = self.out_norm(cx, o.reshape(gate.shape))
        return dense(cx, "o", o * jax.nn.sigmoid(gate), self.model_dim,
                     self.dtype, self.param_dtype)

    def forward(self, cx: Context, y):
        """Whole sequences y [B, T, d] from position 0."""
        with jax.named_scope("lightning_attention"):
            b, t = y.shape[:2]
            q, k, v, gate = self._project(
                cx, y, jnp.broadcast_to(jnp.arange(t), (b, t)))
            lam = jnp.exp(jnp.asarray(self.log_decay, jnp.float32)
                          )[None, :, None, None]

            def step(s, x):
                q_t, k_t, v_t = x                          # [B, H, hd]
                s = lam * s + k_t[..., :, None] * v_t[..., None, :]
                return s, jnp.sum(q_t[..., :, None] * s, axis=-2)

            s0 = jnp.zeros((b, self.num_heads, self.head_dim,
                            self.head_dim), jnp.float32)
            _, o = jax.lax.scan(step, s0, tuple(
                jnp.swapaxes(x, 0, 1) for x in (q, k, v)))
            return self._finish(cx, jnp.swapaxes(o, 0, 1), gate)

    def ragged_step(self, cx: Context, y, state, meta, batch):
        """y [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`; `meta` its `tile_meta`); the
        kernel runs over the flat packing. Returns (output, new
        state)."""
        with jax.named_scope("lightning_attention"):
            q, k, v, gate = self._project(cx, y, batch.positions)
            slots, real, fresh, _ = meta
            o, state = lightning.ragged_lightning_attention(
                *map(batch.packing.expand, (q, k, v)),
                jnp.asarray(self.log_decay, jnp.float32), state,
                slots, real, fresh, batch.tile_offs)
            return self._finish(cx, batch.packing.compact(o), gate), state


class SparseLinearBlock(Module):
    def __init__(self, kind: str, mixer: Module, ffn: PackedGatedFFN, eps,
                 dtype, param_dtype):
        super().__init__()
        self.kind = kind
        self.mixer = mixer
        self.ffn = ffn
        self.ln1 = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)
        self.ln2 = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)


class SparseLinearLM(ServedModel):
    """Decoder-only LM of `SparseLinearBlock`s, one a name of
    `mixer_types`. `first_layer` is the published index of layer 0 and
    `depth_base` the published depth (both enter the residual's scale
    and the lightning layers' decay, so a slice of the stack computes
    what the whole stack's layers do). `sparse` holds the selection's
    sizes: dense_len, block, local, topk, init_blocks, kernel, stride.
    `snapshot_tokens` / `snapshot_slots` are what the model asks of the
    cache manager for prefix reuse over its state (ENGINE.md "State
    snapshots"); an engine's own arguments override them."""
    model_type = "sparse_linear_lm"

    def __init__(self, vocab: int, model_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, ffn_dim: int, mixer_types,
                 la_heads: int, la_head_dim: int, sparse: dict,
                 scale_emb: float = 1.0, scale_depth: float = 1.0,
                 depth_base: int = None, dim_model_base: int = None,
                 first_layer: int = 0, rope_theta: float = 10000.0,
                 eps: float = 1e-6, max_len: int = 4096,
                 snapshot_tokens: int = 0, snapshot_slots: int = 0,
                 dropout: float = 0.0, dtype=jnp.float32, param_dtype=None):
        super().__init__()
        if dropout:
            raise ValueError("SparseLinearLM has no dropout")
        kinds = tuple(mixer_types)
        bad = [k for k in kinds if k not in KINDS]
        if bad:
            raise ValueError(f"unknown mixer types {bad}; there are {KINDS}")
        sparse = dict(sparse)
        if sparse["kernel"] != 2 * sparse["stride"] \
                or sparse["block"] % sparse["stride"]:
            raise ValueError(
                "the index pool keeps a window with the block its last "
                "token lies in: kernel = 2 x stride, and a block a whole "
                f"number of strides; got {sparse}")
        depth_base = depth_base or len(kinds)
        param_dtype = jnp.dtype(param_dtype if param_dtype is not None
                                else dtype)
        self.config = dict(
            vocab=vocab, model_dim=model_dim, num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim, ffn_dim=ffn_dim,
            mixer_types=list(kinds), la_heads=la_heads,
            la_head_dim=la_head_dim, sparse=sparse, scale_emb=scale_emb,
            scale_depth=scale_depth, depth_base=depth_base,
            dim_model_base=dim_model_base, first_layer=first_layer,
            rope_theta=rope_theta, eps=eps, max_len=max_len,
            snapshot_tokens=snapshot_tokens, snapshot_slots=snapshot_slots)
        self.vocab, self.model_dim, self.max_len = vocab, model_dim, max_len
        self.dtype, self.param_dtype = dtype, param_dtype
        self.kinds, self.sparse = kinds, sparse
        self.scale_emb = float(scale_emb)
        self.residual = float(scale_depth) / math.sqrt(depth_base)
        self.head_div = model_dim / float(dim_model_base or model_dim)
        self.snapshot_tokens, self.snapshot_slots = (snapshot_tokens,
                                                     snapshot_slots)
        self.embed = Embedding(vocab, model_dim, dtype=dtype,
                               param_dtype=param_dtype,
                               embedding_init=I.normal(0.0, 1.0))
        blocks = []
        for i, kind in enumerate(kinds):
            if kind == "minicpm4":
                mixer = SparseAttention(model_dim, num_heads, num_kv_heads,
                                        head_dim, sparse, eps, dtype,
                                        param_dtype)
            else:
                mixer = LightningAttention(
                    model_dim, la_heads, la_head_dim, first_layer + i,
                    depth_base, rope_theta, eps, dtype, param_dtype)
            blocks.append(SparseLinearBlock(
                kind, mixer,
                PackedGatedFFN(model_dim, ffn_dim, dtype, param_dtype),
                eps, dtype, param_dtype))
        self.blocks = blocks
        self.norm_f = RMSNorm(eps, dtype=jnp.float32, param_dtype=param_dtype)
        # what one pool's row is: one kv head's [k | v]
        self.kv_row = (1, head_dim)
        self.sparse_layers = kinds.count("minicpm4")
        self.cache_layout = [self._layout(b) for b in blocks]

    def _layout(self, blk) -> dict:
        if blk.kind == "minicpm4":
            return {"kind": "paged", "pools": blk.mixer.num_kv_heads,
                    "index": {"stride": self.sparse["stride"],
                              "lanes": blk.mixer.num_kv_heads
                              * blk.mixer.head_dim}}
        return {"kind": "state", "arrays": blk.mixer.state_shapes}

    def sparse_counts(self, start: int, length: int) -> dict:
        """What a step's row [start, start + length) asks of ONE sparse
        layer, a kv head: cached rows read, keys attended, index rows
        read and blocks kept, after selection (the engine's span fields
        and counters; `benchmarks/flops_sala.py` prices them)."""
        sel = self.sparse
        blk, end = sel["block"], start + length
        keys = kept = 0
        for t in range(start, end):
            if t < sel["dense_len"]:
                keys += t + 1
                continue
            lo = max(t - (sel["local"] - 1), 0) // blk
            first = min(sel["init_blocks"], lo)
            n = first + min(sel["topk"], lo - first) + t // blk - lo + 1
            kept += n
            keys += (n - 1) * blk + t % blk + 1
        sparse = end > sel["dense_len"]
        # a decode row reads its kept blocks; a chunk's rows, whose
        # queries select apart, the whole context under the mask
        rows = keys if length == 1 else end
        return {"sparse_rows_read": rows, "sparse_keys": keys,
                "blocks_selected": kept,
                "index_rows_read": (max(0, window_limit(end - 1, sel))
                                    if sparse else 0)}

    def _mix(self, cx: Context, blk, x, mixed):
        h = x + (self.residual * mixed).astype(x.dtype)
        return h + (self.residual * blk.ffn(cx, blk.ln2(cx, h))
                    ).astype(x.dtype)

    def logits(self, cx: Context, x):
        y = self.norm_f(cx, x) / self.head_div
        return dense(cx, "head", y.astype(self.dtype), self.vocab,
                     self.dtype, self.param_dtype, out=jnp.float32)

    def forward(self, cx: Context, tokens):
        """tokens [B, T] -> float32 logits [B, T, V]; whole sequences,
        nothing cached."""
        if tokens.shape[1] > self.max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds max_len "
                             f"{self.max_len}")
        x = self.embed(cx, tokens) * self.scale_emb
        for blk in self.blocks:
            c = cx.scope(blk._name)
            x = self._mix(c, blk, x, blk.mixer.forward(c.scope("mixer"),
                                                       blk.ln1(c, x)))
        return self.logits(cx, x)

    def _packing(self, batch):
        """What every sparse layer of a step needs of the packing, built
        once: a tile's table, positions and window limit; the windows
        the step completes (the pool blocks their keys lie in and each
        row's weight in the mean, where the mean goes in an index
        pool); which rows are decode rows past dense_len."""
        sel = self.sparse
        blk, stride, kern = sel["block"], sel["stride"], sel["kernel"]
        rows_a = blk // stride
        positions, slots = batch.flat_positions, batch.flat_slots
        block_tables, tile_rows = batch.block_tables, batch.tile_rows
        tile_offs, tq = batch.tile_offs, batch.tq
        nt, t = tile_rows.shape[0], positions.shape[0]
        r = block_tables.shape[0]
        row_of = jnp.repeat(tile_rows, tq)
        tile_pos = (batch.q_starts[tile_rows] + tile_offs)[:, None] \
            + jnp.arange(tq, dtype=jnp.int32)[None, :]
        ctx = batch.context_lens
        # windows complete at the step's own real tokens
        done = batch.packing.flat_real & ((positions + 1) % stride == 0) \
            & (positions + 1 >= kern)
        width = t // stride + r
        at = jnp.nonzero(done, size=width, fill_value=t)[0]
        live = at < t
        at = jnp.minimum(at, t - 1)
        pos = positions[at]
        # the window's keys, positions pos - kernel + 1 .. pos, lie in the
        # block of the first and the block of the last: which pool
        # blocks, and each row's weight in the mean
        table = block_tables[row_of[at]]                     # [W, MB]
        ends = jnp.stack([(pos - (kern - 1)) // blk, pos // blk], axis=1)
        ends = jnp.maximum(ends, 0)
        win_blocks = jnp.where(live[:, None],
                               jnp.take_along_axis(table, ends, axis=1), 0)
        at_row = ends[:, :, None] * blk \
            + jnp.arange(blk, dtype=jnp.int32)[None, None, :]
        inside = (at_row > pos[:, None, None] - kern) \
            & (at_row <= pos[:, None, None])
        # one block holds the whole window: its second mention is idle
        inside = inside & ((jnp.arange(2)[None, :, None] == 0)
                           | (ends[:, 1] != ends[:, 0])[:, None, None])
        win_weights = inside.astype(jnp.float32) / kern
        dest = (slots[at] // blk) * rows_a + ((pos + 1) // stride - 1) \
            % rows_a
        first_tile = jnp.zeros((r,), jnp.int32).at[tile_rows].max(
            jnp.where(tile_offs == 0, jnp.arange(nt, dtype=jnp.int32), 0))
        return {
            "row_of": row_of, "tile_pos": tile_pos,
            "tile_tables": block_tables[tile_rows],
            # this step's own windows are in the index pool when a query
            # scores it; none lies past its row's context
            "tile_limit": window_limit(ctx[tile_rows] - 1, sel)[:, None],
            "win_blocks": win_blocks, "win_weights": win_weights,
            "win_dest": jnp.where(live, dest, 0),
            "first_tile": first_tile,
            "compact": (ctx - batch.q_starts == 1)
            & (ctx - 1 >= sel["dense_len"]),
        }

    def trunk(self, cx: Context, batch, pools):
        """The step's layers (`models/step_rows.py` `serve_step`).
        `pools` is the cache manager's list for this model's
        `cache_layout`: a sparse layer's paged pools, one a kv head,
        then its index pool; a lightning layer's state; last the ROWS
        table (a step row's state slot). The selection runs over the
        flat packing."""
        *arrays, rows = pools
        arrays = iter(arrays)
        meta = batch.tile_meta(rows[:, 0])
        pk = self._packing(batch) if self.sparse_layers else None
        out_pools = []
        x = self.embed(cx, batch.tokens) * self.scale_emb
        for blk in self.blocks:
            c = cx.scope(blk._name)
            m = c.scope("mixer")
            y = blk.ln1(c, x)
            if blk.kind == "minicpm4":
                mine = [next(arrays) for _ in range(blk.mixer.num_kv_heads)]
                mixed, mine, index = blk.mixer.ragged_step(
                    m, y, mine, next(arrays), pk, batch)
                out_pools += mine + [index]
            else:
                mixed, state = blk.mixer.ragged_step(m, y, next(arrays),
                                                     meta, batch)
                out_pools.append(state)
            x = self._mix(c, blk, x, mixed)
        return x, out_pools + [rows], None
