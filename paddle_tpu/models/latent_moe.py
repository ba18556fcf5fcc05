"""A decoder with latent attention (MLA) and routed experts, beside
`CausalLM`: RMSNorm, rotary positions on a slice of each head, low-rank
query and key/value projections with two inner norms, gated SiLU
feed-forward, a leading dense layer then expert layers (a float32
sigmoid router with a selection bias, top-k of the routed experts plus
shared ones), an untied head, parameters resident in `param_dtype`.

    x <- x + MLA(RMSNorm(x));  x <- x + F(RMSNorm(x))

Served through the engine's one ragged step (`models/step_rows.py`
`serve_step`, with per-expert token counts), the attention runs
ABSORBED over a latent block pool: a token's cached row is
[RMSNorm(c_kv) | RoPE(k_r)] (kv_rank + rope values, no head axis), the
query of head h is [W_kvb^K,h^T q_nope_h | q_rope_h], the scores contract
the whole row, the values are the row's first kv_rank lanes, and
W_kvb^V,h lifts the attended latent to the head's value width after the
kernel. `forward` computes the published un-absorbed form (full keys and
values per head, nothing cached); the two agree to rounding
(tests/test_latent_moe.py).

The expert layer is sorted and dropless (`shared_layers.RoutedExperts`);
rows that are padding of the flat packing are routed nowhere and
counted nowhere. (`parallel/moe.py` holds the training-side
capacity-buffer experts; nothing of it is used here.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.models.shared_layers import GatedFFN, RoutedExperts
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, Linear, RMSNorm


def rotary(x, positions, theta: float):
    """Rotary embedding over all of x's last axis, interleaved pairs
    (x[2i], x[2i+1]). x [T, ..., r]; positions [T]. Angles in float32."""
    r = x.shape[-1]
    inv = 1.0 / jnp.power(theta, jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _weight(cx: Context, linear: Linear, in_features: int):
    """A child Linear's weight, for the products that are not x @ W."""
    c = cx.scope(linear._name)
    return c.param("weight", (in_features, linear.features),
                   linear.kernel_init, linear.param_dtype).astype(linear.dtype)


class LatentAttention(Module):
    """Multi-head latent attention. `latent_row` = (k_dim, v_dim) tells
    the engine what one cached row is (kernels/paged_attention.py, "The
    pool's row", has the layout): k_dim = kv_rank + rope_dim values of
    which the first v_dim = kv_rank are also the value."""

    def __init__(self, model_dim: int, num_heads: int, q_rank: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 10000.0, eps: float = 1e-5,
                 dtype=jnp.float32, param_dtype=jnp.float32):
        super().__init__()
        self.model_dim, self.num_heads = model_dim, num_heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope_theta = rope_theta
        self.dtype = dtype
        # what the engine reads to size and lay out its pool
        self.num_kv_heads = 1
        self.head_dim = kv_rank + rope_dim
        self.latent_row = (kv_rank + rope_dim, kv_rank)
        self.scale = 1.0 / math.sqrt(nope_dim + rope_dim)
        kw = dict(use_bias=False, dtype=dtype, param_dtype=param_dtype)
        self.q_a = Linear(q_rank, **kw)
        self.q_norm = RMSNorm(eps, param_dtype=param_dtype)
        self.q_b = Linear(num_heads * (nope_dim + rope_dim), **kw)
        self.kv_a = Linear(kv_rank + rope_dim, **kw)
        self.kv_norm = RMSNorm(eps, param_dtype=param_dtype)
        self.kv_b = Linear(num_heads * (nope_dim + v_dim), **kw)
        self.o = Linear(model_dim, **kw)

    def _project(self, cx: Context, x, positions):
        """x [T, d], positions [T] -> (q_nope [T, H, nope], q_rope
        [T, H, rope] rotated, c_kv [T, kv_rank] normed, k_rope [T, rope]
        rotated)."""
        t = x.shape[0]
        q = self.q_b(cx, self.q_norm(cx, self.q_a(cx, x))).reshape(
            t, self.num_heads, self.nope_dim + self.rope_dim)
        kv = self.kv_a(cx, x)
        c_kv = self.kv_norm(cx, kv[:, :self.kv_rank])
        k_rope = rotary(kv[:, self.kv_rank:], positions, self.rope_theta)
        q_rope = rotary(q[..., self.nope_dim:], positions, self.rope_theta)
        return q[..., :self.nope_dim], q_rope, c_kv, k_rope

    def _kv_b(self, cx: Context):
        """W_kvb as (W^K [kv_rank, H, nope], W^V [kv_rank, H, v])."""
        w = _weight(cx, self.kv_b, self.kv_rank).reshape(
            self.kv_rank, self.num_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def forward(self, cx: Context, x):
        """The published form over whole sequences from position 0,
        causal: x [B, T, d] -> [B, T, d]. Full keys and values a head."""
        b, t, _ = x.shape
        pos = jnp.tile(jnp.arange(t, dtype=jnp.int32), b)
        q_nope, q_rope, c_kv, k_rope = self._project(
            cx, x.reshape(b * t, -1), pos)
        wk, wv = self._kv_b(cx)
        k_nope = jnp.einsum("tc,chn->thn", c_kv, wk)
        v = jnp.einsum("tc,chv->thv", c_kv, wv)

        def seqs(a):
            return a.reshape((b, t) + a.shape[1:])
        s = (jnp.einsum("bqhn,bkhn->bhqk", seqs(q_nope), seqs(k_nope))
             + jnp.einsum("bqhr,bkr->bhqk", seqs(q_rope), seqs(k_rope)))
        s = s.astype(jnp.float32) * self.scale
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhv->bqhv", a.astype(v.dtype), seqs(v))
        return self.o(cx, o.reshape(b, t, self.num_heads * self.v_dim))

    def ragged_step(self, cx: Context, x, kv_pool, batch):
        """The absorbed form over the step's tokens at the compact width
        (`batch`, a `models.step_rows.StepBatch`): x [T_c, d]. The
        step's latent rows are written into the pool at its slots first
        (in place on a donated pool), then one launch of the ragged
        kernel serves every row of the flat packing against the pool as
        it lies. Returns (out [T_c, d], new pool)."""
        cx = cx.scope(self._name or type(self).__name__)
        with jax.named_scope("mla_attention"):
            q_nope, q_rope, c_kv, k_rope = self._project(cx, x,
                                                         batch.positions)
            wk, wv = self._kv_b(cx)
            kv_pool = paged.write_latent(
                kv_pool, batch.slots,
                jnp.concatenate([c_kv, k_rope], axis=-1))
            q = jnp.concatenate(
                [jnp.einsum("thn,chn->thc", q_nope, wk), q_rope], axis=-1)
            latent = paged.ragged_paged_attention(
                batch.packing.expand(q), kv_pool, batch.block_tables,
                batch.context_lens, batch.q_starts, batch.tile_rows,
                batch.tile_offs, scale=self.scale, groups=self.num_heads,
                value_lanes=(0, self.kv_rank))          # [T, H, kv_rank]
            o = jnp.einsum("thc,chv->thv", batch.packing.compact(latent), wv)
        out = self.o(cx, o.reshape(x.shape[0], self.num_heads * self.v_dim))
        return out, kv_pool


class LatentMoEBlock(Module):
    def __init__(self, attn: LatentAttention, ffn: Module, eps: float,
                 param_dtype):
        super().__init__()
        self.attn = attn
        self.routed = isinstance(ffn, RoutedExperts)
        if self.routed:
            self.moe = ffn
        else:
            self.ffn = ffn
        self.ln1 = RMSNorm(eps, param_dtype=param_dtype)
        self.ln2 = RMSNorm(eps, param_dtype=param_dtype)

    def _feed(self, cx: Context, h, real=None):
        """(F(h), tokens per expert, the router's choices), the last
        two None in a dense layer; h [T, d]."""
        if self.routed:
            return self.moe(cx, h, real)
        return self.ffn(cx, h), None, None

    def forward(self, cx: Context, x):
        """x [B, T, d]: whole sequences from position 0. Returns (x,
        the router's choices [B, T, k] or None)."""
        x = x + self.attn(cx, self.ln1(cx, x))
        b, t, d = x.shape
        y, _, chosen = self._feed(cx, self.ln2(cx, x).reshape(b * t, d))
        if chosen is not None:
            chosen = chosen.reshape(b, t, -1)
        return x + y.reshape(b, t, d), chosen

    def ragged_step(self, cx: Context, x, kv_pool, batch):
        cx = cx.scope(self._name or type(self).__name__)
        h, kv_pool = self.attn.ragged_step(cx, self.ln1(cx, x), kv_pool,
                                           batch)
        x = x + h
        y, counts, _ = self._feed(cx, self.ln2(cx, x), batch.packing.real)
        return x + y, kv_pool, counts


class LatentMoELM(ServedModel):
    """Decoder-only LM of `LatentMoEBlock`s: the first `first_dense`
    layers carry a dense gated FFN of width `dense_dim`, the rest the
    routed experts. No position table, no embedding scale, untied head
    with float32 logits. `max_len` bounds the positions served (the
    rotary angles are computed, so it costs nothing)."""
    model_type = "latent_moe_lm"

    def __init__(self, vocab: int, model_dim: int, num_heads: int,
                 num_layers: int, q_rank: int, kv_rank: int, nope_dim: int,
                 rope_dim: int, v_dim: int, dense_dim: int, expert_dim: int,
                 num_experts: int, top_k: int, num_shared: int = 1,
                 first_dense: int = 1, scaling: float = 1.0,
                 rope_theta: float = 10000.0, eps: float = 1e-5,
                 max_len: int = 4096, dropout: float = 0.0,
                 dtype=jnp.float32, param_dtype=None):
        super().__init__()
        if dropout:
            raise ValueError("LatentMoELM has no dropout")
        param_dtype = param_dtype if param_dtype is not None else dtype
        self.config = dict(
            vocab=vocab, model_dim=model_dim, num_heads=num_heads,
            num_layers=num_layers, q_rank=q_rank, kv_rank=kv_rank,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
            dense_dim=dense_dim, expert_dim=expert_dim,
            num_experts=num_experts, top_k=top_k, num_shared=num_shared,
            first_dense=first_dense, scaling=scaling, rope_theta=rope_theta,
            eps=eps, max_len=max_len)
        self.vocab, self.model_dim, self.max_len = vocab, model_dim, max_len
        self.dtype, self.param_dtype = dtype, jnp.dtype(param_dtype)
        self.embed = Embedding(vocab, model_dim, dtype=dtype,
                               param_dtype=param_dtype,
                               embedding_init=I.normal(0.0, 1.0))
        blocks = []
        for i in range(num_layers):
            attn = LatentAttention(model_dim, num_heads, q_rank, kv_rank,
                                   nope_dim, rope_dim, v_dim, rope_theta,
                                   eps, dtype, param_dtype)
            ffn = (GatedFFN(model_dim, dense_dim, dtype, param_dtype)
                   if i < first_dense else
                   RoutedExperts(model_dim, expert_dim, num_experts, top_k,
                                 num_shared, scaling, dtype, param_dtype))
            blocks.append(LatentMoEBlock(attn, ffn, eps, param_dtype))
        self.blocks = blocks
        self.expert_layers = sum(b.routed for b in blocks)
        self.num_experts = num_experts
        self.norm_f = RMSNorm(eps, param_dtype=param_dtype)
        self.head = Linear(vocab, use_bias=False, dtype=dtype,
                           param_dtype=param_dtype)
        # a latent pool in every layer: one (k_dim, v_dim) row a token
        self.cache_layout = [{"kind": "paged"}] * num_layers
        self.latent_row = blocks[0].attn.latent_row

    def logits(self, cx: Context, h):
        w = _weight(cx, self.head, self.model_dim)
        return jnp.matmul(self.norm_f(cx, h).astype(self.dtype), w,
                          preferred_element_type=jnp.float32)

    def forward(self, cx: Context, tokens, return_routing: bool = False):
        """tokens [B, T] -> float32 logits [B, T, V]; the published
        attention, nothing cached. With `return_routing` also the
        experts each token chose, int32 [expert layers, B, T, k]."""
        if tokens.shape[1] > self.max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds max_len "
                             f"{self.max_len}")
        x = self.embed(cx, tokens)
        routing = []
        for blk in self.blocks:
            x, chosen = blk(cx, x)
            if chosen is not None:
                routing.append(chosen)
        logits = self.logits(cx, x)
        return (logits, jnp.stack(routing)) if return_routing else logits

    def trunk(self, cx: Context, batch, pools):
        """The step's layers over latent pools (`models/step_rows.py`
        `serve_step`); the rows past the step's tokens are routed to no
        expert."""
        x = self.embed(cx, batch.tokens)                         # [T_c, D]
        new_pools, counts = [], []
        for blk, kv_pool in zip(self.blocks, pools):
            x, kv_pool, n = blk.ragged_step(cx, x, kv_pool, batch)
            new_pools.append(kv_pool)
            if n is not None:
                counts.append(n)
        return x, new_pools, counts
