"""A post-norm decoder of sliding-window and full GQA layers with a
leading dense FFN and routed plus shared experts after it, beside
`CausalLM`, `LatentMoELM`, `HybridLM`, `SparseLinearLM`,
`ParallelHybridLM` and `ConvMoELM` (K-EXAONE, the `exaone_moe` family,
which extends EXAONE 4.0):

    x0 = E[tok]
    h = x + RMSNorm_attn(Attn_i(x));  x = h + RMSNorm_ffn(F_i(h))
    logits = W_head RMSNorm(x)                       (untied head)

RMSNorm with a learned scale; no norm before the attention or the FFN
(the norms follow them); no biases. Attn_i is GQA
(`shared_layers.Attention`) with q and k each through an RMSNorm over
the head; `layer_types[i]` says its kind:

- "sliding_attention": rotary over the whole head (rotate-half) at
  `rope_theta`, and a query sees the `window` newest positions up to
  its own;
- "full_attention": no rotary, the whole causal context.

F_i (`mlp_layer_types[i]`) is a gated SiLU FFN of `ffn_dim` ("dense")
or the routed experts ("sparse", `shared_layers.RoutedExperts`: a
float32 sigmoid router with a selection bias over all
`num_experts x expert_shards` experts, the top_k, the chosen scores
over their sum, times `scaling`, plus `num_shared` shared experts).
With `expert_shards` > 1 the model is one chip's share of an
expert-parallel deployment: an expert layer holds the `num_experts` of
rank `expert_rank` and adds their part of the sum alone.

Served through the engine's one ragged step, a full layer keeps a paged
pool of Hkv x [k | v] rows and a window layer a ring of them a slot
(`cache_layout`, ENGINE.md "Cache kinds"); the step returns the tokens
each held expert took, and the pairs sent away.
"""

from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.models.shared_layers import (Attention, GatedFFN,
                                             RoutedExperts, dense)
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, RMSNorm

KINDS = ("sliding_attention", "full_attention")
MLP_KINDS = ("dense", "sparse")


class WindowMoEBlock(Module):
    """One layer: its attention (`attn`), its FFN (`ffn`, or the routed
    experts `moe`) and the norm after each."""

    def __init__(self, attn: Attention, ffn: Module, eps, param_dtype):
        super().__init__()
        self.attn = attn
        self.routed = isinstance(ffn, RoutedExperts)
        if self.routed:
            self.moe = ffn
        else:
            self.ffn = ffn
        self.ln_attn = RMSNorm(eps, param_dtype=param_dtype)
        self.ln_ffn = RMSNorm(eps, param_dtype=param_dtype)

    def _feed(self, cx: Context, h, real=None):
        """(h + RMSNorm(F(h)), tokens per expert or None); h [T, d]."""
        if self.routed:
            y, counts, _ = self.moe(cx, h, real)
        else:
            y, counts = self.ffn(cx, h), None
        return h + self.ln_ffn(cx, y), counts

    def forward(self, cx: Context, x):
        """x [B, T, d], whole sequences from position 0."""
        h = x + self.ln_attn(cx, self.attn(cx, x))
        b, t, d = h.shape
        out, _ = self._feed(cx, h.reshape(b * t, d))
        return out.reshape(b, t, d)


class WindowMoELM(ServedModel):
    """Decoder-only LM of `WindowMoEBlock`s, an attention kind and an FFN
    kind a layer (`layer_types`, `mlp_layer_types`); an untied head with
    float32 logits. `max_len` bounds the positions served (the rotary
    angles are computed, so it costs nothing)."""
    model_type = "window_moe_lm"

    def __init__(self, vocab: int, model_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, ffn_dim: int,
                 expert_dim: int, num_experts: int, top_k: int, layer_types,
                 mlp_layer_types, window: int, num_shared: int = 1,
                 expert_shards: int = 1, expert_rank: int = 0,
                 scaling: float = 1.0, rope_theta: float = 1e6,
                 eps: float = 1e-5, max_len: int = 4096,
                 dropout: float = 0.0, dtype=jnp.float32, param_dtype=None):
        super().__init__()
        if dropout:
            raise ValueError("WindowMoELM has no dropout")
        unknown = sorted(set(layer_types) - set(KINDS)) + sorted(
            set(mlp_layer_types) - set(MLP_KINDS))
        if unknown:
            raise ValueError(f"unknown layer types {unknown}; know {KINDS} "
                             f"and {MLP_KINDS}")
        if len(layer_types) != len(mlp_layer_types):
            raise ValueError(f"{len(layer_types)} attention kinds for "
                             f"{len(mlp_layer_types)} FFN kinds")
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "kv heads")
        param_dtype = jnp.dtype(param_dtype if param_dtype is not None
                                else dtype)
        self.config = dict(
            vocab=vocab, model_dim=model_dim, num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim, ffn_dim=ffn_dim,
            expert_dim=expert_dim, num_experts=num_experts, top_k=top_k,
            layer_types=list(layer_types),
            mlp_layer_types=list(mlp_layer_types), window=window,
            num_shared=num_shared, expert_shards=expert_shards,
            expert_rank=expert_rank, scaling=scaling, rope_theta=rope_theta,
            eps=eps, max_len=max_len)
        self.vocab, self.model_dim, self.max_len = vocab, model_dim, max_len
        self.dtype, self.param_dtype = dtype, param_dtype
        self.embed = Embedding(vocab, model_dim, dtype=dtype,
                               param_dtype=param_dtype,
                               embedding_init=I.normal(0.0, 1.0))
        blocks = []
        for kind, mlp in zip(layer_types, mlp_layer_types):
            sliding = kind == "sliding_attention"
            attn = Attention(
                model_dim, num_heads, num_kv_heads, head_dim, rope_theta,
                1.0, dtype, param_dtype, qk_norm_eps=eps, rotary=sliding,
                window=window if sliding else None,
                name="ragged_gqa_window" if sliding else "ragged_gqa_full")
            ffn = (GatedFFN(model_dim, ffn_dim, dtype, param_dtype)
                   if mlp == "dense" else
                   RoutedExperts(model_dim, expert_dim, num_experts, top_k,
                                 num_shared, scaling, dtype, param_dtype,
                                 expert_shards=expert_shards,
                                 rank=expert_rank))
            blocks.append(WindowMoEBlock(attn, ffn, eps, param_dtype))
        self.blocks = blocks
        self.expert_layers = sum(b.routed for b in blocks)
        self.num_experts, self.expert_shards = num_experts, expert_shards
        self.norm_f = RMSNorm(eps, param_dtype=param_dtype)
        # what one pool's row is: every kv head's [k | v]
        self.kv_row = (num_kv_heads, head_dim)
        self.cache_layout = [
            {"kind": "paged"} if b.attn.window is None else
            {"kind": "window", "window": b.attn.window} for b in blocks]

    def logits(self, cx: Context, x):
        return dense(cx, "head", self.norm_f(cx, x), self.vocab, self.dtype,
                     self.param_dtype, out=jnp.float32)

    def forward(self, cx: Context, tokens):
        """tokens [B, T] -> float32 logits [B, T, V]; whole sequences,
        nothing cached."""
        if tokens.shape[1] > self.max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds max_len "
                             f"{self.max_len}")
        x = self.embed(cx, tokens)
        for blk in self.blocks:
            x = blk(cx, x)
        return self.logits(cx, x)

    def trunk(self, cx: Context, batch, pools):
        """The step's layers (`models/step_rows.py` `serve_step`).
        `pools` is the cache manager's list for this model's
        `cache_layout`: a full layer's paged pool, a window layer's ring
        pool; last the ROWS table, whose columns after the first are
        the pool blocks of a step row's ring. The rows past the step's
        tokens are routed to no expert."""
        *arrays, rows = pools
        out_pools, counts = [], []
        x = self.embed(cx, batch.tokens)                         # [T_c, D]
        for blk, pool in zip(self.blocks, arrays):
            c = cx.scope(blk._name)
            table = rows_at = None
            if blk.attn.window is not None:
                table, rows_at = batch.ring_rows(rows[:, 1:], pool.shape[1])
            mixed, pool = blk.attn.ragged_step(c.scope("attn"), x, pool,
                                               batch, table, rows_at)
            out_pools.append(pool)
            x, n = blk._feed(c, x + blk.ln_attn(c, mixed),
                             batch.packing.real)
            if n is not None:
                counts.append(n)
        return x, out_pools + [rows], counts
