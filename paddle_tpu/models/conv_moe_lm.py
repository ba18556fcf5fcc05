"""A decoder of gated short convolutions and grouped-query attention
with routed experts, beside `CausalLM`, `LatentMoELM`, `HybridLM`,
`SparseLinearLM` and `ParallelHybridLM` (LFM2-MoE, the `lfm2_moe`
modelling code of Hugging Face `transformers`):

    x0 = E[tok]
    h = x + Mixer_i(RMSNorm(x));  x = h + F_i(RMSNorm(h))
    logits = E . RMSNorm(x)                                (tied head)

RMSNorm with a learned scale; no biases. The mixer of layer i is
`layer_types[i]`:

- "conv", the gated short convolution: [B | C | x~] = W_in y (three
  blocks of d, in that order); u = B . x~; v_t = sum_j w_j . u_{t-K+1+j}
  for j = 0 .. K-1, depthwise and causal (K = `conv_width`, zeros before
  position 0); out = W_out (C . v).
- "full_attention": GQA (`shared_layers.Attention`), q and k each
  through an RMSNorm over the head, then rotary over the whole head
  (rotate-half) at `rope_theta`, causal softmax at 1 / sqrt(hd), W_o.

F_i is a gated SiLU FFN of `ffn_dim` in the first `num_dense_layers`
layers and the routed experts after them (`shared_layers.RoutedExperts`: a
float32 sigmoid router with a selection bias, the top_k of
`num_experts`, the chosen scores over their sum + 1e-6, times
`scaling`; no shared expert).

Served through the engine's one ragged step, an attention layer keeps a
paged pool of Hkv x [k | v] rows and a conv layer a state slot: the last
K-1 values of u, flat (`cache_layout`, ENGINE.md "Cache kinds"). The
step also returns the tokens each expert took, as `LatentMoELM`'s does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.models.shared_layers import (Attention, GatedFFN,
                                             RoutedExperts, dense)
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, RMSNorm

KINDS = ("conv", "full_attention")


class ShortConv(Module):
    """The gated short convolution. `state_shapes` is what one sequence
    keeps: the last `width - 1` values of u."""

    def __init__(self, model_dim, width, dtype, param_dtype):
        super().__init__()
        self.model_dim, self.width = model_dim, width
        self.dtype, self.param_dtype = dtype, param_dtype
        self.state_shapes = (
            ("conv", ((width - 1) * model_dim,), jnp.dtype(dtype)),)

    def _gate(self, cx: Context, y):
        """y [..., d] -> (C, u = B . x~), in the compute dtype."""
        d = self.model_dim
        bcx = dense(cx, "in_proj", y, 3 * d, self.dtype, self.param_dtype)
        return bcx[..., d:2 * d], bcx[..., :d] * bcx[..., 2 * d:]

    def _weight(self, cx: Context):
        return cx.scope("conv").param("weight", (self.width, self.model_dim),
                                      I.normal(0.0, 0.5), self.param_dtype)

    def _out(self, cx: Context, c, v):
        g = (c.astype(jnp.float32) * v).astype(self.dtype)
        return dense(cx, "out_proj", g, self.model_dim, self.dtype,
                     self.param_dtype)

    def forward(self, cx: Context, y):
        """y [B, T, d], whole sequences from position 0."""
        c, u = self._gate(cx, y)
        k, t = self.width, y.shape[1]
        w = self._weight(cx).astype(jnp.float32)
        padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        return self._out(cx, c, sum(w[j] * padded[:, j:j + t]
                                    for j in range(k)))

    def ragged_step(self, cx: Context, y, tails, meta, batch):
        """y [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`; `meta` its `tile_meta`); the
        convolution runs over the flat packing. Returns (output, new
        tails)."""
        slots, real, fresh, last = meta
        with jax.named_scope("short_conv"):
            c, u = self._gate(cx, y)
            v, tails = scan.ragged_causal_conv(
                batch.packing.expand(u), tails, self._weight(cx), None,
                slots, real, fresh, last, batch.tile_offs)
            return self._out(cx, c, batch.packing.compact(v)), tails


class ConvMoEBlock(Module):
    """One layer: its mixer (`conv` or `attn`) and its FFN (`ffn`, or
    the routed experts `moe`)."""

    def __init__(self, kind: str, mixer: Module, ffn: Module, eps,
                 param_dtype):
        super().__init__()
        self.kind = kind
        if kind == "conv":
            self.conv = mixer
        else:
            self.attn = mixer
        self.routed = isinstance(ffn, RoutedExperts)
        if self.routed:
            self.moe = ffn
        else:
            self.ffn = ffn
        self.ln1 = RMSNorm(eps, param_dtype=param_dtype)
        self.ln2 = RMSNorm(eps, param_dtype=param_dtype)

    def _feed(self, cx: Context, h, real=None):
        """(h + F(RMSNorm(h)), tokens per expert or None); h [T, d]."""
        y = self.ln2(cx, h)
        if self.routed:
            y, counts, _ = self.moe(cx, y, real)
        else:
            y, counts = self.ffn(cx, y), None
        return h + y, counts

    def forward(self, cx: Context, x):
        """x [B, T, d], whole sequences from position 0."""
        mixer = self.conv if self.kind == "conv" else self.attn
        h = x + mixer(cx, self.ln1(cx, x))
        b, t, d = h.shape
        out, _ = self._feed(cx, h.reshape(b * t, d))
        return out.reshape(b, t, d)


class ConvMoELM(ServedModel):
    """Decoder-only LM of `ConvMoEBlock`s, a mixer kind a layer
    (`layer_types`), the first `num_dense_layers` with a dense FFN and
    the rest with routed experts; a tied head with float32 logits.
    `max_len` bounds the positions served (the rotary angles are
    computed, so it costs nothing)."""
    model_type = "conv_moe_lm"

    def __init__(self, vocab: int, model_dim: int, num_heads: int,
                 num_kv_heads: int, ffn_dim: int, expert_dim: int,
                 num_experts: int, top_k: int, layer_types,
                 num_dense_layers: int = 0, conv_width: int = 3,
                 scaling: float = 1.0, rope_theta: float = 10000.0,
                 eps: float = 1e-5, max_len: int = 4096,
                 dropout: float = 0.0, dtype=jnp.float32, param_dtype=None):
        super().__init__()
        if dropout:
            raise ValueError("ConvMoELM has no dropout")
        unknown = sorted(set(layer_types) - set(KINDS))
        if unknown:
            raise ValueError(f"unknown layer types {unknown}; know {KINDS}")
        if model_dim % num_heads or num_heads % num_kv_heads:
            raise ValueError(
                f"{model_dim} wide over {num_heads} query heads over "
                f"{num_kv_heads} kv heads: each must divide")
        param_dtype = jnp.dtype(param_dtype if param_dtype is not None
                                else dtype)
        self.config = dict(
            vocab=vocab, model_dim=model_dim, num_heads=num_heads,
            num_kv_heads=num_kv_heads, ffn_dim=ffn_dim,
            expert_dim=expert_dim, num_experts=num_experts, top_k=top_k,
            layer_types=list(layer_types), num_dense_layers=num_dense_layers,
            conv_width=conv_width, scaling=scaling, rope_theta=rope_theta,
            eps=eps, max_len=max_len)
        self.vocab, self.model_dim, self.max_len = vocab, model_dim, max_len
        self.dtype, self.param_dtype = dtype, param_dtype
        self.embed = Embedding(vocab, model_dim, dtype=dtype,
                               param_dtype=param_dtype,
                               embedding_init=I.normal(0.0, 0.02))
        head_dim = model_dim // num_heads
        blocks = []
        for i, kind in enumerate(layer_types):
            mixer = (ShortConv(model_dim, conv_width, dtype, param_dtype)
                     if kind == "conv" else
                     Attention(model_dim, num_heads, num_kv_heads, head_dim,
                               rope_theta, 1.0, dtype, param_dtype,
                               qk_norm_eps=eps))
            ffn = (GatedFFN(model_dim, ffn_dim, dtype, param_dtype)
                   if i < num_dense_layers else
                   RoutedExperts(model_dim, expert_dim, num_experts, top_k,
                                 0, scaling, dtype, param_dtype, eps=1e-6))
            blocks.append(ConvMoEBlock(kind, mixer, ffn, eps, param_dtype))
        self.blocks = blocks
        self.expert_layers = sum(b.routed for b in blocks)
        self.num_experts = num_experts
        self.norm_f = RMSNorm(eps, param_dtype=param_dtype)
        # what one pool's row is: every kv head's [k | v]
        self.kv_row = (num_kv_heads, head_dim)
        self.cache_layout = [
            {"kind": "state", "arrays": b.conv.state_shapes}
            if b.kind == "conv" else {"kind": "paged"} for b in blocks]

    def logits(self, cx: Context, x):
        h = self.norm_f(cx, x)
        table = cx.scope("embed").param(
            "weight", (self.vocab, self.model_dim), I.normal(0.0, 0.02),
            self.param_dtype)
        return jnp.matmul(h.astype(self.dtype), table.astype(self.dtype).T,
                          preferred_element_type=jnp.float32)

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
        `cache_layout`: an attention layer's paged pool, a conv layer's
        tails; last the ROWS table (a step row's state slot). The rows
        past the step's tokens are routed to no expert."""
        *arrays, rows = pools
        meta = batch.tile_meta(rows[:, 0])
        out_pools, counts = [], []
        x = self.embed(cx, batch.tokens)                         # [T_c, D]
        for blk, held in zip(self.blocks, arrays):
            c = cx.scope(blk._name)
            y = blk.ln1(c, x)
            if blk.kind == "conv":
                mixed, held = blk.conv.ragged_step(c.scope("conv"), y, held,
                                                   meta, batch)
            else:
                mixed, held = blk.attn.ragged_step(c.scope("attn"), y, held,
                                                   batch)
            out_pools.append(held)
            x, n = blk._feed(c, x + mixed, batch.packing.real)
            if n is not None:
                counts.append(n)
        return x, out_pools + [rows], counts
