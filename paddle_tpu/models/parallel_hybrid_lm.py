"""A decoder whose every layer runs softmax attention and a Mamba-2 mixer
side by side on the same normed input, beside `CausalLM`,
`LatentMoELM`, `HybridLM` and `SparseLinearLM` (Falcon-H1, the
`falcon_h1` modelling code of Hugging Face `transformers`). Every
multiplier is the configuration's own (muP):

    x0 = embedding_multiplier . E[tok]
    y = RMSNorm(x)
    x = x + attention_out_multiplier . Attn(attention_in_multiplier . y)
          + ssm_out_multiplier . Mamba2(ssm_in_multiplier . y)
    x = x + MLP(RMSNorm(x))
    logits = lm_head_multiplier . W_head RMSNorm(x)       (untied head)

RMSNorm with a learned scale; no biases but the convolution's.

- Attn: GQA, q = W_q y (H heads), k = key_multiplier . W_k y,
  v = W_v y (Hkv heads), rotary over the whole head (rotate-half) at
  `rope_theta`, causal softmax at 1 / sqrt(hd), query heads
  g.G .. g.G+G-1 over kv head g, then W_o.
- MLP: W_down (up . silu(mlp_multipliers[0] . gate))
  . mlp_multipliers[1], [gate | up] = W1 y.
- Mamba2 (SSD, arXiv:2405.21060): [z | x | B | C | dt] = W_in u, the
  five blocks scaled by `ssm_multipliers` in that order; [x | B | C]
  through a causal depthwise convolution of width `conv` with bias and
  silu; delta = softplus(dt + dt_bias), A_h = -exp(A_log_h);
  S_t = exp(delta_t A_h) S_{t-1} + delta_t x_t (x) B_t,g and
  y_t = C_t,g S_t + D_h x_t, head h in group g = h // (H_s / G) (the
  scan of `kernels/lightning_attention.py`, `ragged_ssd`); then
  RMSNorm over each of the G groups of y . silu(z) (the norm after the
  gate) with a learned scale, then W_out.

Served through the engine's one ragged step, every layer DECLARES two
kinds of cache at once (`cache_layout`, ENGINE.md "Cache kinds"): a
paged pool of Hkv x [k | v] rows AND state arrays in slots, the scan's
state [H_s, N, P] float32 and the convolution's tail, the last `conv`
- 1 inputs of its [x | B | C] channels, held flat. A sequence holds
blocks and one slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import lightning_attention as recurrence
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.models.shared_layers import (Attention, PackedGatedFFN,
                                             dense)
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, RMSNorm


class Mamba2(Module):
    """The Mamba-2 mixer. `state_shapes` is what one sequence keeps."""

    def __init__(self, model_dim, heads, head_dim, d_state, groups, d_conv,
                 multipliers, eps, dtype, param_dtype):
        super().__init__()
        self.model_dim, self.heads, self.head_dim = model_dim, heads, head_dim
        self.d_state, self.groups, self.d_conv = d_state, groups, d_conv
        self.eps = eps
        self.dtype, self.param_dtype = dtype, param_dtype
        self.d_ssm = heads * head_dim
        self.conv_dim = self.d_ssm + 2 * groups * d_state
        widths = (self.d_ssm, self.d_ssm, groups * d_state,
                  groups * d_state, heads)
        # the five blocks of the input projection, each at its multiplier
        self.multipliers = np.concatenate(
            [np.full(w, m, np.float32) for w, m in zip(widths, multipliers)])
        self.state_shapes = (
            ("ssm", (heads, d_state, head_dim), jnp.dtype(jnp.float32)),
            ("conv", ((d_conv - 1) * self.conv_dim,), jnp.dtype(dtype)))

    def _params(self, cx: Context):
        h, pd = self.heads, self.param_dtype
        c = cx.scope("conv")
        return {
            "conv_w": c.param("weight", (self.d_conv, self.conv_dim),
                              I.normal(0.0, 0.5), pd),
            "conv_b": c.param("bias", (self.conv_dim,), I.normal(0.0, 0.02),
                              pd),
            "dt_bias": cx.param("dt_bias", (h,), I.zeros, pd),
            "a_log": cx.param("A_log", (h,), I.zeros, pd),
            "d": cx.param("D", (h,), I.ones, pd),
        }

    def _pre(self, cx: Context, u):
        """u [..., d] -> z (float32), [x | B | C] in the compute dtype,
        dt (float32): the input projection, its blocks scaled."""
        zxbcdt = dense(cx, "in_proj", u, self.multipliers.size, self.dtype,
                       self.param_dtype).astype(jnp.float32) \
            * self.multipliers
        ds = self.d_ssm
        return (zxbcdt[..., :ds],
                zxbcdt[..., ds:ds + self.conv_dim].astype(self.dtype),
                zxbcdt[..., ds + self.conv_dim:])

    def _scan_inputs(self, p, conv, dt):
        """From the convolution's output: x [..., H, P], B, C
        [..., G, N], delta [..., H] (float32), A [H]."""
        xbc = jax.nn.silu(conv.astype(jnp.float32))
        lead = xbc.shape[:-1]
        gn = self.groups * self.d_state
        x = xbc[..., :self.d_ssm].reshape(lead + (self.heads, self.head_dim))
        b = xbc[..., self.d_ssm:self.d_ssm + gn].reshape(
            lead + (self.groups, self.d_state))
        c = xbc[..., self.d_ssm + gn:].reshape(
            lead + (self.groups, self.d_state))
        delta = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        return x, b, c, delta, a

    def _post(self, cx: Context, y, z):
        """The gated norm (after the gate), then W_out."""
        g = y.reshape(z.shape) * jax.nn.silu(z)
        lead = g.shape[:-1]
        gg = g.reshape(lead + (self.groups, -1))
        var = jnp.mean(jnp.square(gg), axis=-1, keepdims=True)
        scale = cx.scope("norm").param("scale", (self.d_ssm,), I.ones,
                                       self.param_dtype)
        g = (gg * jax.lax.rsqrt(var + self.eps)).reshape(g.shape) \
            * scale.astype(jnp.float32)
        return dense(cx, "out_proj", g.astype(self.dtype), self.model_dim,
                     self.dtype, self.param_dtype)

    def forward(self, cx: Context, u):
        """u [B, T, d], whole sequences from position 0."""
        p = self._params(cx)
        z, xbc, dt = self._pre(cx, u)
        k, t = self.d_conv, u.shape[1]
        w = p["conv_w"].astype(jnp.float32)
        padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        conv = p["conv_b"].astype(jnp.float32) + sum(
            w[j] * padded[:, j:j + t] for j in range(k))
        x, b, c, delta, a = self._scan_inputs(p, conv, dt)
        per = self.heads // self.groups
        b, c = (jnp.repeat(v, per, axis=2) for v in (b, c))   # [B, T, H, N]

        def step(s, inp):
            x_t, b_t, c_t, dt_t = inp
            s = jnp.exp(dt_t * a)[..., None, None] * s \
                + b_t[..., :, None] * (dt_t[..., None] * x_t)[..., None, :]
            return s, jnp.einsum("bhn,bhnp->bhp", c_t, s)

        s0 = jnp.zeros((u.shape[0], self.heads, self.d_state, self.head_dim),
                       jnp.float32)
        _, y = jax.lax.scan(step, s0, tuple(
            jnp.swapaxes(v, 0, 1) for v in (x, b, c, delta)))
        y = jnp.swapaxes(y, 0, 1) \
            + p["d"].astype(jnp.float32)[:, None] * x
        return self._post(cx, y, z)

    def ragged_step(self, cx: Context, u, ssm, tails, meta, batch):
        """u [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`; `meta` its `tile_meta`); the
        convolution and the scan run over the flat packing. Returns
        (output, new scan state, new tails)."""
        p = self._params(cx)
        packing, tile_offs = batch.packing, batch.tile_offs
        slots, real, fresh, last = meta
        z, xbc, dt = self._pre(cx, u)
        with jax.named_scope("ssd_scan"):
            conv, tails = scan.ragged_causal_conv(
                packing.expand(xbc), tails, p["conv_w"], p["conv_b"], slots,
                real, fresh, last, tile_offs)
            x, b, c, delta, a = self._scan_inputs(p, conv,
                                                  packing.expand(dt))
            y, ssm = recurrence.ragged_ssd(x, delta, a, b, c, p["d"], ssm,
                                           slots, real, fresh, tile_offs)
        return self._post(cx, packing.compact(y), z), ssm, tails


class ParallelBlock(Module):
    def __init__(self, attn: Attention, ssm: Mamba2, ffn: PackedGatedFFN,
                 eps, dtype, param_dtype):
        super().__init__()
        self.attn = attn
        self.ssm = ssm
        self.ffn = ffn
        self.ln1 = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)
        self.ln2 = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)


class ParallelHybridLM(ServedModel):
    """Decoder-only LM of `ParallelBlock`s: attention and a Mamba-2
    mixer in every layer. The multipliers are the configuration's
    `*_multiplier(s)`; `ssm_multipliers` scales the input projection's
    blocks z, x, B, C, dt."""
    model_type = "parallel_hybrid_lm"

    def __init__(self, vocab: int, model_dim: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, ffn_dim: int,
                 num_layers: int, ssm_heads: int, ssm_head_dim: int,
                 ssm_state: int, ssm_groups: int, conv: int = 4,
                 embedding_multiplier: float = 1.0,
                 attention_in_multiplier: float = 1.0,
                 attention_out_multiplier: float = 1.0,
                 key_multiplier: float = 1.0,
                 ssm_in_multiplier: float = 1.0,
                 ssm_out_multiplier: float = 1.0,
                 ssm_multipliers=(1.0,) * 5, mlp_multipliers=(1.0, 1.0),
                 lm_head_multiplier: float = 1.0,
                 rope_theta: float = 10000.0, eps: float = 1e-5,
                 max_len: int = 4096, dropout: float = 0.0,
                 dtype=jnp.float32, param_dtype=None):
        super().__init__()
        if dropout:
            raise ValueError("ParallelHybridLM has no dropout")
        if num_heads % num_kv_heads or ssm_heads % ssm_groups:
            raise ValueError(
                f"{num_heads} query heads over {num_kv_heads} kv heads, "
                f"{ssm_heads} scan heads over {ssm_groups} groups: each "
                "must divide")
        param_dtype = jnp.dtype(param_dtype if param_dtype is not None
                                else dtype)
        self.config = dict(
            vocab=vocab, model_dim=model_dim, num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim, ffn_dim=ffn_dim,
            num_layers=num_layers, ssm_heads=ssm_heads,
            ssm_head_dim=ssm_head_dim, ssm_state=ssm_state,
            ssm_groups=ssm_groups, conv=conv,
            embedding_multiplier=embedding_multiplier,
            attention_in_multiplier=attention_in_multiplier,
            attention_out_multiplier=attention_out_multiplier,
            key_multiplier=key_multiplier,
            ssm_in_multiplier=ssm_in_multiplier,
            ssm_out_multiplier=ssm_out_multiplier,
            ssm_multipliers=list(ssm_multipliers),
            mlp_multipliers=list(mlp_multipliers),
            lm_head_multiplier=lm_head_multiplier,
            rope_theta=rope_theta, eps=eps, max_len=max_len)
        self.vocab, self.model_dim, self.max_len = vocab, model_dim, max_len
        self.dtype, self.param_dtype = dtype, param_dtype
        self.embedding_multiplier = float(embedding_multiplier)
        self.attn_in = float(attention_in_multiplier)
        self.attn_out = float(attention_out_multiplier)
        self.ssm_in, self.ssm_out = (float(ssm_in_multiplier),
                                     float(ssm_out_multiplier))
        self.mlp_out = float(mlp_multipliers[1])
        self.lm_head_multiplier = float(lm_head_multiplier)
        self.embed = Embedding(vocab, model_dim, dtype=dtype,
                               param_dtype=param_dtype,
                               embedding_init=I.normal(0.0, 1.0))
        self.blocks = [ParallelBlock(
            Attention(model_dim, num_heads, num_kv_heads, head_dim,
                      rope_theta, key_multiplier, dtype, param_dtype),
            Mamba2(model_dim, ssm_heads, ssm_head_dim, ssm_state, ssm_groups,
                   conv, ssm_multipliers, eps, dtype, param_dtype),
            PackedGatedFFN(model_dim, ffn_dim, dtype, param_dtype,
                           gate_scale=float(mlp_multipliers[0])),
            eps, dtype, param_dtype) for _ in range(num_layers)]
        self.norm_f = RMSNorm(eps, dtype=jnp.float32, param_dtype=param_dtype)
        # what one pool's row is: every kv head's [k | v]
        self.kv_row = self.blocks[0].attn.kv_row
        # every layer keeps both kinds: its paged rows and its slot
        self.cache_layout = [{"kind": "paged", "arrays": b.ssm.state_shapes}
                             for b in self.blocks]

    def _finish(self, cx: Context, blk, x, attended, scanned):
        h = x + (self.attn_out * attended.astype(jnp.float32)
                 + self.ssm_out * scanned.astype(jnp.float32)).astype(x.dtype)
        return h + (self.mlp_out * blk.ffn(cx, blk.ln2(cx, h))).astype(
            x.dtype)

    def logits(self, cx: Context, x):
        y = self.norm_f(cx, x)
        return self.lm_head_multiplier * dense(
            cx, "head", y.astype(self.dtype), self.vocab, self.dtype,
            self.param_dtype, out=jnp.float32)

    def forward(self, cx: Context, tokens):
        """tokens [B, T] -> float32 logits [B, T, V]; whole sequences,
        nothing cached."""
        if tokens.shape[1] > self.max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds max_len "
                             f"{self.max_len}")
        x = self.embed(cx, tokens) * self.embedding_multiplier
        for blk in self.blocks:
            c = cx.scope(blk._name)
            y = blk.ln1(c, x)
            with jax.named_scope("parallel_mixer"):
                attended = blk.attn.forward(c.scope("attn"), y * self.attn_in)
                scanned = blk.ssm.forward(c.scope("ssm"), y * self.ssm_in)
            x = self._finish(c, blk, x, attended, scanned)
        return self.logits(cx, x)

    def trunk(self, cx: Context, batch, pools):
        """The step's layers (`models/step_rows.py` `serve_step`).
        `pools` is the cache manager's list for this model's
        `cache_layout`: each layer's paged pool, then its scan state and
        its tails; last the ROWS table (a step row's state slot)."""
        *arrays, rows = pools
        arrays = iter(arrays)
        meta = batch.tile_meta(rows[:, 0])
        out_pools = []
        x = self.embed(cx, batch.tokens) * self.embedding_multiplier
        for blk in self.blocks:
            c = cx.scope(blk._name)
            y = blk.ln1(c, x)
            pool, ssm, tails = next(arrays), next(arrays), next(arrays)
            with jax.named_scope("parallel_mixer"):
                attended, pool = blk.attn.ragged_step(
                    c.scope("attn"), y * self.attn_in, pool, batch)
                scanned, ssm, tails = blk.ssm.ragged_step(
                    c.scope("ssm"), y * self.ssm_in, ssm, tails, meta, batch)
            out_pools += [pool, ssm, tails]
            x = self._finish(c, blk, x, attended, scanned)
        return x, out_pools + [rows], None
