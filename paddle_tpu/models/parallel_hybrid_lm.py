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

import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import lightning_attention as recurrence
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.models.hybrid_lm import GatedFFN, _dense
from paddle_tpu.models.sparse_linear_lm import rotate
from paddle_tpu.models.step_rows import step_rows
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, RMSNorm


class Attention(Module):
    """GQA with rotary over the whole head and a key multiplier; with
    `qk_norm_eps`, q and k each through an RMSNorm over the head (a
    learned scale of head_dim) before the rotary. `kv_row` is what one
    pool's row holds."""

    def __init__(self, model_dim, num_heads, num_kv_heads, head_dim, theta,
                 key_multiplier, dtype, param_dtype, qk_norm_eps=None):
        super().__init__()
        self.model_dim, self.num_heads = model_dim, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.groups = num_heads // num_kv_heads
        self.theta, self.key_multiplier = float(theta), float(key_multiplier)
        self.dtype, self.param_dtype = dtype, param_dtype
        self.scale = 1.0 / math.sqrt(head_dim)
        self.kv_row = (num_kv_heads, head_dim)
        self.qk_norm = qk_norm_eps is not None
        if self.qk_norm:
            self.q_norm = RMSNorm(qk_norm_eps, dtype=jnp.float32,
                                  param_dtype=param_dtype)
            self.k_norm = RMSNorm(qk_norm_eps, dtype=jnp.float32,
                                  param_dtype=param_dtype)

    def _project(self, cx: Context, y, positions):
        """y [..., T, d] -> q [..., T, H, hd], k, v [..., T, Hkv, hd]."""
        h, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        lead = y.shape[:-1]
        qkv = _dense(cx, "qkv", y, (h + 2 * kvh) * hd, self.dtype,
                     self.param_dtype)
        q = qkv[..., :h * hd].reshape(lead + (h, hd))
        k = qkv[..., h * hd:(h + kvh) * hd].reshape(lead + (kvh, hd))
        v = qkv[..., (h + kvh) * hd:].reshape(lead + (kvh, hd))
        if self.qk_norm:
            q, k = self.q_norm(cx, q), self.k_norm(cx, k)
        q = rotate(q.astype(jnp.float32), positions, self.theta)
        k = rotate(k.astype(jnp.float32) * self.key_multiplier, positions,
                   self.theta)
        return q.astype(self.dtype), k.astype(self.dtype), v

    def _finish(self, cx: Context, att):
        att = att.reshape(att.shape[:-2] + (-1,)).astype(self.dtype)
        return _dense(cx, "o", att, self.model_dim, self.dtype,
                      self.param_dtype)

    def forward(self, cx: Context, y):
        """Whole sequences y [B, T, d] from position 0."""
        b, t = y.shape[:2]
        q, k, v = self._project(cx, y, jnp.broadcast_to(jnp.arange(t),
                                                        (b, t)))
        qg = q.reshape(b, t, self.num_kv_heads, self.groups, self.head_dim)
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, k,
                       preferred_element_type=jnp.float32) * self.scale
        pos = jnp.arange(t)
        a = jax.nn.softmax(jnp.where(pos[None, :] <= pos[:, None], s,
                                     -jnp.inf), axis=-1)
        att = jnp.einsum("bkgqj,bjkd->bqkgd", a.astype(v.dtype), v)
        return self._finish(cx, att.reshape(b, t, self.num_heads,
                                            self.head_dim))

    def ragged_step(self, cx: Context, y, pool, positions, block_tables,
                    context_lens, q_starts, tile_rows, tile_offs, slots,
                    packing):
        """y [T_c, d], positions and slots [T_c]: the step's tokens
        (`packing`, `models/step_rows.py`); the kernel runs over the flat
        packing. Returns (output, pool)."""
        q, k, v = self._project(cx, y, positions)
        pool = paged.write_kv(pool, slots, k, v)
        att = paged.ragged_paged_attention(
            packing.expand(q), pool, block_tables, context_lens, q_starts,
            tile_rows, tile_offs, scale=self.scale, groups=self.groups)
        return self._finish(cx, packing.compact(att)), pool


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
        zxbcdt = _dense(cx, "in_proj", u, self.multipliers.size, self.dtype,
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
        return _dense(cx, "out_proj", g.astype(self.dtype), self.model_dim,
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

    def ragged_step(self, cx: Context, u, ssm, tails, meta, tile_offs,
                    packing):
        """u [T_c, d], the step's tokens (`packing`,
        `models/step_rows.py`); the convolution and the scan run over
        the flat packing. Returns (output, new scan state, new tails)."""
        p = self._params(cx)
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
    def __init__(self, attn: Attention, ssm: Mamba2, ffn: GatedFFN, eps,
                 dtype, param_dtype):
        super().__init__()
        self.attn = attn
        self.ssm = ssm
        self.ffn = ffn
        self.ln1 = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)
        self.ln2 = RMSNorm(eps, dtype=dtype, param_dtype=param_dtype)


class ParallelHybridLM(Module):
    """Decoder-only LM of `ParallelBlock`s: attention and a Mamba-2
    mixer in every layer. The multipliers are the configuration's
    `*_multiplier(s)`; `ssm_multipliers` scales the input projection's
    blocks z, x, B, C, dt."""

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
            GatedFFN(model_dim, ffn_dim, dtype, param_dtype,
                     gate_scale=float(mlp_multipliers[0])),
            eps, dtype, param_dtype) for _ in range(num_layers)]
        self.norm_f = RMSNorm(eps, dtype=jnp.float32, param_dtype=param_dtype)
        # what one pool's row is: every kv head's [k | v]
        self.kv_row = self.blocks[0].attn.kv_row
        # every layer keeps both kinds: its paged rows and its slot
        self.cache_layout = [{"kind": "paged", "arrays": b.ssm.state_shapes}
                             for b in self.blocks]

    def serve_metadata(self) -> dict:
        return {"model_type": "parallel_hybrid_lm",
                "config": dict(self.config), "max_len": self.max_len,
                "dtype": jnp.dtype(self.dtype).name,
                "param_dtype": self.param_dtype.name}

    def _finish(self, cx: Context, blk, x, attended, scanned):
        h = x + (self.attn_out * attended.astype(jnp.float32)
                 + self.ssm_out * scanned.astype(jnp.float32)).astype(x.dtype)
        return h + (self.mlp_out * blk.ffn(cx, blk.ln2(cx, h))).astype(
            x.dtype)

    def _logits(self, cx: Context, x):
        y = self.norm_f(cx, x)
        return self.lm_head_multiplier * _dense(
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
        return self._logits(cx, x)

    def ragged_step_paged(self, cx: Context, tokens, positions, pools,
                          block_tables, context_lens, q_starts, tile_rows,
                          tile_offs, slots, last_idx, tp=None,
                          qpools=None, qscales=None):
        """The engine's one step (`CausalLM.ragged_step_paged` has the
        contract). `pools` is the cache manager's list for this model's
        `cache_layout`: each layer's paged pool, then its scan state and
        its tails; last the ROWS table (a step row's state slot).
        Returns (logits, the same list updated). Everything but the
        kernels runs on the step's tokens alone, at the compact width
        (`models/step_rows.py`)."""
        if tp is not None or qpools:
            raise ValueError("recurrent state is served on one chip with no "
                             "int8 tier (engine/paged_cache.py)")
        *arrays, rows = pools
        arrays = iter(arrays)
        t, nt = tokens.shape[0], tile_rows.shape[0]
        positions = positions.astype(jnp.int32)
        meta = scan.tile_meta(rows[:, 0], context_lens, q_starts, tile_rows,
                              tile_offs, t // nt)
        packing = step_rows(tile_rows, tile_offs, q_starts, context_lens,
                            last_idx, t)
        tokens, positions, slots = map(packing.compact,
                                       (tokens, positions, slots))
        out_pools = []
        x = self.embed(cx, tokens) * self.embedding_multiplier   # [T_c, D]
        for blk in self.blocks:
            c = cx.scope(blk._name)
            y = blk.ln1(c, x)
            pool, ssm, tails = next(arrays), next(arrays), next(arrays)
            with jax.named_scope("parallel_mixer"):
                attended, pool = blk.attn.ragged_step(
                    c.scope("attn"), y * self.attn_in, pool, positions,
                    block_tables, context_lens, q_starts, tile_rows,
                    tile_offs, slots, packing)
                scanned, ssm, tails = blk.ssm.ragged_step(
                    c.scope("ssm"), y * self.ssm_in, ssm, tails, meta,
                    tile_offs, packing)
            out_pools += [pool, ssm, tails]
            x = self._finish(c, blk, x, attended, scanned)
        idx = packing.last
        logits = self._logits(cx, jnp.take(x, idx.reshape(-1), axis=0))
        return (logits.reshape(idx.shape + (logits.shape[-1],)),
                out_pools + [rows])
