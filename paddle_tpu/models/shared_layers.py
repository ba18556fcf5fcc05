"""The served layers that more than one model file builds from: a plain
product under a scope, rotary positions by rotate-half, grouped-query
attention over a paged pool, the two gated feed-forward layouts, and the
routed experts. Each model file imports from here and from
`models/step_rows.py`, never from another model file. Parameter paths
come from the attribute a model keeps a layer under and from the scope
strings below, so where a layer is defined changes none.

The two gated FFNs and the rotary embeddings of `latent_moe.py`
(interleaved pairs) and of this module (rotate-half) are different
published layouts, not copies of one another.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import grouped_product as grouped
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Linear, RMSNorm


def dense(cx: Context, name: str, x, features: int, dtype, param_dtype,
          bias: bool = False, out=None):
    """x @ W (+ b) under the scope `name`; `out` is the product's
    element type (float32 keeps the accumulator)."""
    c = cx.scope(name)
    w = c.param("weight", (x.shape[-1], features), I.glorot_uniform,
                param_dtype)
    y = jnp.matmul(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out or dtype)
    if bias:
        y = y + c.param("bias", (features,), I.normal(0.0, 0.02),
                        param_dtype).astype(y.dtype)
    return y


def rotate(x, positions, theta: float):
    """Rotary embedding over the whole last axis, rotate-half: x
    [..., T, H, D] float32, positions [..., T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


class Attention(Module):
    """GQA with rotary over the whole head and a key multiplier; with
    `qk_norm_eps`, q and k each through an RMSNorm over the head (a
    learned scale of head_dim) before the rotary. `rotary=False` leaves
    out the rotary; `window` lets a query see only the `window` newest
    positions up to its own; `name` names the ragged kernel's call.
    `kv_row` is what one pool's row holds."""

    def __init__(self, model_dim, num_heads, num_kv_heads, head_dim, theta,
                 key_multiplier, dtype, param_dtype, qk_norm_eps=None,
                 rotary: bool = True, window=None, name=None):
        super().__init__()
        self.model_dim, self.num_heads = model_dim, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.groups = num_heads // num_kv_heads
        self.theta, self.key_multiplier = float(theta), float(key_multiplier)
        self.rotary, self.window, self.kernel_name = rotary, window, name
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
        qkv = dense(cx, "qkv", y, (h + 2 * kvh) * hd, self.dtype,
                    self.param_dtype)
        q = qkv[..., :h * hd].reshape(lead + (h, hd))
        k = qkv[..., h * hd:(h + kvh) * hd].reshape(lead + (kvh, hd))
        v = qkv[..., (h + kvh) * hd:].reshape(lead + (kvh, hd))
        if self.qk_norm:
            q, k = self.q_norm(cx, q), self.k_norm(cx, k)
        q, k = q.astype(jnp.float32), k.astype(jnp.float32) * \
            self.key_multiplier
        if self.rotary:
            q = rotate(q, positions, self.theta)
            k = rotate(k, positions, self.theta)
        return q.astype(self.dtype), k.astype(self.dtype), v

    def _finish(self, cx: Context, att):
        att = att.reshape(att.shape[:-2] + (-1,)).astype(self.dtype)
        return dense(cx, "o", att, self.model_dim, self.dtype,
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
        seen = pos[None, :] <= pos[:, None]
        if self.window is not None:
            seen = seen & (pos[None, :] > pos[:, None] - self.window)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        att = jnp.einsum("bkgqj,bjkd->bqkgd", a.astype(v.dtype), v)
        return self._finish(cx, att.reshape(b, t, self.num_heads,
                                            self.head_dim))

    def ragged_step(self, cx: Context, y, pool, batch, table=None,
                    rows_at=None):
        """y [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`); the kernel runs over the flat
        packing. `table` and `rows_at` are the pool's block tables and
        the pool rows the tokens are written to (a window ring's,
        `StepBatch.ring_rows`), the step's paged ones by default.
        Returns (output, pool)."""
        if table is None:
            table, rows_at = batch.block_tables, batch.slots
        q, k, v = self._project(cx, y, batch.positions)
        pool = paged.write_kv(pool, rows_at, k, v)
        att = paged.ragged_paged_attention(
            batch.packing.expand(q), pool, table, batch.context_lens,
            batch.q_starts, batch.tile_rows, batch.tile_offs,
            scale=self.scale, groups=self.groups, window=self.window,
            name=self.kernel_name)
        return self._finish(cx, batch.packing.compact(att)), pool


class PackedGatedFFN(Module):
    """W2 (up . silu(gate_scale . gate)), [gate | up] = W1 y."""

    def __init__(self, model_dim, ffn_dim, dtype, param_dtype,
                 gate_scale: float = 1.0):
        super().__init__()
        self.model_dim, self.ffn_dim = model_dim, ffn_dim
        self.dtype, self.param_dtype = dtype, param_dtype
        self.gate_scale = gate_scale

    def forward(self, cx: Context, y):
        gu = dense(cx, "w1", y, 2 * self.ffn_dim, self.dtype,
                   self.param_dtype)
        gate = gu[..., :self.ffn_dim]
        if self.gate_scale != 1.0:
            gate = gate * self.gate_scale
        h = gu[..., self.ffn_dim:] * jax.nn.silu(gate)
        return dense(cx, "w2", h, self.model_dim, self.dtype,
                     self.param_dtype)


class GatedFFN(Module):
    """down(silu(gate x) * up x), no biases."""

    def __init__(self, model_dim: int, hidden_dim: int, dtype=jnp.float32,
                 param_dtype=jnp.float32):
        super().__init__()
        kw = dict(use_bias=False, dtype=dtype, param_dtype=param_dtype)
        self.gate = Linear(hidden_dim, **kw)
        self.up = Linear(hidden_dim, **kw)
        self.down = Linear(model_dim, **kw)

    def forward(self, cx: Context, x):
        return self.down(cx, jax.nn.silu(self.gate(cx, x)) * self.up(cx, x))


class RoutedExperts(Module):
    """`num_experts` routed gated-SiLU experts of which each token takes
    `top_k`, plus `num_shared` always-on ones (one FFN of their summed
    width; none at 0). Router: scores = sigmoid(W_g x) in float32; the
    chosen are the top_k of scores + bias; their weights the scores
    (without the bias) over their sum + `eps`, times `scaling`.

    Sorted and dropless: the step's (row, choice) pairs are ordered by
    expert, the grouped products run over the expert groups
    (`kernels/grouped_product.py`: on the TPU one Pallas call for the
    gate and up, one for the down, each reading a touched expert's
    weights once), the results are un-sorted, weighted and summed, and
    the shared expert is added. No capacity, so no token is dropped
    whatever the imbalance.

    A SHARE of an expert-parallel layer: with `expert_shards` S > 1 the
    router scores S x `num_experts` experts and this layer holds
    `num_experts` of them, those of `rank` r, [r E, (r + 1) E); a
    token's weights are normalised over all its top_k, held or not. A
    pair whose expert is held elsewhere takes the padding id, sorts
    behind every held expert, is visited by no row tile and adds
    nothing: the layer's output is this share's part of the sum (and the
    shared expert, which every share computes alike). Nothing stands in
    for the other shares or the exchange with them."""

    def __init__(self, model_dim: int, expert_dim: int, num_experts: int,
                 top_k: int, num_shared: int = 1, scaling: float = 1.0,
                 dtype=jnp.float32, param_dtype=jnp.float32,
                 eps: float = 1e-20, expert_shards: int = 1, rank: int = 0):
        super().__init__()
        if not 0 <= rank < expert_shards:
            raise ValueError(f"rank {rank} of {expert_shards} expert shards")
        self.model_dim, self.expert_dim = model_dim, expert_dim
        self.num_experts, self.top_k = num_experts, top_k
        self.expert_shards, self.rank = expert_shards, rank
        self.scaling, self.eps = scaling, eps
        self.dtype, self.param_dtype = dtype, param_dtype
        self.num_shared = num_shared
        if num_shared:
            self.shared = GatedFFN(model_dim, expert_dim * num_shared, dtype,
                                   param_dtype)

    def _route(self, cx: Context, x):
        """x [T, d] -> (chosen [T, k] int32, weights [T, k] float32)."""
        c = cx.scope("router")
        routed = self.num_experts * self.expert_shards
        w = c.param("weight", (self.model_dim, routed), I.glorot_uniform,
                    self.param_dtype)
        b = c.param("bias", (routed,), I.normal(0.0, 0.02),
                    self.param_dtype)
        scores = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(scores + b.astype(jnp.float32), self.top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = (picked / (picked.sum(axis=-1, keepdims=True) + self.eps)
                   * self.scaling)
        return chosen.astype(jnp.int32), weights

    def forward(self, cx: Context, x, real=None):
        """x [T, d] -> (y [T, d], tokens per held expert [E] int32, the
        router's choices [T, k] over all the experts). `real` [T] bool
        marks the rows that are tokens; the others are routed to no
        expert, counted nowhere, and come out as the shared expert's
        output alone, or zeros (nobody reads them). A share
        (`expert_shards` > 1) counts one entry more, [E + 1]: the real
        pairs sent to experts held elsewhere."""
        t, d = x.shape
        e, k, f = self.num_experts, self.top_k, self.expert_dim
        routed, weights = self._route(cx, x)
        c = cx.scope("experts")
        gate = c.param("gate", (e, d, f), I.glorot_uniform, self.param_dtype)
        up = c.param("up", (e, d, f), I.glorot_uniform, self.param_dtype)
        down = c.param("down", (e, f, d), I.glorot_uniform, self.param_dtype)
        with jax.named_scope("moe_experts"):
            ids, away = routed, None
            if self.expert_shards > 1:
                ids = routed - self.rank * e
                held = (ids >= 0) & (ids < e)
                away = jnp.sum(~held if real is None else
                               ~held & real[:, None], dtype=jnp.int32)
                ids = jnp.where(held, ids, e)
            # padding takes expert id E, which sorts behind every expert
            flat = (ids if real is None else
                    jnp.where(real[:, None], ids, e)).reshape(-1)  # [T*k]
            order = jnp.argsort(flat, stable=True)
            counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
            xs = jnp.take(x.astype(self.dtype), order // k, axis=0)
            h = grouped.gated_grouped_product(
                xs, gate.astype(self.dtype), up.astype(self.dtype), counts)
            ys = grouped.grouped_product(h, down.astype(self.dtype), counts)
            # un-sort: pair (row, choice) sits at inverse[row * k + choice]
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=order.dtype))
            pairs = jnp.take(ys, inverse, axis=0).reshape(t, k, d)
            y = jnp.einsum("tkd,tk->td", pairs.astype(jnp.float32), weights)
        y = y.astype(self.dtype)
        if self.num_shared:
            y = y + self.shared(cx, x)
        if away is not None:
            counts = jnp.concatenate([counts, away[None]])
        return y, counts, routed
