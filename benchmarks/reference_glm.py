"""The plain reference of the latent-attention, routed-expert decoder
(`benchmarks/configs/glm-4.7-flash.json`): its forward pass in
straightforward `jax.numpy`, float32, matmuls at "highest" precision, no
kernel, no cache, no sort. It imports nothing of the program.

The block, from the configuration's published keys (d = hidden_size, H
heads, no bias anywhere, no embedding scale, no position table):

    x <- x + MLA(RMSNorm(x));  x <- x + F(RMSNorm(x))
    F = the dense gated FFN in the first `first_k_dense_replace` layers,
        the expert layer after them; a final RMSNorm; an untied head.

MLA, in the published (un-absorbed) form:
    c_q = RMSNorm(W_qa x);  q_h = [q_nope_h | q_rope_h] = W_qb c_q
    [c_kv | k_r] = W_kva x;  c_kv <- RMSNorm(c_kv)
    q_rope_h <- RoPE(q_rope_h, p);  k_rope = RoPE(k_r, p)  (one for all heads)
    [k_nope_h | v_h] = W_kvb c_kv
    score_h(p, s) = (q_nope_h . k_nope_h,s + q_rope_h . k_rope,s)
                    / sqrt(qk_nope_head_dim + qk_rope_head_dim)
    o_h = sum_s softmax_s(score_h)(p, s) v_h,s;  out = W_o [o_1 .. o_H]
Expert layer: s = sigmoid(W_g x) in float32; the experts chosen are the
top `num_experts_per_tok` of s + b (b the selection bias; n_group =
topk_group = 1, so no group limit); their weights are s_e (without b)
over their sum (+1e-20), times `routed_scaling_factor`;
y = sum_e w_e E_e(x) + S(x), every E_e and the shared S a gated SiLU
FFN. Each expert runs over all tokens and a mask keeps those routed to
it: nothing is sorted, nothing can be dropped.

Departures from the published description, each also in the
configuration file: the rotary pairing is the interleaved one of the
DeepSeek-V3 code this family follows (`assumed.rotary`; with seeded
weights another pairing is a permutation of columns); b is seeded and
held fixed (`assumed.selection_bias`); the multi-token-prediction layer
is left out (`reduced`: the model's own logits do not depend on it).

A layer's weights are made and used one layer at a time
(`weights_glm.layer`): a float32 expert layer is 2.54 GB at the
published widths. Attention runs in blocks of queries.

`precision`: "f32" is the reference proper. "fp8" is the control, the
nearest precision below the bf16 the configuration states: inputs and
weights of every linear layer (the router excepted: it is float32 in
program and reference alike) and the cached row [c_kv | k_rope] are
rounded to e4m3 under a scale per row of activations and per output
column of weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights_glm


def _round_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _cast(x, precision, axis=-1):
    if precision == "fp8":
        return _round_fp8(x, axis)
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return x


def _mm(x, w, precision):
    """x [..., in] @ w [in, out]; the weight's scale is per output
    column (its axis -2 is the input)."""
    return jnp.matmul(_cast(x, precision), _cast(w, precision, -2),
                      precision="highest")


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """Rotary embedding over all of x's last axis, interleaved pairs
    (x[2i], x[2i+1]); x [T, ..., r], positions [T]."""
    r = x.shape[-1]
    inv = 1.0 / jnp.power(theta, jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T, r/2]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _gated(x, gate, up, down, precision):
    h = jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision)
    return _mm(h, down, precision)


def attention(x, p, m, eps, theta, precision):
    """x [T, d] -> [T, d]: one sequence from position 0, causal."""
    t = x.shape[0]
    h, nope, rp, vd = m["heads"], m["nope"], m["rope"], m["v"]
    pos = jnp.arange(t)
    c_q = rms_norm(_mm(x, p["q_a"]["weight"], precision),
                   p["q_norm"]["scale"], eps)
    q = _mm(c_q, p["q_b"]["weight"], precision).reshape(t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, theta)
    kv = _mm(x, p["kv_a"]["weight"], precision)
    c_kv = rms_norm(kv[:, :m["kv_rank"]], p["kv_norm"]["scale"], eps)
    k_rope = rope(kv[:, m["kv_rank"]:], pos, theta)            # [T, rope]
    if precision == "fp8":      # the row as an 8-bit cache holds it
        row = _round_fp8(jnp.concatenate([c_kv, k_rope], axis=-1), -1)
        c_kv, k_rope = row[:, :m["kv_rank"]], row[:, m["kv_rank"]:]
    kvb = _mm(c_kv, p["kv_b"]["weight"], precision).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = 1.0 / math.sqrt(nope + rp)
    qb = 512 if t % 512 == 0 else 128 if t % 128 == 0 else t

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision="highest")
             + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision="highest"))
        causal = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
        a = jax.nn.softmax(jnp.where(causal[None], s * scale, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision="highest")

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * vd)
    return _mm(o, p["o"]["weight"], precision)


def route(x, p, top_k: int, scaling: float):
    """(weights [T, E] with zeros off the chosen experts, chosen [T, k]).
    Float32 whatever the precision of the rest."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["weight"],
                                  precision="highest"))
    _, chosen = jax.lax.top_k(s + p["router"]["bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * scaling
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(w)
    return dense, chosen


def expert_layer(x, p, top_k, scaling, precision):
    """x [T, d] -> (y [T, d], chosen [T, k]). Every expert over every
    token, kept by the mask of its routing weight."""
    w, chosen = route(x, p, top_k, scaling)

    def one(y, e):
        gate, up, down, we = e
        return y + we[:, None] * _gated(x, gate, up, down, precision), None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (ex["gate"], ex["up"], ex["down"], w.T))
    sh = p["shared"]
    y = y + _gated(x, sh["gate"]["weight"], sh["up"]["weight"],
                   sh["down"]["weight"], precision)
    return y, chosen


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _layer(x, p, m_items, eps, theta, top_k, scaling, precision):
    """x [G, T, d] through one layer, a sequence at a time. Returns
    (x, chosen [G, T, k] int32; zeros for a dense layer)."""
    m = dict(m_items)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def one(x):
        x = x + attention(rms_norm(x, p["ln1"]["scale"], eps), p["attn"], m,
                          eps, theta, precision)
        hidden = rms_norm(x, p["ln2"]["scale"], eps)
        if "ffn" in p:
            f = p["ffn"]
            y = _gated(hidden, f["gate"]["weight"], f["up"]["weight"],
                       f["down"]["weight"], precision)
            chosen = jnp.zeros((x.shape[0], top_k), jnp.int32)
        else:
            y, chosen = expert_layer(hidden, p["moe"], top_k, scaling,
                                     precision)
        return x + y, chosen

    return jax.lax.map(one, x)


def hidden(cfg: dict, seed: int, tokens, precision: str = "f32",
           weights=weights_glm):
    """tokens [G, T] -> (residual stream before the final norm
    [G, T, d], chosen experts [layers, G, T, k]). `weights` is where
    the leaves come from (`embed`, `layer`, `norm_f`, `head`); a test
    may hand in altered ones."""
    m = weights_glm.dims(cfg)
    table = weights.embed(cfg, seed)
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    del table
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        p = weights.layer(cfg, seed, i)
        x, c = _layer(x, p, tuple(sorted(m.items())),
                      float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                      int(cfg["num_experts_per_tok"]),
                      float(cfg["routed_scaling_factor"]), precision)
        del p
        chosen.append(c)
    return x, jnp.stack(chosen)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(x, rows, scale, head, eps, precision):
    """x [G, T, d], rows [G, R] -> logits [G, R, V]."""
    h = rms_norm(jnp.take_along_axis(x, rows[..., None], axis=1),
                 scale.astype(jnp.float32), eps)
    return _mm(h, head.astype(jnp.float32), precision)


def logits_at(cfg: dict, seed: int, tokens, rows, precision: str = "f32",
              weights=weights_glm):
    """Logits [G, R, V] of padded sequences `tokens` [G, T] at the
    positions `rows` [G, R], and the experts chosen [layers, G, T, k]
    (zeros in a dense layer). Padding after a position cannot reach it:
    the attention is causal and every other operation is per token."""
    x, chosen = hidden(cfg, seed, tokens, precision, weights)
    return _logits(x, rows, weights.norm_f(cfg, seed),
                   weights.head(cfg, seed),
                   float(cfg["rms_norm_eps"]), precision), chosen


@functools.partial(jax.jit, static_argnums=(6,))
def _gaps(x, rows, served, other, scale, head, eps):
    """How far the float32 logit of `served` [G, R] and of `other`
    [G, R] lies below the float32 best, a sequence at a time (the
    logits of one are [R, V])."""
    def one(args):
        x, rows, served, other = args
        ref = _logits(x[None], rows[None], scale, head, eps, "f32")[0]
        best = ref.max(axis=-1)

        def below(tok):
            return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return below(served), below(other)
    return jax.lax.map(one, (x, rows, served, other))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _argmax(x, rows, scale, head, eps, precision):
    def one(args):
        x, rows = args
        return _logits(x[None], rows[None], scale, head, eps,
                       precision)[0].argmax(axis=-1).astype(jnp.int32)
    return jax.lax.map(one, (x, rows))


def served_gaps(cfg: dict, seed: int, tokens, rows, served, control=None):
    """For each of G padded sequences `tokens` [G, T], at the positions
    `rows` [G, R]: how far the served token's float32 logit lies below
    the float32 best and (with `control`) how far the token that the
    lower precision puts first does."""
    eps = float(cfg["rms_norm_eps"])
    scale, head = weights_glm.norm_f(cfg, seed), weights_glm.head(cfg, seed)
    other = served
    if control is not None:
        x, _ = hidden(cfg, seed, tokens, control)
        other = _argmax(x, rows, scale, head, eps, control)
        del x
    x, _ = hidden(cfg, seed, tokens, "f32")
    got, low = _gaps(x, rows, served, other, scale, head, eps)
    return got, (low if control is not None else jnp.zeros_like(got))
