"""The plain reference of the short-convolution, routed-expert decoder
(`benchmarks/configs/lfm2-8b-a1b.json`; LFM2-MoE, the `lfm2_moe`
modelling code of Hugging Face `transformers`): its forward pass in
straightforward `jax.numpy`, float32, matmuls at "highest" precision, no
kernel, no cache, no batching, no sort. It imports nothing of the
program.

Trunk: x0 = E[tok]. Layer i: h = x + Mixer_i(RMSNorm(x)),
x = h + F_i(RMSNorm(h)). After the last layer RMSNorm, then
logits = E h (the head is the table). RMSNorm x . rsqrt(mean x^2 + eps)
. g. No biases. The mixer is `layer_types[i]`:

conv            [B | C | x~] = W_in y; u = B . x~;
                v_t = w_0 . u_{t-2} + w_1 . u_{t-1} + w_2 . u_t
                (zeros before position 0); W_out (C . v).
full_attention  q = RMSNorm_head(W_q y) (32 heads of 64),
                k = RMSNorm_head(W_k y), v = W_v y (8 heads); rotary over
                the whole head, rotate-half, theta; causal softmax at
                1/8, query heads 4g .. 4g+3 over kv head g; W_o.

F_i: the first `num_dense_layers` W_down (silu(W_gate y) . W_up y); the
rest s = sigmoid(W_g y) in float32, the experts chosen the top
`num_experts_per_tok` of s + b (b the expert bias), their weights s_e
(without b) over their sum + 1e-6, times `routed_scaling_factor`;
y = sum_e w_e E_e(y), every E_e a gated SiLU FFN, no shared expert. Each
expert runs over every token and a mask keeps those routed to it.

Departures from the published description, each also in the
configuration's `assumed`: the tied head, the router's epsilon, the
order [B | C | x~], a conv state of two values.

A layer's weights are made and used one layer at a time
(`weights_lfm2.layer`); projections and attention run in blocks of rows.

`precision`: "f32" is the reference proper. "fp8" is the control, the
nearest precision below the bf16 the configuration states: inputs and
weights of every linear layer (the router excepted: it is float32 in
program and reference alike) and the cached rows (k and v) are rounded
to e4m3 under a scale per row of activations and per output column of
weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights_lfm2 as W
from benchmarks.reference_sala import (_by_rows, _mm, _round_fp8, rms_norm,
                                       rotate)

ROWS = 128      # query rows a block of the attention


def short_conv(y, p, m, precision):
    """y [T, d] (normed) -> the gated short convolution [T, d]: one
    sequence from position 0."""
    d, k, t = m["d"], m["conv"], y.shape[0]
    bcx = _by_rows(lambda yb: _mm(yb, p["in_proj"]["weight"], precision), y)
    u = bcx[:, :d] * bcx[:, 2 * d:]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    w = p["conv"]["weight"]
    v = sum(w[j] * padded[j:j + t] for j in range(k))
    return _by_rows(lambda ob: _mm(ob, p["out_proj"]["weight"], precision),
                    bcx[:, d:2 * d] * v)


def attention(y, p, m, precision):
    """y [T, d] (normed) -> GQA with QK-norm [T, d]: one sequence from
    position 0."""
    t = y.shape[0]
    h, kvh, hd = m["heads"], m["kv_heads"], m["hd"]
    g = h // kvh
    qkv = _by_rows(lambda yb: _mm(yb, p["qkv"]["weight"], precision), y)
    q = rms_norm(qkv[:, :h * hd].reshape(t, h, hd), p["q_norm"]["scale"],
                 m["eps"])
    k = rms_norm(qkv[:, h * hd:(h + kvh) * hd].reshape(t, kvh, hd),
                 p["k_norm"]["scale"], m["eps"])
    v = qkv[:, (h + kvh) * hd:].reshape(t, kvh, hd)
    pos = jnp.arange(t)
    q, k = rotate(q, pos, m["theta"]), rotate(k, pos, m["theta"])
    if precision == "fp8":      # as an 8-bit cache would hold them
        k, v = _round_fp8(k, -1), _round_fp8(v, -1)
    qb = ROWS if t % ROWS == 0 else t

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb).reshape(
            qb, kvh, g, hd)
        s = jnp.einsum("qkgd,jkd->kgqj", qs, k,
                       precision="highest") / math.sqrt(hd)
        seen = pos[None, :] <= rows[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqj,jkd->qkgd", a, v,
                          precision="highest").reshape(qb, h * hd)

    att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * hd)
    return _by_rows(lambda ob: _mm(ob, p["o"]["weight"], precision), att)


def _gated(x, gate, up, down, precision):
    h = jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision)
    return _mm(h, down, precision)


def route(x, p, top_k: int, scaling: float):
    """(weights [T, E] with zeros off the chosen experts, chosen [T, k]).
    Float32 whatever the precision of the rest."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["weight"],
                                  precision="highest"))
    _, chosen = jax.lax.top_k(s + p["router"]["bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6) * scaling
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(w)
    return dense, chosen


def expert_layer(x, p, m, precision):
    """x [T, d] -> y [T, d]: every expert over every token, kept by the
    mask of its routing weight."""
    w, _ = route(x, p, m["top_k"], m["scaling"])

    def one(y, e):
        gate, up, down, we = e
        return y + we[:, None] * _gated(x, gate, up, down, precision), None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (ex["gate"], ex["up"], ex["down"], w.T))
    return y


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, p, m_items, precision):
    """x [G, T, d] through one layer, a sequence at a time."""
    m = dict(m_items)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def one(x):
        y = rms_norm(x, p["ln1"]["scale"], m["eps"])
        if "conv" in p:
            h = x + short_conv(y, p["conv"], m, precision)
        else:
            h = x + attention(y, p["attn"], m, precision)
        y = rms_norm(h, p["ln2"]["scale"], m["eps"])
        if "ffn" in p:
            f = p["ffn"]
            return h + _gated(y, f["gate"]["weight"], f["up"]["weight"],
                              f["down"]["weight"], precision)
        return h + expert_layer(y, p["moe"], m, precision)

    return jax.lax.map(one, x)


def hidden(cfg: dict, seed: int, tokens, precision: str = "f32",
           weights=W):
    """tokens [G, T] -> the residual stream before the final norm
    [G, T, d]. `weights` is where the leaves come from (`embed`,
    `layer`, `norm_f`); a test may hand in altered ones."""
    m_items = tuple(sorted(W.dims(cfg).items()))
    x = jnp.take(weights.embed(cfg, seed), tokens, axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = weights.layer(cfg, seed, i)
        x = _layer(x, p, m_items, precision)
        del p
    return x


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(x, rows, scale, table, eps, precision):
    """x [G, T, d], rows [G, R] -> logits [G, R, V]."""
    h = rms_norm(jnp.take_along_axis(x, rows[..., None], axis=1),
                 scale.astype(jnp.float32), eps)
    return _mm(h, table.astype(jnp.float32).T, precision)


def logits_at(cfg: dict, seed: int, tokens, rows, precision: str = "f32",
              weights=W):
    """Logits [G, R, V] of padded sequences `tokens` [G, T] at the
    positions `rows` [G, R]. Padding after a position cannot reach it:
    attention and convolution are causal and every other operation is
    per token."""
    x = hidden(cfg, seed, tokens, precision, weights)
    return _logits(x, rows, weights.norm_f(cfg, seed),
                   weights.embed(cfg, seed), W.dims(cfg)["eps"], precision)


@functools.partial(jax.jit, static_argnums=(6,))
def _gaps(x, rows, served, other, scale, table, eps):
    """How far the float32 logit of `served` [G, R] and of `other`
    [G, R] lies below the float32 best, a sequence at a time."""
    def one(args):
        x, rows, served, other = args
        ref = _logits(x[None], rows[None], scale, table, eps, "f32")[0]
        best = ref.max(axis=-1)

        def below(tok):
            return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return below(served), below(other)
    return jax.lax.map(one, (x, rows, served, other))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _argmax(x, rows, scale, table, eps, precision):
    def one(args):
        x, rows = args
        return _logits(x[None], rows[None], scale, table, eps,
                       precision)[0].argmax(axis=-1).astype(jnp.int32)
    return jax.lax.map(one, (x, rows))


def served_gaps(cfg: dict, seed: int, tokens, rows, served, control=None):
    """For each of G padded sequences `tokens` [G, T], at the positions
    `rows` [G, R]: how far the served token's float32 logit lies below
    the float32 best and (with `control`) how far the token that the
    lower precision puts first does."""
    eps = W.dims(cfg)["eps"]
    scale, table = W.norm_f(cfg, seed), W.embed(cfg, seed)
    other = served
    with jax.default_matmul_precision("highest"):
        if control is not None:
            x = hidden(cfg, seed, tokens, control)
            other = _argmax(x, rows, scale, table, eps, control)
            del x
        x = hidden(cfg, seed, tokens, "f32")
        got, low = _gaps(x, rows, served, other, scale, table, eps)
    return got, (low if control is not None else jnp.zeros_like(got))
