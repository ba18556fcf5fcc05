"""The plain reference of the window and full GQA decoder with routed
experts (`benchmarks/configs/k-exaone-236b-a23b.json`; K-EXAONE, the
`exaone_moe` family, which extends EXAONE 4.0), as one chip's share of
its expert-parallel deployment: its forward pass in straightforward
`jax.numpy`, float32, matmuls at "highest" precision, no kernel, no
cache, no batching, no sort. It imports nothing of the program.

Trunk: x0 = E[tok]. Layer i: h = x + RMSNorm(Attn_i(x)),
x = h + RMSNorm(F_i(h)) (post-norm: nothing is normed before the
attention or the FFN). After the last layer RMSNorm, then
logits = W_head h (untied, over the vocabulary slice). RMSNorm
x . rsqrt(mean x^2 + eps) . g. No biases.

Attn_i   q = RMSNorm_head(W_q x) (64 heads of 128),
         k = RMSNorm_head(W_k x), v = W_v x (8 heads); on a
         "sliding_attention" layer rotary over the whole head,
         rotate-half, theta, and a query at i sees keys i - W < j <= i;
         on a "full_attention" layer no rotary and every j <= i; softmax
         at 1/sqrt(128), query heads 8g .. 8g+7 over kv head g; W_o.

F_i: "dense" W_down (silu(W_gate y) . W_up y); "sparse" the routed
experts: s = sigmoid(W_r y) in float32 over ALL num_experts x
expert_shards experts, the chosen the top num_experts_per_tok of s + b
(b the selection bias), their weights s_e (without b) over the chosen
scores' sum + 1e-20, times routed_scaling_factor; y = shared(y) +
sum over the chosen experts that this share HOLDS (rank r: experts
r E .. r E + E - 1) of w_e E_e(y). A chosen expert held elsewhere adds
nothing here, and its weight still counts in the sum it is normalised
by: the share's part of the deployment's layer. Each held expert runs
over every token and a mask keeps those routed to it.

Departures from the published description, each also in the
configuration's `assumed`: the rotary only on the window layers, the
norms' placement, the selection bias, the router's epsilon.

A layer's weights are made and used one layer at a time
(`weights_kexaone.layer`); projections and attention run in blocks of
rows.

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

from benchmarks import weights_kexaone as W
from benchmarks.reference_sala import (_by_rows, _mm, _round_fp8, rms_norm,
                                       rotate)

ROWS = 128      # query rows a block of the attention


def attention(x, p, m, window, precision):
    """x [T, d] -> GQA with QK-norm [T, d]: one sequence from position
    0; `window` None on a full layer (and no rotary there)."""
    t = x.shape[0]
    h, kvh, hd = m["heads"], m["kv_heads"], m["hd"]
    g = h // kvh
    qkv = _by_rows(lambda xb: _mm(xb, p["qkv"]["weight"], precision), x)
    q = rms_norm(qkv[:, :h * hd].reshape(t, h, hd), p["q_norm"]["scale"],
                 m["eps"])
    k = rms_norm(qkv[:, h * hd:(h + kvh) * hd].reshape(t, kvh, hd),
                 p["k_norm"]["scale"], m["eps"])
    v = qkv[:, (h + kvh) * hd:].reshape(t, kvh, hd)
    pos = jnp.arange(t)
    if window is not None:
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
        if window is not None:
            seen = seen & (pos[None, :] > rows[:, None] - window)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqj,jkd->qkgd", a, v,
                          precision="highest").reshape(qb, h * hd)

    att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * hd)
    return _by_rows(lambda ob: _mm(ob, p["o"]["weight"], precision), att)


def _gated(x, gate, up, down, precision):
    h = jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision)
    return _mm(h, down, precision)


def route(x, p, top_k: int, scaling: float):
    """(weights [T, E x shards] with zeros off the chosen experts,
    chosen [T, k]) over every expert of the deployment. Float32
    whatever the precision of the rest."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["weight"],
                                  precision="highest"))
    _, chosen = jax.lax.top_k(s + p["router"]["bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * scaling
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(w)
    return dense, chosen


def expert_layer(x, p, m, precision):
    """x [T, d] -> y [T, d]: the shared expert, and every HELD expert
    over every token, kept by the mask of its routing weight."""
    w, _ = route(x, p, m["top_k"], m["scaling"])
    held = jax.lax.dynamic_slice_in_dim(w, m["rank"] * m["experts"],
                                        m["experts"], axis=1)

    def one(y, e):
        gate, up, down, we = e
        return y + we[:, None] * _gated(x, gate, up, down, precision), None

    ex, sh = p["experts"], p["shared"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (ex["gate"], ex["up"], ex["down"], held.T))
    return y + _gated(x, sh["gate"]["weight"], sh["up"]["weight"],
                      sh["down"]["weight"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, m_items, window, precision):
    """x [G, T, d] through one layer, a sequence at a time."""
    m = dict(m_items)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def one(x):
        h = x + rms_norm(attention(x, p["attn"], m, window, precision),
                         p["ln_attn"]["scale"], m["eps"])
        if "ffn" in p:
            f = p["ffn"]
            y = _gated(h, f["gate"]["weight"], f["up"]["weight"],
                       f["down"]["weight"], precision)
        else:
            y = expert_layer(h, p["moe"], m, precision)
        return h + rms_norm(y, p["ln_ffn"]["scale"], m["eps"])

    return jax.lax.map(one, x)


def hidden(cfg: dict, seed: int, tokens, precision: str = "f32",
           weights=W):
    """tokens [G, T] -> the residual stream before the final norm
    [G, T, d]. `weights` is where the leaves come from (`embed`,
    `layer`, `norm_f`, `head`); a test may hand in altered ones."""
    m = W.dims(cfg)
    m_items = tuple(sorted(m.items()))
    x = jnp.take(weights.embed(cfg, seed), tokens, axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = weights.layer(cfg, seed, i)
        window = (m["window"] if cfg["layer_types"][i] == "sliding_attention"
                  else None)
        x = _layer(x, p, m_items, window, precision)
        del p
    return x


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(x, rows, scale, head, eps, precision):
    """x [G, T, d], rows [G, R] -> logits [G, R, V]."""
    h = rms_norm(jnp.take_along_axis(x, rows[..., None], axis=1),
                 scale.astype(jnp.float32), eps)
    return _mm(h, head.astype(jnp.float32), precision)


def logits_at(cfg: dict, seed: int, tokens, rows, precision: str = "f32",
              weights=W):
    """Logits [G, R, V] of padded sequences `tokens` [G, T] at the
    positions `rows` [G, R]. Padding after a position cannot reach it:
    attention is causal and every other operation is per token."""
    x = hidden(cfg, seed, tokens, precision, weights)
    return _logits(x, rows, weights.norm_f(cfg, seed),
                   weights.head(cfg, seed), W.dims(cfg)["eps"], precision)


@functools.partial(jax.jit, static_argnums=(6,))
def _gaps(x, rows, served, other, scale, head, eps):
    """How far the float32 logit of `served` [G, R] and of `other`
    [G, R] lies below the float32 best, a sequence at a time."""
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
    eps = W.dims(cfg)["eps"]
    scale, head = W.norm_f(cfg, seed), W.head(cfg, seed)
    other = served
    with jax.default_matmul_precision("highest"):
        if control is not None:
            x = hidden(cfg, seed, tokens, control)
            other = _argmax(x, rows, scale, head, eps, control)
            del x
        x = hidden(cfg, seed, tokens, "f32")
        got, low = _gaps(x, rows, served, other, scale, head, eps)
    return got, (low if control is not None else jnp.zeros_like(got))
