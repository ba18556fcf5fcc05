"""The plain reference of the parallel hybrid decoder
(`benchmarks/configs/falcon-h1-34b.json`; Falcon-H1, the `falcon_h1`
modelling code of Hugging Face `transformers`; Mamba-2,
arXiv:2405.21060): its forward pass in straightforward `jax.numpy`,
float32, matmuls at "highest" precision, no kernel, no cache, no
batching, a `lax.scan` over positions for the SSD in its recurrent form.
It imports nothing of the program.

Trunk: x0 = embedding_multiplier . E[tok]. Each layer:
y = RMSNorm(x); x = x + attention_out_multiplier . Attn(
attention_in_multiplier . y) + ssm_out_multiplier . Mamba2(
ssm_in_multiplier . y); x = x + MLP(RMSNorm(x)). After the last layer
RMSNorm, then logits = lm_head_multiplier . W_head h.

Attn     q = W_q y (20 heads of 128), k = key_multiplier . W_k y,
         v = W_v y (4 heads); rotary over the whole head, rotate-half,
         theta; causal softmax at 1/sqrt(128), query heads 5g .. 5g+4
         over kv head g; W_o.
MLP      W_down (up . silu(mlp_multipliers[0] . gate))
         . mlp_multipliers[1].
Mamba2   [z | x | B | C | dt] = W_in u . (ssm_multipliers per block);
         [x | B | C] <- silu(causal depthwise conv_4 + bias);
         delta = softplus(dt + dt_bias), A_h = -exp(A_log_h);
         S_t = exp(delta_t A_h) S_{t-1} + delta_t x_t (x) B_t,g,
         y_t = S_t . C_t,g + D_h x_t (head h in group h // 16);
         RMSNorm over each group of 2048 of y . silu(z) with a learned
         scale; W_out.

Departures from the published description, each also in the
configuration's `assumed`: the order of ssm_multipliers, the gated
norm's groups, rotary over the whole head, the float32 state.

A layer's weights are made and used one layer at a time
(`weights_falconh1.layer`); projections, the MLP and attention run in
blocks of rows, and the head in blocks of its vocabulary, so that 12
sequences of 3,328 positions fit beside the 5.3 GB the head would take
in float32.

`precision`: "f32" is the reference proper. "fp8" is the control, the
nearest precision below the bf16 the configuration states: inputs and
weights of every linear layer and the cached rows (k and v) are rounded
to e4m3 under a scale per row of activations and per output column of
weights. The SSD state is float32 in program, reference and control
alike.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights_falconh1 as W
from benchmarks.reference_sala import (_by_rows, _mm, _round_fp8, rms_norm,
                                       rotate)

ROWS = 128      # query rows a block of the attention


def attention(y, p, m, precision):
    """y [T, d] (normed) -> the attention branch [T, d], multipliers
    applied: one sequence from position 0."""
    t = y.shape[0]
    h, kvh, hd = m["heads"], m["kv_heads"], m["hd"]
    g = h // kvh
    qkv = _by_rows(lambda yb: _mm(yb * m["attn_in"], p["qkv"]["weight"],
                                  precision), y)
    q = qkv[:, :h * hd].reshape(t, h, hd)
    k = qkv[:, h * hd:(h + kvh) * hd].reshape(t, kvh, hd) * m["key"]
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
    return m["attn_out"] * _by_rows(
        lambda ob: _mm(ob, p["o"]["weight"], precision), att)


def mamba2(y, p, m, precision):
    """y [T, d] (normed) -> the Mamba-2 branch [T, d], multipliers
    applied: one sequence from position 0, the scan a position at a
    time."""
    t = y.shape[0]
    hs, ph, gr, n = m["ssm_heads"], m["ssm_hd"], m["groups"], m["state"]
    ds, gn = m["d_ssm"], gr * n
    mult = jnp.concatenate([jnp.full((w,), s, jnp.float32) for w, s in zip(
        (ds, ds, gn, gn, hs), m["ssm_mult"])])
    zxbcdt = _by_rows(lambda yb: _mm(yb * m["ssm_in"],
                                     p["in_proj"]["weight"], precision),
                      y) * mult
    z, xbc, dt = (zxbcdt[:, :ds], zxbcdt[:, ds:2 * ds + 2 * gn],
                  zxbcdt[:, 2 * ds + 2 * gn:])
    k = m["conv"]
    w = p["conv"]["weight"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv"]["bias"] + sum(
        w[j] * padded[j:j + t] for j in range(k)))
    x = xbc[:, :ds].reshape(t, hs, ph)
    b = jnp.repeat(xbc[:, ds:ds + gn].reshape(t, gr, n), hs // gr, axis=1)
    c = jnp.repeat(xbc[:, ds + gn:].reshape(t, gr, n), hs // gr, axis=1)
    delta = jax.nn.softplus(dt + p["dt_bias"])                  # [T, H]
    a = -jnp.exp(p["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, d_t = inp
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + b_t[:, :, None] * (d_t[:, None] * x_t)[:, None, :]
        return s, jnp.einsum("hn,hnp->hp", c_t, s, precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((hs, n, ph), jnp.float32),
                        (x, b, c, delta))
    o = (o + p["D"][:, None] * x).reshape(t, ds) * jax.nn.silu(z)
    var = jnp.mean(jnp.square(o.reshape(t, gr, -1)), axis=-1, keepdims=True)
    o = (o.reshape(t, gr, -1) * jax.lax.rsqrt(var + m["eps"])).reshape(
        t, ds) * p["norm"]["scale"]
    return m["ssm_out"] * _by_rows(
        lambda ob: _mm(ob, p["out_proj"]["weight"], precision), o)


def mlp(y, p, m, precision):
    f = m["ffn"]

    def one(yb):
        gu = _mm(yb, p["w1"]["weight"], precision)
        return _mm(gu[:, f:] * jax.nn.silu(m["mlp_mult"][0] * gu[:, :f]),
                   p["w2"]["weight"], precision)
    return m["mlp_mult"][1] * _by_rows(one, y)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, m_items, precision, parts):
    """x [G, T, d] through one layer, a sequence at a time. `parts`
    names the branches kept (the tests' ablations drop one)."""
    m = dict(m_items)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def one(x):
        y = rms_norm(x, p["ln1"]["scale"], m["eps"])
        if "attn" in parts:
            x = x + attention(y, p["attn"], m, precision)
        if "ssm" in parts:
            x = x + mamba2(y, p["ssm"], m, precision)
        return x + mlp(rms_norm(x, p["ln2"]["scale"], m["eps"]), p["ffn"],
                       m, precision)

    return jax.lax.map(one, x)


def hidden(cfg: dict, seed: int, tokens, precision: str = "f32", weights=W,
           parts=("attn", "ssm")):
    """tokens [G, T] -> the residual stream before the final norm
    [G, T, d]. `weights` is where the leaves come from; a test may hand
    in altered ones or keep fewer `parts`."""
    m = W.dims(cfg)
    x = jnp.take(weights.embed(cfg, seed), tokens, axis=0
                 ).astype(jnp.float32) * m["emb"]
    m_items = tuple(sorted(m.items()))
    for i in range(cfg["num_hidden_layers"]):
        p = weights.layer(cfg, seed, i)
        x = _layer(x, p, m_items, precision, tuple(parts))
        del p
    return x


def _vocab_blocks(v: int) -> int:
    return next((n for n in (32, 16, 8, 4, 2) if v % n == 0), 1)


def _head_pass(h, head, toks, lm, precision):
    """h [R, d] normed, head [d, V] (bf16), toks [K, R]: a block of the
    vocabulary at a time, (the best logit [R], the first id that has it
    [R], the logits of `toks` [K, R])."""
    r, v = h.shape[0], head.shape[1]
    vb = v // _vocab_blocks(v)

    def body(carry, i):
        best, arg, got = carry
        w = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1)
        lg = lm * _mm(h, w.astype(jnp.float32), precision)     # [R, VB]
        top = lg.max(axis=-1)
        better = top > best
        best = jnp.where(better, top, best)
        arg = jnp.where(better, lg.argmax(axis=-1).astype(jnp.int32)
                        + i * vb, arg)
        at = toks - i * vb
        inside = (at >= 0) & (at < vb)
        val = jnp.take_along_axis(lg[None], jnp.clip(at, 0, vb - 1)[..., None],
                                  axis=-1)[..., 0]
        return (best, arg, jnp.where(inside, val, got)), None

    init = (jnp.full((r,), -jnp.inf, jnp.float32), jnp.zeros((r,), jnp.int32),
            jnp.zeros(toks.shape, jnp.float32))
    (best, arg, got), _ = jax.lax.scan(body, init,
                                       jnp.arange(v // vb, dtype=jnp.int32))
    return best, arg, got


def _normed(x, rows, norm, eps):
    """x [T, d], rows [R] -> the final norm of those rows [R, d]."""
    return rms_norm(x[rows], norm["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _logits(x, rows, norm, head, eps, lm, precision):
    """x [G, T, d], rows [G, R] -> logits [G, R, V], whole (toy sizes)."""
    h = rms_norm(jnp.take_along_axis(x, rows[..., None], axis=1),
                 norm["scale"].astype(jnp.float32), eps)
    return lm * _mm(h, head.astype(jnp.float32), precision)


def logits_at(cfg: dict, seed: int, tokens, rows, precision: str = "f32",
              weights=W, parts=("attn", "ssm")):
    """Logits [G, R, V] of padded sequences `tokens` [G, T] at the
    positions `rows` [G, R]. Padding after a position cannot reach it:
    attention, convolution and scan are causal and every other
    operation is per token."""
    m = W.dims(cfg)
    x = hidden(cfg, seed, tokens, precision, weights, parts)
    return _logits(x, rows, weights.norm_f(cfg, seed),
                   weights.head(cfg, seed), m["eps"], m["lm_head"],
                   precision)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _gaps(x, rows, served, other, norm, head, eps, lm):
    """How far the float32 logit of `served` [G, R] and of `other`
    [G, R] lies below the float32 best, a sequence at a time."""
    def one(args):
        x, rows, served, other = args
        best, _, got = _head_pass(_normed(x, rows, norm, eps), head,
                                  jnp.stack([served, other]), lm, "f32")
        return best - got[0], best - got[1]
    return jax.lax.map(one, (x, rows, served, other))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _argmax(x, rows, norm, head, eps, lm, precision):
    def one(args):
        x, rows = args
        return _head_pass(_normed(x, rows, norm, eps), head,
                          rows[None], lm, precision)[1]
    return jax.lax.map(one, (x, rows))


def served_gaps(cfg: dict, seed: int, tokens, rows, served, control=None):
    """For each of G padded sequences `tokens` [G, T], at the positions
    `rows` [G, R]: how far the served token's float32 logit lies below
    the float32 best and (with `control`) how far the token that the
    lower precision puts first does."""
    m = W.dims(cfg)
    norm, head = W.norm_f(cfg, seed), W.head(cfg, seed)
    other = served
    if control is not None:
        x = hidden(cfg, seed, tokens, control)
        other = _argmax(x, rows, norm, head, m["eps"], m["lm_head"], control)
        del x
    x = hidden(cfg, seed, tokens, "f32")
    got, low = _gaps(x, rows, served, other, norm, head, m["eps"],
                     m["lm_head"])
    return got, (low if control is not None else jnp.zeros_like(got))
