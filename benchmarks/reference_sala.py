"""The plain reference of the sparse-and-linear decoder
(`benchmarks/configs/minicpm-sala.json`; MiniCPM4 / InfLLM-v2,
arXiv:2506.07900; Lightning Attention-2, arXiv:2401.04658): its forward
pass in straightforward `jax.numpy`, float32, matmuls at "highest"
precision, no kernel, no cache, no batching, a `lax.scan` over time for
the lightning layer, the sparse layer's selection as masks over the full
score matrix a block of query rows at a time. It imports nothing of the
program.

Trunk: x0 = scale_emb . E[tok]. Layer i: h = x + c . mixer_i(RMSNorm(x)),
x = h + c . FFN(RMSNorm(h)), c = scale_depth / sqrt(published depth);
RMSNorm with a learned scale; FFN(y) = W2 (up . silu(gate)),
[gate | up] = W1 y. After the last layer RMSNorm, then
logits = W_head (x / (hidden / dim_model_base)). The mixer is
`mixer_types[i]`:

minicpm4        q = W_q y (H heads of hd), [k | v] = W_kv y (Hkv heads);
                RMSNorm over each head of q and k with a learned scale;
                no positions. Causal softmax attention at 1/sqrt(hd),
                query head g.G .. g.G+G-1 over kv head g;
                o . sigmoid(W_gate y), then W_o. A query at position
                t >= dense_len sees only the keys of its KEPT blocks:
                compressed keys K~_j = mean(k[s j : s j + 2 s]) for the
                windows with s j + 2 s <= t + 1; p = softmax_j(q . K~_j /
                sqrt(hd)) a query head, summed over the G heads of a kv
                group; a block of `block` tokens scores the maximum of
                p over the windows that overlap it; kept: the first
                `init_blocks` blocks, every block that holds one of the
                `local` newest positions (t among them), and of the
                rest the `topk` best. Below dense_len: every key.
lightning-attn  [q | k | v] = W y (H_l heads of hd_l); RMSNorm over each
                head of q and k; rotary over the whole head, rotate-half,
                theta; S_t = lambda_h S_{t-1} + k_t^T v_t from S = 0,
                o_t = (q_t / sqrt(hd_l)) S_t;
                lambda_h = exp(-s_h (1 - l / (depth - 1) + 1e-5)),
                s_h = 2^(-8 (h + 1) / H_l), l the layer's published
                index; RMSNorm over the concatenated heads, then
                . sigmoid(W_gate y), then W_o.

Departures from the published description, each also in the
configuration's `assumed`: the selection's sizes (dense_len, kernel,
stride, block, init_blocks, local, topk), the full-width output gates,
rotary over the whole head, the decay's slopes, float32 state.

A layer's weights are made and used one layer at a time
(`weights_sala.layer`); projections, the FFN and attention run in blocks
of rows so that 33 k positions fit.

`precision`: "f32" is the reference proper. "fp8" is the control, the
nearest precision below the bf16 the configuration states: inputs and
weights of every linear layer and the cached rows (k and v, and so the
compressed keys made of them) are rounded to e4m3 under a scale per row
of activations and per output column of weights. The lightning state is
float32 in program, reference and control alike.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights_sala as W

ROWS = 128      # rows a block of the projections and of the attention


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
    column."""
    return jnp.matmul(_cast(x, precision), _cast(w, precision, -2),
                      precision="highest")


def _by_rows(fn, x):
    """fn over x [T, ...] a block of ROWS rows at a time."""
    t = x.shape[0]
    rb = ROWS if t % ROWS == 0 else t
    out = jax.lax.map(fn, x.reshape((t // rb, rb) + x.shape[1:]))
    return jax.tree.map(lambda a: a.reshape((t,) + a.shape[2:]), out)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotate(x, pos, theta):
    """x [T, H, D], pos [T]: rotary over the whole head, rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def kept(p, rows, sel):
    """p [Q, J]: a query's group-summed probabilities over the windows
    (0 where the window is not complete at the query); rows [Q] the
    queries' positions. Bool [Q, NB]: the blocks each query keeps."""
    blk, s = sel["block"], sel["stride"]
    j = jnp.arange(p.shape[1])
    nb = -(-(p.shape[1] * s + sel["kernel"]) // blk)
    b = jnp.arange(nb)
    # window j covers [s j, s j + kernel): which blocks it overlaps
    over = ((j[None, :] * s < (b[:, None] + 1) * blk)
            & (j[None, :] * s + sel["kernel"] > b[:, None] * blk))
    score = jnp.max(jnp.where(over[None], p[:, None, :], 0.0), axis=-1)
    t = rows[:, None]
    cur = t // blk
    lo = jnp.maximum(t - (sel["local"] - 1), 0) // blk
    local = (b[None, :] >= lo) & (b[None, :] <= cur)
    init = b[None, :] < sel["init_blocks"]
    rest = (b[None, :] < lo) & ~init
    ranked = jnp.where(rest, score, -jnp.inf)
    k = sel["topk"]
    padded = jnp.pad(ranked, ((0, 0), (0, max(0, k - nb))),
                     constant_values=-jnp.inf)
    kth = jax.lax.top_k(padded, k)[0][:, -1:]
    keep = init | local | (rest & (ranked >= kth))
    return jnp.where(t < sel["dense_len"], b[None, :] <= cur, keep)


def sparse_attention(y, p, m, sel, precision):
    """y [T, d] -> [T, d]: one sequence from position 0."""
    t = y.shape[0]
    h, kvh, hd = m["heads"], m["kv_heads"], m["hd"]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)

    def project(yb):
        kv = _mm(yb, p["kv"]["weight"], precision)
        return (_mm(yb, p["q"]["weight"], precision), kv[:, :kvh * hd],
                kv[:, kvh * hd:], _mm(yb, p["gate"]["weight"], precision))

    q, k, v, gate = _by_rows(project, y)
    q = rms_norm(q.reshape(t, h, hd), p["q_norm"]["scale"], m["eps"])
    k = rms_norm(k.reshape(t, kvh, hd), p["k_norm"]["scale"], m["eps"])
    v = v.reshape(t, kvh, hd)
    if precision == "fp8":      # as an 8-bit cache would hold them
        k, v = _round_fp8(k, -1), _round_fp8(v, -1)
    s, kern = sel["stride"], sel["kernel"]
    nj = max((t - kern) // s + 1, 1)
    at = jnp.arange(nj)[:, None] * s + jnp.arange(kern)[None, :]
    kbar = k[jnp.minimum(at, t - 1)].mean(axis=1)             # [J, Hkv, hd]
    if precision == "fp8":
        kbar = _round_fp8(kbar, -1)
    pos = jnp.arange(t)
    qb = ROWS if t % ROWS == 0 else t
    jdone = jnp.arange(nj) * s + kern                         # a window's end

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb).reshape(
            qb, kvh, g, hd)
        there = jdone[None, :] <= rows[:, None] + 1           # [Q, J]
        outs = []
        for a in range(kvh):        # a kv group selects for itself
            sc = jnp.einsum("qgd,jd->qgj", qs[:, a], kbar[:, a],
                            precision="highest") * scale
            sc = jnp.where(there[:, None, :], sc, -jnp.inf)
            pr = jnp.where(there[:, None, :],
                           jax.nn.softmax(sc, axis=-1), 0.0).sum(axis=1)
            pr = jnp.where(jnp.any(there, axis=-1, keepdims=True), pr, 0.0)
            keep = kept(pr, rows, sel)                        # [Q, NB]
            seen = jnp.repeat(keep, sel["block"], axis=1)[:, :t] \
                & (pos[None, :] <= rows[:, None])
            sa = jnp.einsum("qgd,kd->gqk", qs[:, a], k[:, a],
                            precision="highest") * scale
            w = jax.nn.softmax(jnp.where(seen[None], sa, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("gqk,kd->qgd", w, v[:, a],
                                   precision="highest"))
        return jnp.stack(outs, axis=1).reshape(qb, h * hd)

    att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * hd)
    return _by_rows(lambda ob: _mm(ob, p["o"]["weight"], precision),
                    att * jax.nn.sigmoid(gate))


def lightning_attention(y, p, m, log_decay, precision):
    """y [T, d] -> [T, d]: one sequence from position 0, the recurrence
    a position at a time."""
    t = y.shape[0]
    h, hd = m["la_heads"], m["la_hd"]

    def project(yb):
        return (_mm(yb, p["qkv"]["weight"], precision),
                _mm(yb, p["gate"]["weight"], precision))

    qkv, gate = _by_rows(project, y)
    q, k, v = (qkv[:, i * h * hd:(i + 1) * h * hd].reshape(t, h, hd)
               for i in range(3))
    pos = jnp.arange(t)
    q = rotate(rms_norm(q, p["q_norm"]["scale"], m["eps"]), pos, m["theta"]
               ) / math.sqrt(hd)
    k = rotate(rms_norm(k, p["k_norm"]["scale"], m["eps"]), pos, m["theta"])
    lam = jnp.exp(log_decay)[:, None, None]

    def step(s, x):
        q_t, k_t, v_t = x
        s = lam * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((h, hd, hd), jnp.float32), (q, k, v))
    o = rms_norm(o.reshape(t, h * hd), p["out_norm"]["scale"], m["eps"])
    return _by_rows(lambda ob: _mm(ob, p["o"]["weight"], precision),
                    o * jax.nn.sigmoid(gate))


def ffn(y, p, f, precision):
    def one(yb):
        gu = _mm(yb, p["w1"]["weight"], precision)
        return _mm(gu[:, f:] * jax.nn.silu(gu[:, :f]), p["w2"]["weight"],
                   precision)
    return _by_rows(one, y)


def log_decay(m: dict, layer: int):
    """log lambda_h of the lightning layer at published index `layer`."""
    h = m["la_heads"]
    slopes = 2.0 ** (-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1.0) / h)
    return -slopes * (1.0 - layer / (m["depth"] - 1) + 1e-5)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer(x, p, decay, m_items, sel_items, kind, precision):
    """x [G, T, d] through one layer, a sequence at a time; `decay` the
    layer's log lambda_h (an operand: one compilation a kind)."""
    m, sel = dict(m_items), dict(sel_items)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def one(x):
        y = rms_norm(x, p["ln1"]["scale"], m["eps"])
        if kind == "minicpm4":
            mixed = sparse_attention(y, p["mixer"], m, sel, precision)
        else:
            mixed = lightning_attention(y, p["mixer"], m, decay, precision)
        h = x + m["residual"] * mixed
        return h + m["residual"] * ffn(
            rms_norm(h, p["ln2"]["scale"], m["eps"]), p["ffn"], m["ffn"],
            precision)

    return jax.lax.map(one, x)


def hidden(cfg: dict, seed: int, tokens, precision: str = "f32", weights=W,
           upto=None, taps=None):
    """tokens [G, T] -> the residual stream before the final norm
    [G, T, d]. `weights` is where the leaves come from; a test may hand
    in altered ones, stop after `upto` layers, or ask for the stream
    after each layer (`taps`, a list that is appended to)."""
    m = W.dims(cfg)
    x = jnp.take(weights.embed(cfg, seed), tokens, axis=0
                 ).astype(jnp.float32) * m["scale_emb"]
    m_items = tuple(sorted(m.items()))
    sel_items = tuple(sorted(cfg["sparse"].items()))
    for i, kind in enumerate(cfg["mixer_types"][:upto]):
        p = weights.layer(cfg, seed, i)
        x = _layer(x, p, log_decay(m, m["first"] + i), m_items, sel_items,
                   kind, precision)
        del p
        if taps is not None:
            taps.append(x)
    return x


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _logits(x, rows, norm, head, eps, head_div, precision):
    """x [G, T, d], rows [G, R] -> logits [G, R, V]."""
    h = rms_norm(jnp.take_along_axis(x, rows[..., None], axis=1),
                 norm["scale"].astype(jnp.float32), eps) / head_div
    return _mm(h, head.astype(jnp.float32), precision)


def logits_at(cfg: dict, seed: int, tokens, rows, precision: str = "f32",
              weights=W):
    """Logits [G, R, V] of padded sequences `tokens` [G, T] at the
    positions `rows` [G, R]. Padding after a position cannot reach it:
    attention, selection and recurrence are causal and every other
    operation is per token."""
    m = W.dims(cfg)
    x = hidden(cfg, seed, tokens, precision, weights)
    return _logits(x, rows, weights.norm_f(cfg, seed),
                   weights.head(cfg, seed), m["eps"], m["head_div"],
                   precision)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _gaps(x, rows, served, other, norm, head, eps, head_div):
    """How far the float32 logit of `served` [G, R] and of `other`
    [G, R] lies below the float32 best, a sequence at a time."""
    def one(args):
        x, rows, served, other = args
        ref = _logits(x[None], rows[None], norm, head, eps, head_div,
                      "f32")[0]
        best = ref.max(axis=-1)

        def below(tok):
            return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return below(served), below(other)
    return jax.lax.map(one, (x, rows, served, other))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _argmax(x, rows, norm, head, eps, head_div, precision):
    def one(args):
        x, rows = args
        return _logits(x[None], rows[None], norm, head, eps, head_div,
                       precision)[0].argmax(axis=-1).astype(jnp.int32)
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
        other = _argmax(x, rows, norm, head, m["eps"], m["head_div"],
                        control)
        del x
    x = hidden(cfg, seed, tokens, "f32")
    got, low = _gaps(x, rows, served, other, norm, head, m["eps"],
                     m["head_div"])
    return got, (low if control is not None else jnp.zeros_like(got))
