"""The plain reference of the decoder-hybrid-decoder
(`benchmarks/configs/phi-4-mini-flash.json`; arXiv:2507.06607): its
forward pass in straightforward `jax.numpy`, float32, matmuls at
"highest" precision, no kernel, no cache, a `lax.scan` over time for the
state-space layer. It imports nothing of the program.

Every layer i: h = x + mixer_i(LN1(x)); x = h + FFN(LN2(h)); LayerNorm
with scale and bias; FFN(y) = W2 (up . silu(gate)), [gate | up] = W1 y.
After the last layer a LayerNorm and logits = h E^T, E the token table.
No position encoding anywhere. The mixer is `layer_kinds[i]`:

mamba   [u | z] = W_in y; u' = silu(conv(u)) (causal, depthwise, width
        d_conv, with bias, zeros before the sequence);
        [dt | B | C] = W_x u'; delta = softplus(W_dt dt + b_dt);
        A = -exp(A_log); s_t = exp(delta_t (x) A) s_{t-1}
        + (delta_t u'_t) (x) B_t from s = 0; m_t = s_t C_t + D u'_t;
        out = W_out (m . silu(z)). The last mamba layer below the first
        gmu hands its m (before the gate) to the gated memory units.
gmu     W_2 (m . silu(W_1 y)), m that memory at the same token.
window  differential attention (arXiv:2410.05258, two-call form):
full    [q | k | v] = W_qkv y + b. Query heads pair as (2p, 2p+1), key
        heads as (2j, 2j+1), V_j = [v_2j | v_2j+1]; pair p reads key
        pair j = p // (query pairs a key pair). With Att(q, k, V) =
        softmax(q k^T / sqrt(head) + mask) V:
        o_p = Att(q1_p, k1_j, V_j) - lambda Att(q2_p, k2_j, V_j),
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i),
        lambda_init(i) = 0.8 - 0.6 exp(-0.3 i);
        o_p <- RMSNorm(o_p) . scale . (1 - lambda_init(i)); W_o with
        bias. Causal; a window layer also hides key j from query t
        unless t - (window - 1) <= j.
cross   the same with q = W_q y + b of its own over the k and v of the
        nearest full layer below; it projects no key or value.

Departures from the published description, each also in the
configuration's `assumed`: the state-space layer's inner sizes
(d_inner = 2 d, d_state 16, d_conv 4, dt_rank = ceil(d / 16)), the
pairing order, the constants of lambda_init and the RMSNorm's epsilon
(the configuration's layer_norm_eps) are the families' conventions, not
in the published configuration.

A layer's weights are made and used one layer at a time
(`weights_phi4flash.layer`); attention runs in blocks of queries.

`precision`: "f32" is the reference proper. "fp8" is the control, the
nearest precision below the bf16 the configuration states: inputs and
weights of every linear layer and the cached rows (k and v) are rounded
to e4m3 under a scale per row of activations and per output column of
weights. The scan's state is float32 in program, reference and control
alike.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights_phi4flash as W


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


def _mm(x, w, precision, bias=None):
    """x [..., in] @ w [in, out] (+ bias); the weight's scale is per
    output column."""
    y = jnp.matmul(_cast(x, precision), _cast(w, precision, -2),
                   precision="highest")
    return y if bias is None else y + bias


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def mamba(y, p, m, precision):
    """y [T, d] -> (out [T, d], memory m [T, d_inner]): one sequence
    from position 0."""
    dn, n, k, r = m["inner"], m["state"], m["conv"], m["dt_rank"]
    uz = _mm(y, p["in_proj"]["weight"], precision)
    u, z = uz[:, :dn], uz[:, dn:]
    t = u.shape[0]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    conv = p["conv"]["bias"] + sum(
        p["conv"]["weight"][j] * padded[j:j + t] for j in range(k))
    u = jax.nn.silu(conv)
    dbc = _mm(u, p["x_proj"]["weight"], precision)
    dt, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    delta = jax.nn.softplus(_mm(dt, p["dt_proj"]["weight"], precision,
                                p["dt_proj"]["bias"]))
    a = -jnp.exp(p["A_log"])                                  # [dn, n]

    def step(s, x):
        u_t, d_t, b_t, c_t = x
        s = (jnp.exp(d_t[:, None] * a) * s
             + (d_t * u_t)[:, None] * b_t[None, :])
        return s, s @ c_t + p["D"] * u_t

    _, mem = jax.lax.scan(step, jnp.zeros((dn, n), jnp.float32),
                          (u, delta, b, c))
    return _mm(mem * jax.nn.silu(z), p["out_proj"]["weight"], precision), mem


def gated_memory(y, memory, p, precision):
    g = _mm(y, p["w1"]["weight"], precision)
    return _mm(memory * jax.nn.silu(g), p["w2"]["weight"], precision)


def project_kv(y, p, m, precision):
    """(q [T, H, hd], k, v [T, Hkv, hd]) of a window or full layer; k
    and v as an 8-bit cache would hold them under the control."""
    t = y.shape[0]
    d, hd, kvd = m["d"], m["hd"], m["kv_heads"] * m["hd"]
    qkv = _mm(y, p["qkv"]["weight"], precision, p["qkv"]["bias"])
    k = qkv[:, d:d + kvd].reshape(t, m["kv_heads"], hd)
    v = qkv[:, d + kvd:].reshape(t, m["kv_heads"], hd)
    if precision == "fp8":
        k, v = _round_fp8(k, -1), _round_fp8(v, -1)
    return qkv[:, :d].reshape(t, m["heads"], hd), k, v


def diff_attention(q, k, v, p, m, lam_init, window, precision):
    """q [T, H, hd]; k, v [T, Hkv, hd] -> [T, d]: one sequence from
    position 0, the two softmax maps a pair computed apart; `lam_init`
    is the layer's lambda_init."""
    t, hd = q.shape[0], m["hd"]
    pairs, kv_pairs = m["heads"] // 2, m["kv_heads"] // 2
    per = pairs // kv_pairs
    q = q.reshape(t, pairs, 2, hd)
    k = jnp.repeat(k.reshape(t, kv_pairs, 2, hd), per, axis=1)
    v = jnp.repeat(v.reshape(t, kv_pairs, 2 * hd), per, axis=1)
    scale = 1.0 / math.sqrt(hd)
    pos = jnp.arange(t)
    qb = 128 if t % 128 == 0 else t

    def att(qc, kc, rows):
        """softmax(qc kc^T) V for the query rows `rows`: [qb, P, 2 hd]."""
        s = jnp.einsum("qpd,kpd->pqk", qc, kc, precision="highest") * scale
        seen = rows[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (pos[None, :] > rows[:, None] - window)
        a = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("pqk,kpv->qpv", a, v, precision="highest")

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        return (att(qs[:, :, 0], k[:, :, 0], rows),
                att(qs[:, :, 1], k[:, :, 1], rows))

    a1, a2 = jax.lax.map(block, jnp.arange(t // qb))
    a1 = a1.reshape(t, pairs, 2 * hd)
    a2 = a2.reshape(t, pairs, 2 * hd)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
           + lam_init)
    o = a1 - lam * a2
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = (o * jax.lax.rsqrt(var + m["eps"]) * p["subln"]["scale"]
         * (1.0 - lam_init))
    return _mm(o.reshape(t, m["d"]), p["o"]["weight"], precision,
               p["o"]["bias"])


def ffn(y, p, f, precision):
    gu = _mm(y, p["w1"]["weight"], precision)
    return _mm(gu[:, f:] * jax.nn.silu(gu[:, :f]), p["w2"]["weight"],
               precision)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _layer(x, memory, kv, p, lam_init, m_items, kind, precision):
    """x [G, T, d] through one layer, a sequence at a time. `memory`
    [G, T, d_inner] and `kv` (k, v) are what the layer reads of earlier
    layers (zeros where it reads nothing); `lam_init` the layer's
    lambda_init (an operand: one compilation a kind, not a layer).
    Returns (x, the memory this layer makes or the one it was given,
    the (k, v) it makes or was given)."""
    m = dict(m_items)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def one(args):
        x, memory, k, v = args
        y = layer_norm(x, p["ln1"], m["eps"])
        mx = p["mixer"]
        if kind == "mamba":
            mixed, memory = mamba(y, mx, m, precision)
        elif kind == "gmu":
            mixed = gated_memory(y, memory, mx, precision)
        else:
            if kind == "cross":
                q = _mm(y, mx["q"]["weight"], precision,
                        mx["q"]["bias"]).reshape(-1, m["heads"], m["hd"])
            else:
                q, k, v = project_kv(y, mx, m, precision)
            mixed = diff_attention(
                q, k, v, mx, m, lam_init,
                m["window"] if kind == "window" else None, precision)
        h = x + mixed
        return (h + ffn(layer_norm(h, p["ln2"], m["eps"]), p["ffn"],
                        m["ffn"], precision), memory, k, v)

    x, memory, k, v = jax.lax.map(one, (x, memory) + tuple(kv))
    return x, memory, (k, v)


def hidden(cfg: dict, seed: int, tokens, precision: str = "f32",
           weights=W, upto=None, taps=None):
    """tokens [G, T] -> the residual stream before the final norm
    [G, T, d]. `weights` is where the leaves come from (`embed`,
    `layer`, `norm_f`); a test may hand in altered ones, stop after
    `upto` layers, or ask for the stream after each layer (`taps`, a
    list that is appended to)."""
    m = W.dims(cfg)
    kinds = cfg["layer_kinds"]
    g, t = tokens.shape
    x = jnp.take(weights.embed(cfg, seed), tokens, axis=0
                 ).astype(jnp.float32)
    memory = jnp.zeros((g, t, m["inner"]), jnp.float32)
    kv = (jnp.zeros((g, t, m["kv_heads"], m["hd"]), jnp.float32),) * 2
    first_gmu = kinds.index("gmu") if "gmu" in kinds else len(kinds)
    items = tuple(sorted(m.items()))
    for i, kind in enumerate(kinds[:upto]):
        p = weights.layer(cfg, seed, i)
        x, mem, made = _layer(x, memory, kv, p, jnp.float32(lambda_init(i)),
                              items, kind, precision)
        del p
        if kind == "mamba" and i < first_gmu:
            memory = mem
        if kind == "full":
            kv = made
        if taps is not None:
            taps.append(x)
    return x


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(x, rows, norm, table, eps, precision):
    """x [G, T, d], rows [G, R] -> logits [G, R, V]."""
    norm = jax.tree.map(lambda a: a.astype(jnp.float32), norm)
    h = layer_norm(jnp.take_along_axis(x, rows[..., None], axis=1), norm,
                   eps)
    return _mm(h, table.astype(jnp.float32).T, precision)


def logits_at(cfg: dict, seed: int, tokens, rows, precision: str = "f32",
              weights=W):
    """Logits [G, R, V] of padded sequences `tokens` [G, T] at the
    positions `rows` [G, R]. Padding after a position cannot reach it:
    attention, convolution and scan are causal and every other
    operation is per token."""
    x = hidden(cfg, seed, tokens, precision, weights)
    return _logits(x, rows, weights.norm_f(cfg, seed),
                   weights.embed(cfg, seed), W.dims(cfg)["eps"], precision)


@functools.partial(jax.jit, static_argnums=(6,))
def _gaps(x, rows, served, other, norm, table, eps):
    """How far the float32 logit of `served` [G, R] and of `other`
    [G, R] lies below the float32 best, a sequence at a time (the
    logits of one are [R, V])."""
    def one(args):
        x, rows, served, other = args
        ref = _logits(x[None], rows[None], norm, table, eps, "f32")[0]
        best = ref.max(axis=-1)

        def below(tok):
            return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return below(served), below(other)
    return jax.lax.map(one, (x, rows, served, other))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _argmax(x, rows, norm, table, eps, precision):
    def one(args):
        x, rows = args
        return _logits(x[None], rows[None], norm, table, eps,
                       precision)[0].argmax(axis=-1).astype(jnp.int32)
    return jax.lax.map(one, (x, rows))


def served_gaps(cfg: dict, seed: int, tokens, rows, served, control=None):
    """For each of G padded sequences `tokens` [G, T], at the positions
    `rows` [G, R]: how far the served token's float32 logit lies below
    the float32 best and (with `control`) how far the token that the
    lower precision puts first does."""
    eps = W.dims(cfg)["eps"]
    norm, table = W.norm_f(cfg, seed), W.embed(cfg, seed)
    other = served
    if control is not None:
        x = hidden(cfg, seed, tokens, control)
        other = _argmax(x, rows, norm, table, eps, control)
        del x
    x = hidden(cfg, seed, tokens, "f32")
    got, low = _gaps(x, rows, served, other, norm, table, eps)
    return got, (low if control is not None else jnp.zeros_like(got))
