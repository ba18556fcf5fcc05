"""The plain reference: the decoder block of these configurations in
straightforward `jax.numpy`, float32, matmuls at "highest" precision, no
kernels, no cache, no batching tricks. It imports nothing of the program.

The block (the repo's `CausalLM`, a GPT-2 row with the departures the
configuration files list under `assumed`): token table times sqrt(d) plus
a sinusoid position table; per layer pre-LayerNorm causal self-attention
with biases, residual, pre-LayerNorm feed-forward d -> ffn -> d with ReLU,
residual; a final LayerNorm; the head is the token table transposed.

`precision` selects how the matmuls are computed:
- "f32": the reference proper.
- "fp8": the control, the nearest precision below the bf16 the
  configurations state from which a limit can be set (8 bits as int8
  with a scale per row read as close to float32 as the bf16 program
  does, PERF.md section 2). Inputs and weights of every linear layer, and
  the keys and values (as an 8-bit KV cache holds them), are rounded to
  e4m3 (three bits of mantissa) under a scale per row of activations and
  per output column of weights; in the linear layers' backward pass the
  incoming gradient is rounded to e5m2, as fp8 training does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def sinusoid_table(n_positions: int, dim: int):
    pos = jnp.arange(n_positions, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / dim)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def _round_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _cast(x, precision, axis=-1):
    if precision == "fp8":
        return _round_fp8(x, axis)
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return x


def _round_e5m2(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 57344.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_matmul(x, w):
    """x [T, in] @ w [in, out] as an fp8 training step computes it:
    e4m3 operands forward, the incoming gradient in e5m2 backward."""
    return jnp.matmul(_round_fp8(x, -1), _round_fp8(w, 0),
                      precision="highest")


def _fp8_matmul_fwd(x, w):
    xq, wq = _round_fp8(x, -1), _round_fp8(w, 0)
    return jnp.matmul(xq, wq, precision="highest"), (xq, wq)


def _fp8_matmul_bwd(saved, dy):
    xq, wq = saved
    dyq = _round_e5m2(dy)
    return (jnp.matmul(dyq, wq.T, precision="highest"),
            jnp.matmul(xq.T, dyq, precision="highest"))


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


def _linear(x, p, precision):
    if precision == "fp8":
        return _fp8_matmul(x, p["weight"]) + p["bias"]
    # weights: one scale per output column (axis 0 is the input dim)
    y = jnp.matmul(_cast(x, precision), _cast(p["weight"], precision, 0),
                   precision="highest")
    return y + p["bias"]


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _block(x, p, n_head, precision):
    """x [T, d] -> [T, d]; one sequence, causal."""
    t, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln1"])
    q = _linear(h, p["attn"]["q_proj"], precision).reshape(t, n_head, hd)
    k = _linear(h, p["attn"]["k_proj"], precision).reshape(t, n_head, hd)
    v = _linear(h, p["attn"]["v_proj"], precision).reshape(t, n_head, hd)
    k, v = _cast(k, precision), _cast(v, precision)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision="highest").reshape(t, d)
    x = x + _linear(o, p["attn"]["out_proj"], precision)
    h = _layer_norm(x, p["ln2"])
    f = jax.nn.relu(_linear(h, p["ffn"]["fc1"], precision))
    return x + _linear(f, p["ffn"]["fc2"], precision)


def stack_layers(params: dict, n_layer: int):
    """(stacked per-layer tree, the rest) so that the layers run under
    one `lax.scan`: one layer is compiled, not all of them."""
    layers = [params[f"blocks_{i}"] for i in range(n_layer)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    rest = {k: v for k, v in params.items() if not k.startswith("blocks_")}
    return stacked, rest


def hidden(stacked, rest, tokens, cfg_n_head: int, n_positions: int,
           precision: str):
    """tokens [T] -> final-LayerNorm hidden states [T, d]."""
    table = rest["embed"]["weight"]
    d = table.shape[1]
    x = table[tokens] * math.sqrt(d)
    x = x + sinusoid_table(n_positions, d)[: tokens.shape[0]]

    @jax.checkpoint
    def body(x, layer):
        return _block(x, layer, cfg_n_head, precision), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _layer_norm(x, rest["ln_f"])


def _logits(stacked, rest, tokens, rows, n_head, n_positions, precision):
    h = hidden(stacked, rest, tokens, n_head, n_positions, precision)
    return jnp.matmul(_cast(h[rows], precision),
                      _cast(rest["embed"]["weight"], precision, 1).T,
                      precision="highest")


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def logits_at(stacked, rest, tokens, rows, n_head, n_positions, precision):
    """Logits [len(rows), V] of one padded sequence `tokens` [T] at the
    positions `rows`. Padding after a position cannot reach it: the
    attention is causal."""
    return _logits(stacked, rest, tokens, rows, n_head, n_positions,
                   precision)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def served_gaps(stacked, rest, tokens, rows, served, n_head, n_positions,
                control=None):
    """For each of G padded sequences `tokens` [G, T], at the positions
    `rows` [G, R]: how far the served token's float32 logit lies below
    the float32 best, and (with `control`) how far the token that the
    lower precision puts first does. One sequence at a time."""
    def one(args):
        tokens, rows, served = args
        ref = _logits(stacked, rest, tokens, rows, n_head, n_positions, "f32")
        best = ref.max(axis=-1)

        def below(tok):
            return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        if control is None:
            return below(served), jnp.zeros_like(best)
        low = _logits(stacked, rest, tokens, rows, n_head, n_positions,
                      control)
        return below(served), below(low.argmax(axis=-1))
    return jax.lax.map(one, (tokens, rows, served))


def _row_loss(stacked, rest, inp, tgt, n_head, n_positions, precision):
    h = hidden(stacked, rest, inp, n_head, n_positions, precision)
    logits = jnp.matmul(_cast(h, precision),
                        _cast(rest["embed"]["weight"], precision, 1).T,
                        precision="highest")
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def loss_and_grads(stacked, rest, batch, n_head, n_positions, precision,
                   rows_used=None):
    """Mean next-token cross-entropy over the batch and its gradient,
    one row at a time so that a row's activations are all that is live.
    batch: (inp [B, T], tgt [B, T]). `rows_used` plants the fault "half
    of the batch left out, the mean taken over the rest"."""
    inp, tgt = batch
    if rows_used is not None:
        inp, tgt = inp[:rows_used], tgt[:rows_used]
    count = inp.shape[0] * inp.shape[1]
    grad_fn = jax.value_and_grad(_row_loss, argnums=(0, 1))

    def one(carry, row):
        loss, grads = carry
        l, g = grad_fn(stacked, rest, row[0], row[1], n_head, n_positions,
                       precision)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    zero = jax.tree.map(jnp.zeros_like, (stacked, rest))
    (loss, grads), _ = jax.lax.scan(one, (jnp.zeros(()), zero), (inp, tgt))
    scale = 1.0 / count
    return loss * scale, jax.tree.map(lambda g: g * scale, grads)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def adam_update(params, grads, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Bias-corrected Adam (Kingma & Ba 2015), `step` counted from 0."""
    t = step + 1.0

    def leaf(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * upd, m, v

    leaves, treedef = jax.tree.flatten(params)
    out = [leaf(*xs) for xs in zip(leaves, jax.tree.leaves(grads),
                                   jax.tree.leaves(m), jax.tree.leaves(v))]
    return tuple(jax.tree.unflatten(treedef, [o[i] for o in out])
                 for i in range(3))


def _norm(x, stacked: bool):
    axes = tuple(range(1, x.ndim)) if stacked else None
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))


@jax.jit
def leaf_norms(state):
    """Per-leaf norms of a (stacked, rest) pair; a stacked leaf gives one
    norm per layer."""
    stacked, rest = state
    return (jax.tree.map(lambda x: _norm(x, True), stacked),
            jax.tree.map(lambda x: _norm(x, False), rest))


@jax.jit
def diff_norms(a, b):
    return leaf_norms(jax.tree.map(jnp.subtract, a, b))


def train_reference(params, cfg: dict, batches, lr: float,
                    precision: str = "f32", rows_used=None,
                    grads_like=None, return_grads: bool = False) -> dict:
    """Follow the first len(batches) Adam steps from `params`. Returns
    the losses, the per-leaf norms of the first gradient and of the
    parameters' change after the last step, as flat {path: float}.
    `grads_like`, another side's first gradient as a (stacked, rest)
    pair, adds the per-leaf norms of this side's first gradient minus
    it; `return_grads` hands this side's first gradient back."""
    n_layer, n_head, n_pos = cfg["n_layer"], cfg["n_head"], cfg["n_positions"]
    state = stack_layers(params, n_layer)
    start = jax.tree.map(jnp.copy, state)
    m = jax.tree.map(jnp.zeros_like, state)
    v = jax.tree.map(jnp.zeros_like, state)
    out = {"losses": []}
    for step, batch in enumerate(batches):
        loss, grads = loss_and_grads(state[0], state[1], batch, n_head,
                                     n_pos, precision, rows_used)
        if step == 0:
            out["grad_norms"] = flat_norms(leaf_norms(grads), n_layer)
            if grads_like is not None:
                out["grad_diff_norms"] = flat_norms(
                    diff_norms(grads, grads_like), n_layer)
            if return_grads:
                out["grads0"] = jax.tree.map(jnp.copy, grads)
        state, m, v = adam_update(state, grads, m, v, float(step), lr)
        out["losses"].append(float(loss))
    out["change_norms"] = flat_norms(diff_norms(state, start), n_layer)
    return out


def flat_norms(stacked_and_rest, n_layer: int) -> dict:
    """{'blocks_3/attn/q_proj/weight': norm, ...} from norms of the
    stacked form (a stacked leaf's norm is a vector over layers)."""
    stacked, rest = jax.device_get(stacked_and_rest)
    out = {}
    for path, leaf in jax.tree.flatten_with_path(rest)[0]:
        out["/".join(k.key for k in path)] = float(leaf)
    for path, leaf in jax.tree.flatten_with_path(stacked)[0]:
        name = "/".join(k.key for k in path)
        for i in range(n_layer):
            out[f"blocks_{i}/{name}"] = float(np.asarray(leaf)[i])
    return out
