"""Seeded weights of the parallel hybrid decoder
(`benchmarks/configs/falcon-h1-34b.json`), a layer at a time.

A layer's leaves come from `(seed, the layer's index)` alone, as bf16
values: the program's whole tree (`make_params`, 10.5 GB at the
published widths) and the reference's layer loop (`layer`, one layer in
float32 at a time) make the same numbers, and neither needs what the
other made. The tree has the names of the program's checkpoint format,
which is a data interface. The program's own initialiser is not used.

Scales (the configuration's `assumed.weights`): every matrix is normal
with std gain / sqrt(fan-in), and the gain UNDOES the multiplier that
follows the product, so that each product of a unit-RMS input has unit
RMS after its multiplier, as a trained muP model's would: the token
table std 1 / embedding_multiplier; W_k 1 / key_multiplier (scores of
unit std: the keys' multiplier is 0.011); W_o 1 / attention_out;
W_in's five blocks 1 / (ssm_in . ssm_multipliers[j]); W_out
1 / ssm_out; the MLP's gate 1 / mlp_multipliers[0] and W_down
1 / mlp_multipliers[1]; the head 1 / lm_head_multiplier (unit logits).
The attention, the Mamba-2 mixer and the MLP then each add about unit
RMS to the stream in every layer: each moves the logits. The scan's
own leaves are Mamba-2's initialisation: A_log = log U(1, 16),
dt_bias = softplus^-1 of log-uniform on [0.001, 0.1], D = 1 + noise,
the convolution std 1 / sqrt(width) and its bias 0.02. Norm scales
1 + 0.02 noise.

    python3 benchmarks/weights_falconh1.py     # prints the exact count
"""

from __future__ import annotations

import functools
import math
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

NOISE = 0.02
EMBED, NORM_F, HEAD = 1_000_001, 1_000_003, 1_000_005    # "layer" indices


def dims(cfg: dict) -> dict:
    """The widths and multipliers, from the configuration's keys."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "ssm_heads": h, "ssm_hd": p, "groups": g, "state": n,
        "d_ssm": h * p, "conv_dim": h * p + 2 * g * n,
        "conv": cfg["mamba_d_conv"],
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "emb": float(cfg["embedding_multiplier"]),
        "attn_in": float(cfg["attention_in_multiplier"]),
        "attn_out": float(cfg["attention_out_multiplier"]),
        "key": float(cfg["key_multiplier"]),
        "ssm_in": float(cfg["ssm_in_multiplier"]),
        "ssm_out": float(cfg["ssm_out_multiplier"]),
        "ssm_mult": tuple(float(m) for m in cfg["ssm_multipliers"]),
        "mlp_mult": tuple(float(m) for m in cfg["mlp_multipliers"]),
        "lm_head": float(cfg["lm_head_multiplier"]),
    }


def layer_shapes(cfg: dict) -> dict:
    """{path: (shape, kind, gain)} of one layer; a matrix's gain is a
    tuple of (columns, gain) blocks along its output axis."""
    m = dims(cfg)
    d, f, hd = m["d"], m["ffn"], m["hd"]
    h, kvh = m["heads"], m["kv_heads"]
    ds, gn, sh = m["d_ssm"], m["groups"] * m["state"], m["ssm_heads"]
    z, x, b, c, dt = m["ssm_mult"]
    inv = 1.0 / m["ssm_in"]
    return {
        "ln1/scale": ((d,), "scale", ()), "ln2/scale": ((d,), "scale", ()),
        "attn/qkv/weight": ((d, (h + 2 * kvh) * hd), "matrix",
                            ((h * hd, 1.0), (kvh * hd, 1.0 / m["key"]),
                             (kvh * hd, 1.0))),
        "attn/o/weight": ((h * hd, d), "matrix",
                          ((d, 1.0 / m["attn_out"]),)),
        "ssm/in_proj/weight": ((d, 2 * ds + 2 * gn + sh), "matrix",
                               ((ds, inv / z), (ds, inv / x), (gn, inv / b),
                                (gn, inv / c), (sh, inv / dt))),
        "ssm/conv/weight": ((m["conv"], m["conv_dim"]), "matrix",
                            ((m["conv_dim"], 1.0),)),
        "ssm/conv/bias": ((m["conv_dim"],), "small", ()),
        "ssm/dt_bias": ((sh,), "dt_bias", ()),
        "ssm/A_log": ((sh,), "a_log", ()),
        "ssm/D": ((sh,), "scale", ()),
        "ssm/norm/scale": ((ds,), "scale", ()),
        "ssm/out_proj/weight": ((ds, d), "matrix",
                                ((d, 1.0 / m["ssm_out"]),)),
        "ffn/w1/weight": ((d, 2 * f), "matrix",
                          ((f, 1.0 / m["mlp_mult"][0]), (f, 1.0))),
        "ffn/w2/weight": ((f, d), "matrix", ((d, 1.0 / m["mlp_mult"][1]),)),
    }


def _leaf(key, shape, kind, gain):
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(jnp.bfloat16)
    noise = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        cols = jnp.concatenate([jnp.full((w,), g, jnp.float32)
                                for w, g in gain])
        noise = noise * cols / math.sqrt(shape[-2])
    elif kind == "scale":
        noise = 1.0 + NOISE * noise
    elif kind == "small":
        noise = NOISE * noise
    elif kind == "table":
        noise = noise * gain
    else:
        raise ValueError(f"unknown kind of leaf {kind!r}")
    return noise.astype(jnp.bfloat16)


def _nest(flat: dict) -> dict:
    out = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_layer(key, shapes_items):
    shapes = dict(shapes_items)
    keys = jax.random.split(key, len(shapes))
    return {path: _leaf(k, *shapes[path])
            for k, path in zip(keys, sorted(shapes))}


def layer(cfg: dict, seed: int, index: int) -> dict:
    """Layer `index`'s nested tree, bf16."""
    key = jax.random.fold_in(seed_key(seed), index)
    return _nest(_make_layer(key, tuple(sorted(layer_shapes(cfg).items()))))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_one(key, shape, kind, gain):
    return _leaf(key, shape, kind, gain)


def embed(cfg: dict, seed: int):
    """The token table [vocab, d]."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), EMBED),
                     (m["vocab"], m["d"]), "table", 1.0 / m["emb"])


def head(cfg: dict, seed: int):
    """The untied head [d, vocab]."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), HEAD),
                     (m["d"], m["vocab"]), "matrix",
                     ((m["vocab"], 1.0 / m["lm_head"]),))


def norm_f(cfg: dict, seed: int) -> dict:
    return {"scale": _make_one(jax.random.fold_in(seed_key(seed), NORM_F),
                               (dims(cfg)["d"],), "scale", ())}


def make_params(cfg: dict, seed: int) -> dict:
    """The program's whole parameter tree, a layer at a time: the bf16
    values, held in the configuration's `param_dtype`."""
    tree = {"embed": {"weight": embed(cfg, seed)},
            "head": {"weight": head(cfg, seed)},
            "norm_f": norm_f(cfg, seed)}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"blocks_{i}"] = layer(cfg, seed, i)
    dtype = jnp.dtype(cfg["param_dtype"])
    if dtype == jnp.bfloat16:
        return tree
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def count_params(cfg: dict) -> int:
    m = dims(cfg)
    one = sum(math.prod(shape) for shape, _, _ in layer_shapes(cfg).values())
    return 2 * m["vocab"] * m["d"] + m["d"] + cfg["num_hidden_layers"] * one


if __name__ == "__main__":
    from benchmarks.common import load_json
    print(count_params(load_json("benchmarks", "configs",
                                 "falcon-h1-34b.json")))
