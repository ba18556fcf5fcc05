"""Seeded weights of the sparse-and-linear decoder
(`benchmarks/configs/minicpm-sala.json`), a layer at a time.

A layer's leaves come from `(seed, the layer's PUBLISHED index)` alone,
as bf16 values: the program's whole tree (`make_params`, 10.1 GB at the
published sizes) and the reference's layer loop (`layer`, one layer in
float32 at a time) make the same numbers, and neither needs what the
other made; a deeper or shallower slice of the stack keeps its layers'
weights. The tree has the names of the program's checkpoint format,
which is a data interface. The program's own initialiser is not used.

Scales (the configuration's `assumed.weights`): every matrix normal with
std 1 / sqrt(fan-in), so each projection of a unit-RMS input has unit
RMS (the gates' among them: sigmoid of a unit normal spreads over 0.27
to 0.73); the token table std 0.02 (times scale_emb 12 it enters the
stream at 0.24, and sixteen layers at c = 0.25 each outweigh it); the
head std (hidden / dim_model_base) / sqrt(hidden), which undoes the
head's divisor: unit logits, where 1 / sqrt(hidden) would leave them at
0.06 and every served token inside the rounding of bf16; norm scales
1 + 0.02 noise, but the sparse layers' q and k norms 1.5 + 0.02 noise:
a raw logit then has std 2.25 and a compressed one (the mean of 32
keys) 0.4, so the softmax over a context's windows is neither flat nor
one-hot and the blocks' scores lie apart (the top-64 is not a tie).

    python3 benchmarks/weights_sala.py     # prints the exact count
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
SPARSE_QK = 1.5
EMBED, NORM_F, HEAD = 1_000_001, 1_000_003, 1_000_005    # "layer" indices


def dims(cfg: dict) -> dict:
    """The widths of the layers, from the configuration's keys."""
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "ffn": cfg["intermediate_size"], "la_heads": cfg["lightning_nh"],
        "la_hd": cfg["lightning_head_dim"], "vocab": cfg["vocab_size"],
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "scale_emb": float(cfg["scale_emb"]),
        "residual": float(cfg["scale_depth"])
        / math.sqrt(cfg["published"]["num_hidden_layers"]),
        "depth": cfg["published"]["num_hidden_layers"],
        "head_div": cfg["hidden_size"] / float(cfg["dim_model_base"]),
        "first": cfg["first_layer"],
    }


def layer_shapes(cfg: dict, index: int) -> dict:
    """{path: (shape, kind)} of layer `index` of the configuration's
    stack (its published index is `first_layer` + index)."""
    m = dims(cfg)
    d, f = m["d"], m["ffn"]
    tree = {
        "ln1/scale": ((d,), "scale"), "ln2/scale": ((d,), "scale"),
        "ffn/w1/weight": ((d, 2 * f), "matrix"),
        "ffn/w2/weight": ((f, d), "matrix"),
    }
    if cfg["mixer_types"][index] == "minicpm4":
        w, kvw = m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
        tree.update({
            "mixer/q/weight": ((d, w), "matrix"),
            "mixer/kv/weight": ((d, 2 * kvw), "matrix"),
            "mixer/q_norm/scale": ((m["hd"],), "sparse_qk"),
            "mixer/k_norm/scale": ((m["hd"],), "sparse_qk"),
            "mixer/gate/weight": ((d, w), "matrix"),
            "mixer/o/weight": ((w, d), "matrix"),
        })
    else:
        w = m["la_heads"] * m["la_hd"]
        tree.update({
            "mixer/qkv/weight": ((d, 3 * w), "matrix"),
            "mixer/q_norm/scale": ((m["la_hd"],), "scale"),
            "mixer/k_norm/scale": ((m["la_hd"],), "scale"),
            "mixer/out_norm/scale": ((w,), "scale"),
            "mixer/gate/weight": ((d, w), "matrix"),
            "mixer/o/weight": ((w, d), "matrix"),
        })
    return tree


def _leaf(key, shape, kind, gain=1.0):
    noise = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        noise = noise * (gain / math.sqrt(shape[-2]))
    elif kind == "scale":
        noise = 1.0 + NOISE * noise
    elif kind == "sparse_qk":
        noise = SPARSE_QK + NOISE * noise
    elif kind == "table":
        noise = NOISE * noise
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
    shapes = layer_shapes(cfg, index)
    key = jax.random.fold_in(seed_key(seed), cfg["first_layer"] + index)
    return _nest(_make_layer(key, tuple(sorted(shapes.items()))))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_one(key, shape, kind, gain=1.0):
    return _leaf(key, shape, kind, gain)


def embed(cfg: dict, seed: int):
    """The token table [vocab, d]."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), EMBED),
                     (m["vocab"], m["d"]), "table")


def head(cfg: dict, seed: int):
    """The untied head [d, vocab]."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), HEAD),
                     (m["d"], m["vocab"]), "matrix", m["head_div"])


def norm_f(cfg: dict, seed: int) -> dict:
    return {"scale": _make_one(jax.random.fold_in(seed_key(seed), NORM_F),
                               (dims(cfg)["d"],), "scale")}


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
    total = 2 * m["vocab"] * m["d"] + m["d"]
    for i in range(cfg["num_hidden_layers"]):
        total += sum(math.prod(shape)
                     for shape, _ in layer_shapes(cfg, i).values())
    return total


if __name__ == "__main__":
    from benchmarks.common import load_json
    print(count_params(load_json("benchmarks", "configs",
                                 "minicpm-sala.json")))
