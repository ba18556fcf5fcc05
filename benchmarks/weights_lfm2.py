"""Seeded weights of the short-convolution, routed-expert decoder
(`benchmarks/configs/lfm2-8b-a1b.json`), a layer at a time.

A layer's leaves come from `(seed, layer index)` alone, as bf16 values:
the program's whole tree (`make_params`, 9.2 GB at the cell's depth) and
the reference's layer loop (`layer`, one layer in float32 at a time: an
expert layer is 1.48 GB there) make the same numbers, and neither needs
what the other made. The tree has the names of the program's checkpoint
format, which is a data interface. The program's own initialiser is not
used.

Scales (the configuration's `assumed.weights`): every matrix normal with
std 1 / sqrt(fan-in), the convolution's taps among them (fan-in the
width, 3), so that each product of a unit-RMS input has unit RMS
whatever its width; the token table std 0.02 (the head is the table: at
std 1 every position's best logit would be its own input token, and
nothing a layer computes could change a served token; at 0.02 the
logits have unit scale); norm scales, the query and key norms among
them, 1 + 0.02 noise; the router's selection bias normal with std 0.02,
non-zero and held fixed (the published model learns it and ships it).

    python3 benchmarks/weights_lfm2.py     # prints the exact count
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
TABLE_STD = 0.02
EMBED, NORM_F = 1_000_001, 1_000_003      # "layer" indices


def dims(cfg: dict) -> dict:
    """The widths, from the configuration's published keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d, "heads": h, "kv_heads": cfg["num_key_value_heads"],
        "hd": d // h, "ffn": cfg["intermediate_size"],
        "expert": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
        "conv": cfg["conv_L_cache"], "dense": cfg["num_dense_layers"],
        "vocab": cfg["vocab_size"], "eps": float(cfg["norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "scaling": float(cfg["routed_scaling_factor"]),
    }


def layer_shapes(cfg: dict, index: int) -> dict:
    """{path: (shape, kind)} of layer `index`; kind is "matrix" (fan-in
    is the second-to-last axis), "scale" or "bias"."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    tree = {"ln1/scale": ((d,), "scale"), "ln2/scale": ((d,), "scale")}
    if cfg["layer_types"][index] == "conv":
        tree.update({
            "conv/in_proj/weight": ((d, 3 * d), "matrix"),
            "conv/conv/weight": ((m["conv"], d), "matrix"),
            "conv/out_proj/weight": ((d, d), "matrix")})
    else:
        q, kv = m["heads"] * hd, m["kv_heads"] * hd
        tree.update({
            "attn/qkv/weight": ((d, q + 2 * kv), "matrix"),
            "attn/q_norm/scale": ((hd,), "scale"),
            "attn/k_norm/scale": ((hd,), "scale"),
            "attn/o/weight": ((q, d), "matrix")})
    if index < m["dense"]:
        f = m["ffn"]
        tree.update({"ffn/gate/weight": ((d, f), "matrix"),
                     "ffn/up/weight": ((d, f), "matrix"),
                     "ffn/down/weight": ((f, d), "matrix")})
    else:
        f, e = m["expert"], m["experts"]
        tree.update({
            "moe/router/weight": ((d, e), "matrix"),
            "moe/router/bias": ((e,), "bias"),
            "moe/experts/gate": ((e, d, f), "matrix"),
            "moe/experts/up": ((e, d, f), "matrix"),
            "moe/experts/down": ((e, f, d), "matrix")})
    return tree


def _leaf(key, shape, kind):
    noise = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        noise = noise / math.sqrt(shape[-2])
    elif kind == "scale":
        noise = 1.0 + NOISE * noise
    elif kind == "bias":
        noise = NOISE * noise
    elif kind == "table":
        noise = TABLE_STD * noise
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
    key = jax.random.fold_in(seed_key(seed), index)
    return _nest(_make_layer(key, tuple(sorted(shapes.items()))))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_one(key, shape, kind):
    return _leaf(key, shape, kind)


def embed(cfg: dict, seed: int):
    """The token table [vocab, d], which is the head too."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), EMBED),
                     (m["vocab"], m["d"]), "table")


def norm_f(cfg: dict, seed: int):
    return _make_one(jax.random.fold_in(seed_key(seed), NORM_F),
                     (dims(cfg)["d"],), "scale")


def make_params(cfg: dict, seed: int) -> dict:
    """The program's whole parameter tree, a layer at a time: the bf16
    values, held in the configuration's `param_dtype`."""
    tree = {"embed": {"weight": embed(cfg, seed)},
            "norm_f": {"scale": norm_f(cfg, seed)}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"blocks_{i}"] = layer(cfg, seed, i)
    dtype = jnp.dtype(cfg["param_dtype"])
    if dtype == jnp.bfloat16:
        return tree
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def count_params(cfg: dict) -> int:
    m = dims(cfg)
    layers = sum(math.prod(shape) for i in range(cfg["num_hidden_layers"])
                 for shape, _ in layer_shapes(cfg, i).values())
    return m["vocab"] * m["d"] + m["d"] + layers


if __name__ == "__main__":
    from benchmarks.common import load_json
    print(count_params(load_json("benchmarks", "configs",
                                 "lfm2-8b-a1b.json")))
