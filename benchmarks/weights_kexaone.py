"""Seeded weights of the window and full GQA decoder with routed
experts (`benchmarks/configs/k-exaone-236b-a23b.json`), one chip's share
of an expert-parallel deployment, a layer at a time.

A layer's leaves come from `(seed, layer index)` alone, as bf16 values:
the program's whole tree (`make_params`, 7.4 GB at the cell's depth) and
the reference's layer loop (`layer`, one layer in float32 at a time: an
expert layer is 3.0 GB there) make the same numbers, and neither needs
what the other made. The tree has the names of the program's checkpoint
format, which is a data interface. The program's own initialiser is not
used. An expert layer holds the `num_experts` experts of rank
`expert_rank` and a router over `num_experts x expert_shards`.

Scales (the configuration's `assumed.weights`): every matrix normal with
std 1 / sqrt(fan-in), the untied head among them, so that each product
of a unit-RMS input has unit RMS whatever its width; the token table std
1, the scale each post-norm sublayer adds to the residual; norm scales,
the query and key norms among them, 1 + 0.02 noise; the router's
selection bias normal with std 0.02, non-zero and held fixed (the
published model learns it and ships it).

    python3 benchmarks/weights_kexaone.py     # prints the exact count
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
from benchmarks.weights_lfm2 import _make_layer, _make_one, _nest

EMBED, NORM_F, HEAD = 1_000_001, 1_000_003, 1_000_005    # "layer" indices


def dims(cfg: dict) -> dict:
    """The widths, from the configuration's published keys."""
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "ffn": cfg["intermediate_size"],
        "expert": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"], "shards": cfg["expert_shards"],
        "rank": cfg["expert_rank"], "top_k": cfg["num_experts_per_tok"],
        "shared": cfg["num_shared_experts"], "window": cfg["sliding_window"],
        "vocab": cfg["vocab_size"], "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "scaling": float(cfg["routed_scaling_factor"]),
    }


def layer_shapes(cfg: dict, index: int) -> dict:
    """{path: (shape, kind)} of layer `index`; kind is "matrix" (fan-in
    is the second-to-last axis), "scale" or "bias"."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    tree = {"ln_attn/scale": ((d,), "scale"), "ln_ffn/scale": ((d,), "scale"),
            "attn/qkv/weight": ((d, q + 2 * kv), "matrix"),
            "attn/q_norm/scale": ((hd,), "scale"),
            "attn/k_norm/scale": ((hd,), "scale"),
            "attn/o/weight": ((q, d), "matrix")}
    if cfg["mlp_layer_types"][index] == "dense":
        f = m["ffn"]
        tree.update({"ffn/gate/weight": ((d, f), "matrix"),
                     "ffn/up/weight": ((d, f), "matrix"),
                     "ffn/down/weight": ((f, d), "matrix")})
    else:
        f, e, s = m["expert"], m["experts"], m["expert"] * m["shared"]
        routed = e * m["shards"]
        tree.update({
            "moe/router/weight": ((d, routed), "matrix"),
            "moe/router/bias": ((routed,), "bias"),
            "moe/experts/gate": ((e, d, f), "matrix"),
            "moe/experts/up": ((e, d, f), "matrix"),
            "moe/experts/down": ((e, f, d), "matrix"),
            "moe/shared/gate/weight": ((d, s), "matrix"),
            "moe/shared/up/weight": ((d, s), "matrix"),
            "moe/shared/down/weight": ((s, d), "matrix")})
    return tree


def layer(cfg: dict, seed: int, index: int) -> dict:
    """Layer `index`'s nested tree, bf16."""
    shapes = layer_shapes(cfg, index)
    key = jax.random.fold_in(seed_key(seed), index)
    return _nest(_make_layer(key, tuple(sorted(shapes.items()))))


@functools.partial(jax.jit, static_argnums=(1,))
def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def embed(cfg: dict, seed: int):
    """The token table [vocab, d], std 1."""
    m = dims(cfg)
    return _normal(jax.random.fold_in(seed_key(seed), EMBED),
                   (m["vocab"], m["d"]))


def head(cfg: dict, seed: int):
    """The untied head [d, vocab]."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), HEAD),
                     (m["d"], m["vocab"]), "matrix")


def norm_f(cfg: dict, seed: int):
    return _make_one(jax.random.fold_in(seed_key(seed), NORM_F),
                     (dims(cfg)["d"],), "scale")


def make_params(cfg: dict, seed: int) -> dict:
    """The program's whole parameter tree, a layer at a time: the bf16
    values, held in the configuration's `param_dtype`."""
    tree = {"embed": {"weight": embed(cfg, seed)},
            "head": {"weight": head(cfg, seed)},
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
    return 2 * m["vocab"] * m["d"] + m["d"] + layers


if __name__ == "__main__":
    from benchmarks.common import load_json
    print(count_params(load_json("benchmarks", "configs",
                                 "k-exaone-236b-a23b.json")))
