"""Seeded weights of the latent-attention, routed-expert decoder
(`benchmarks/configs/glm-4.7-flash.json`), a layer at a time.

A layer's leaves come from `(seed, layer index)` alone, as bf16 values:
the program's whole tree (`make_params`, 9 GB at the cell's depth) and
the reference's layer loop (`layer`, one layer in float32 at a time: an
expert layer is 2.54 GB there) make the same numbers, and neither needs
what the other made. The tree has the names of the program's checkpoint
format, which is a data interface. The program's own initialiser is not
used.

Scales (the configuration's `assumed.weights`): every matrix normal with
std 1 / sqrt(fan-in), so that each projection of a unit-RMS input has
unit RMS whatever its width; the token table std 1 (no embedding scale
in this block, so the table is the residual stream's first term); norm
scales 1 + 0.02 noise; the router's selection bias normal with std 0.02,
non-zero and held fixed (the published model learns it and ships it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

NOISE = 0.02
EMBED, HEAD, NORM_F = 1_000_001, 1_000_002, 1_000_003   # "layer" indices


def dims(cfg: dict) -> dict:
    """The widths of one layer, from the published keys."""
    h = cfg["num_attention_heads"]
    return {
        "d": cfg["hidden_size"], "heads": h,
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "dense": cfg["intermediate_size"],
        "expert": cfg["moe_intermediate_size"],
        "experts": cfg["n_routed_experts"],
        "shared": cfg["n_shared_experts"],
        "first_dense": cfg["first_k_dense_replace"],
        "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
    }


def layer_shapes(cfg: dict, index: int) -> dict:
    """{path: (shape, kind)} of layer `index`; kind is "matrix" (fan-in
    is the second-to-last axis), "scale" or "bias" (`_leaf` also makes
    the token "table")."""
    m = dims(cfg)
    d, h = m["d"], m["heads"]
    tree = {
        "ln1/scale": ((d,), "scale"),
        "ln2/scale": ((d,), "scale"),
        "attn/q_a/weight": ((d, m["q_rank"]), "matrix"),
        "attn/q_norm/scale": ((m["q_rank"],), "scale"),
        "attn/q_b/weight": ((m["q_rank"], h * (m["nope"] + m["rope"])),
                            "matrix"),
        "attn/kv_a/weight": ((d, m["kv_rank"] + m["rope"]), "matrix"),
        "attn/kv_norm/scale": ((m["kv_rank"],), "scale"),
        "attn/kv_b/weight": ((m["kv_rank"], h * (m["nope"] + m["v"])),
                             "matrix"),
        "attn/o/weight": ((h * m["v"], d), "matrix"),
    }
    if index < m["first_dense"]:
        f = m["dense"]
        tree.update({"ffn/gate/weight": ((d, f), "matrix"),
                     "ffn/up/weight": ((d, f), "matrix"),
                     "ffn/down/weight": ((f, d), "matrix")})
    else:
        f, e, s = m["expert"], m["experts"], m["expert"] * m["shared"]
        tree.update({
            "moe/router/weight": ((d, e), "matrix"),
            "moe/router/bias": ((e,), "bias"),
            "moe/experts/gate": ((e, d, f), "matrix"),
            "moe/experts/up": ((e, d, f), "matrix"),
            "moe/experts/down": ((e, f, d), "matrix"),
            "moe/shared/gate/weight": ((d, s), "matrix"),
            "moe/shared/up/weight": ((d, s), "matrix"),
            "moe/shared/down/weight": ((s, d), "matrix"),
        })
    return tree


def _leaf(key, shape, kind):
    noise = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        noise = noise / math.sqrt(shape[-2])
    elif kind == "scale":
        noise = 1.0 + NOISE * noise
    elif kind == "bias":
        noise = NOISE * noise
    elif kind != "table":       # the table is the unit normal itself
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
    """The token table [vocab, d]: normal, std 1."""
    m = dims(cfg)
    return _make_one(jax.random.fold_in(seed_key(seed), EMBED),
                     (m["vocab"], m["d"]), "table")


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
