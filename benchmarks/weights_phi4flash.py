"""Seeded weights of the decoder-hybrid-decoder
(`benchmarks/configs/phi-4-mini-flash.json`), a layer at a time.

A layer's leaves come from `(seed, layer index)` alone, as bf16 values:
the program's whole tree (`make_params`, 7.7 GB at the published sizes)
and the reference's layer loop (`layer`, one layer in float32 at a time)
make the same numbers, and neither needs what the other made. The tree
has the names of the program's checkpoint format, which is a data
interface. The program's own initialiser is not used.

Scales (the configuration's `assumed.weights`): every matrix normal with
std 1 / sqrt(fan-in), the convolution's four taps among them, so each
projection of a unit-RMS input has unit RMS; the token table std 0.02
(the head is the table: at std 1 every position's best logit would be
its own input token by 50 standard deviations, and nothing a layer
computes could change a served token; at 0.02 the layers' sum outweighs
the token's own row after the first layer and the logits have unit
scale); norm scales and the scan's skip D 1 + 0.02 noise; biases and the
four lambda vectors normal std 0.1, non-zero, so that a dropped term
fails the comparison; A_log the log of 1..d_state in every channel and
the step's bias the inverse softplus of a log-uniform draw from [1e-3,
1e-1], as Mamba initialises them: a scan whose decay is all 0 or all 1
tests nothing.

    python3 benchmarks/weights_phi4flash.py     # prints the exact count
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
BIAS = 0.1
EMBED, NORM_F = 1_000_001, 1_000_003      # "layer" indices
DT_MIN, DT_MAX = 1e-3, 1e-1


def dims(cfg: dict) -> dict:
    """The widths of the layers, from the configuration's keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d, "heads": h, "kv_heads": cfg["num_key_value_heads"],
        "hd": d // h, "ffn": cfg["intermediate_size"],
        "window": cfg["sliding_window"], "inner": cfg["ssm_d_inner"],
        "state": cfg["ssm_d_state"], "conv": cfg["ssm_d_conv"],
        "dt_rank": cfg["ssm_dt_rank"], "vocab": cfg["vocab_size"],
        "eps": float(cfg["layer_norm_eps"]),
    }


def layer_shapes(cfg: dict, index: int) -> dict:
    """{path: (shape, kind)} of layer `index`."""
    m = dims(cfg)
    d, f, dn, n = m["d"], m["ffn"], m["inner"], m["state"]
    kvd = m["kv_heads"] * m["hd"]
    tree = {
        "ln1/scale": ((d,), "scale"), "ln1/bias": ((d,), "bias"),
        "ln2/scale": ((d,), "scale"), "ln2/bias": ((d,), "bias"),
        "ffn/w1/weight": ((d, 2 * f), "matrix"),
        "ffn/w2/weight": ((f, d), "matrix"),
    }
    kind = cfg["layer_kinds"][index]
    if kind == "mamba":
        tree.update({
            "mixer/in_proj/weight": ((d, 2 * dn), "matrix"),
            "mixer/conv/weight": ((m["conv"], dn), "matrix"),
            "mixer/conv/bias": ((dn,), "bias"),
            "mixer/x_proj/weight": ((dn, m["dt_rank"] + 2 * n), "matrix"),
            "mixer/dt_proj/weight": ((m["dt_rank"], dn), "matrix"),
            "mixer/dt_proj/bias": ((dn,), "dt_bias"),
            "mixer/A_log": ((dn, n), "a_log"),
            "mixer/D": ((dn,), "scale"),
            "mixer/out_proj/weight": ((dn, d), "matrix"),
        })
    elif kind == "gmu":
        tree.update({"mixer/w1/weight": ((d, dn), "matrix"),
                     "mixer/w2/weight": ((dn, d), "matrix")})
    else:
        if kind == "cross":
            tree.update({"mixer/q/weight": ((d, d), "matrix"),
                         "mixer/q/bias": ((d,), "bias")})
        else:
            tree.update({"mixer/qkv/weight": ((d, d + 2 * kvd), "matrix"),
                         "mixer/qkv/bias": ((d + 2 * kvd,), "bias")})
        tree.update({f"mixer/lambda_{x}": ((m["hd"],), "bias")
                     for x in ("q1", "k1", "q2", "k2")})
        tree.update({"mixer/subln/scale": ((2 * m["hd"],), "scale"),
                     "mixer/o/weight": ((d, d), "matrix"),
                     "mixer/o/bias": ((d,), "bias")})
    return tree


def _leaf(key, shape, kind):
    if kind == "a_log":     # log of 1..d_state in every channel
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
            shape).astype(jnp.bfloat16)
    if kind == "dt_bias":   # softplus(bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(DT_MIN), math.log(DT_MAX)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16)
    noise = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        noise = noise / math.sqrt(shape[-2])
    elif kind == "scale":
        noise = 1.0 + NOISE * noise
    elif kind == "bias":
        noise = BIAS * noise
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


def norm_f(cfg: dict, seed: int) -> dict:
    d = dims(cfg)["d"]
    key = jax.random.fold_in(seed_key(seed), NORM_F)
    return {"scale": _make_one(jax.random.fold_in(key, 0), (d,), "scale"),
            "bias": _make_one(jax.random.fold_in(key, 1), (d,), "bias")}


def make_params(cfg: dict, seed: int) -> dict:
    """The program's whole parameter tree, a layer at a time: the bf16
    values, held in the configuration's `param_dtype`."""
    tree = {"embed": {"weight": embed(cfg, seed)},
            "norm_f": norm_f(cfg, seed)}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"blocks_{i}"] = layer(cfg, seed, i)
    dtype = jnp.dtype(cfg["param_dtype"])
    if dtype == jnp.bfloat16:
        return tree
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def count_params(cfg: dict) -> int:
    m = dims(cfg)
    total = m["vocab"] * m["d"] + 2 * m["d"]
    for i in range(cfg["num_hidden_layers"]):
        total += sum(math.prod(shape)
                     for shape, _ in layer_shapes(cfg, i).values())
    return total


if __name__ == "__main__":
    from benchmarks.common import load_json
    print(count_params(load_json("benchmarks", "configs",
                                 "phi-4-mini-flash.json")))
