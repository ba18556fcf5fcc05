"""Seeded weights, made by the benchmark and handed to both sides.

One jitted call from the seed, on the device, in float32 (the type the
repo's `Linear` keeps its parameters in; compute casts to bf16). The
tree has the names of the program's checkpoint format, which is a data
interface: `blocks_<i>/attn/q_proj/weight` and so on. The program's own
initialiser is not used, so the reference needs nothing the program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02           # token table, biases, LayerNorm noise
MATRIX_STD = 0.04    # the layers' matrices


def seed_key(seed: int):
    """A key from any whole number up to and past 2**32."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _shapes(cfg: dict) -> dict:
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    tree = {"embed": {"weight": (v, d)},
            "ln_f": {"scale": (d,), "bias": (d,)}}
    for i in range(cfg["n_layer"]):
        tree[f"blocks_{i}"] = {
            "attn": {name: {"weight": (d, d), "bias": (d,)}
                     for name in ("q_proj", "k_proj", "v_proj", "out_proj")},
            "ffn": {"fc1": {"weight": (d, f), "bias": (f,)},
                    "fc2": {"weight": (f, d), "bias": (d,)}},
            "ln1": {"scale": (d,), "bias": (d,)},
            "ln2": {"scale": (d,), "bias": (d,)},
        }
    return tree


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, cfg_items):
    cfg = dict(cfg_items)
    shapes = _shapes(cfg)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [p for p, _ in jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, path, shape in zip(keys, paths, leaves):
        name = path[-1].key
        matrix = name == "weight" and path[0].key != "embed"
        noise = (MATRIX_STD if matrix else STD) * jax.random.normal(
            k, shape, jnp.float32)
        out.append(1.0 + noise if name == "scale" else noise)
    return jax.tree.unflatten(treedef, out)


def make_params(cfg: dict, seed: int):
    """The parameter tree of the configuration, float32, from the seed:
    the token table, biases and LayerNorm offsets normal with std 0.02,
    LayerNorm scales 1 plus the same noise (no leaf is a constant), the
    layers' matrices with std 0.04. At 0.02 throughout the tied head
    returns each position's own input token by a wide margin (92% of
    positions at GPT-2 medium's widths), and no loss of precision could
    change a served token; at 0.04 the layers outweigh the token's own
    embedding and the two best logits lie 0.09 apart at the median."""
    items = tuple(sorted((k, cfg[k]) for k in
                         ("n_embd", "n_inner", "vocab_size", "n_layer")))
    return _make(seed_key(seed), items)
