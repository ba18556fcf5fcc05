"""Operations and bytes the window and full GQA decoder with routed
experts requires, as one chip's share of its expert-parallel deployment,
from the configuration's keys alone
(`benchmarks/configs/k-exaone-236b-a23b.json` names this module as
`flops`).

As in `flops.py`, every function counts what the algorithm needs, not
what a program happens to execute: padding rows, a flat step's unused
width and an expert's weights read twice are not counted. One
multiply-add is two operations. What the chip is asked for is its
share: the experts it holds (`num_experts` of `num_experts x
expert_shards`), whatever the others compute. The routed experts' need
is `flops_glm.moe_need`'s, which the program's counts of HELD pairs and
held (layer, expert) pairs feed: the held experts' bytes alone.
"""

from __future__ import annotations

from benchmarks.flops_glm import moe_need  # noqa: F401  (the readers')
from benchmarks.flops_phi4flash import _clipped

BF16 = 2      # bytes of a weight, an activation and a cached value


def params(cfg: dict) -> dict:
    """Matrix parameters of the parts of one layer, and of the head.
    Norm scales and the selection bias take no matrix product; the
    token table is a lookup."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {"attention": d * (q + 2 * kv) + q * d,
            "expert": expert,
            "shared": cfg["num_shared_experts"] * expert,
            "router": d * cfg["num_experts"] * cfg["expert_shards"],
            "dense_ffn": 3 * d * cfg["intermediate_size"],
            "head": d * cfg["vocab_size"]}


def layer_counts(cfg: dict) -> dict:
    """Layers as run, by attention and by FFN."""
    n = cfg["num_hidden_layers"]
    kinds, mlp = cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]
    return {"window": kinds.count("sliding_attention"),
            "full": kinds.count("full_attention"),
            "dense": mlp.count("dense"), "routed": mlp.count("sparse")}


def held_experts_per_token(cfg: dict) -> float:
    """The chosen experts of a token that this share computes, on the
    mean: top_k over the shards (routing spread evenly), so that the
    shards together compute all top_k."""
    return cfg["num_experts_per_tok"] / cfg["expert_shards"]


def active_params(cfg: dict) -> float:
    """Matrix parameters one token passes through below the head, on
    this chip: every layer's attention, the dense layer's FFN, and of an
    expert layer the router, the shared expert and the held share of the
    chosen experts."""
    p, n = params(cfg), layer_counts(cfg)
    return ((n["window"] + n["full"]) * p["attention"]
            + n["dense"] * p["dense_ffn"]
            + n["routed"] * (p["router"] + p["shared"]
                             + held_experts_per_token(cfg) * p["expert"]))


def attention_flops_per_key(cfg: dict) -> int:
    """One query of one attention layer against one key, all heads: q.k
    and p.v over the head."""
    return cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


def serve_flops_active(cfg: dict, prefill_tokens: float,
                       generated_tokens: float, prefill_context_sum: float,
                       generated_context_sum: float) -> float:
    """Forward operations this chip's share needs for the tokens a
    serving window computed: 2 per active parameter per computed token,
    the head once per generated token, and attention per key attended:
    the whole context in the full layers, clipped to the window in the
    window layers (the context sums are the sums of position + 1; a
    generated token stands past the prompt, so its clip is min(its
    context, window) on the mean)."""
    n, w = layer_counts(cfg), cfg["sliding_window"]
    tokens = prefill_tokens + generated_tokens
    whole = prefill_context_sum + generated_context_sum
    mean = generated_context_sum / generated_tokens if generated_tokens else 0
    clipped = (_clipped(prefill_tokens, prefill_context_sum, w)
               + generated_tokens * min(mean, w))
    return (2.0 * active_params(cfg) * tokens
            + 2.0 * params(cfg)["head"] * generated_tokens
            + float(attention_flops_per_key(cfg))
            * (n["full"] * whole + n["window"] * clipped))


def kv_row_bytes(cfg: dict) -> int:
    """One cached row: every key head's k and v."""
    return cfg["num_key_value_heads"] * 2 * cfg["head_dim"] * BF16


def attn_need(cfg: dict, keys_attended: float, kv_rows_read: float) -> dict:
    """The attention of ONE layer: each query against each key it
    attends, every head (`attention_flops_per_key`); each cached row of
    a step's contexts (clipped to the window in a window layer, as the
    program's `kv_rows_window` and `attn_keys_window` are) read once."""
    return {"flops": float(attention_flops_per_key(cfg)) * keys_attended,
            "bytes": float(kv_row_bytes(cfg)) * kv_rows_read}
