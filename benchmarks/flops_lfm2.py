"""Operations and bytes the short-convolution, routed-expert decoder
requires, from the configuration's keys alone
(`benchmarks/configs/lfm2-8b-a1b.json` names this module as `flops`).

As in `flops.py`, every function counts what the algorithm needs, not
what a program happens to execute: padding rows, a flat step's unused
width and an expert's weights read twice are not counted. One
multiply-add is two operations. The routed experts' need is
`flops_glm.moe_need`'s: the same layer, counted the same way.
"""

from __future__ import annotations

from benchmarks.flops_glm import moe_need  # noqa: F401  (the readers')


def params(cfg: dict) -> dict:
    """Matrix parameters of the parts of one layer, and of the head (the
    table). Norm scales, the convolution's taps and the expert bias take
    no matrix product."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    q, kv = h * hd, cfg["num_key_value_heads"] * hd
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {"conv": d * 3 * d + d * d,
            "attention": d * (q + 2 * kv) + q * d,
            "expert": expert,
            "router": d * cfg["num_experts"],
            "dense_ffn": 3 * d * cfg["intermediate_size"],
            "head": d * cfg["vocab_size"]}


def layer_counts(cfg: dict) -> dict:
    """Layers as run, by mixer and by FFN."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    dense = min(cfg["num_dense_layers"], len(types))
    return {"conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "dense": dense, "routed": len(types) - dense}


def active_params(cfg: dict) -> int:
    """Matrix parameters one token passes through below the head: every
    layer's mixer, the dense layers' FFN, and of an expert layer the
    router and the `num_experts_per_tok` routed experts."""
    p, n = params(cfg), layer_counts(cfg)
    return (n["conv"] * p["conv"] + n["attention"] * p["attention"]
            + n["dense"] * p["dense_ffn"]
            + n["routed"] * (p["router"]
                             + cfg["num_experts_per_tok"] * p["expert"]))


def attention_flops_per_key(cfg: dict) -> int:
    """One query of one attention layer against one key, all heads: q.k
    and p.v over the head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return h * 2 * 2 * (d // h)


def conv_flops_per_token(cfg: dict) -> int:
    """One token of one conv layer: the depthwise convolution's
    `conv_L_cache` multiply-adds a channel and the gate's two
    products."""
    return cfg["hidden_size"] * 2 * (cfg["conv_L_cache"] + 1)


def serve_flops_active(cfg: dict, prefill_tokens: float,
                       generated_tokens: float, prefill_context_sum: float,
                       generated_context_sum: float) -> float:
    """Forward operations the model needs for the tokens a serving
    window computed: 2 per active parameter per computed token, the
    head once per generated token, the convolution's a token in every
    conv layer, and attention per key a query head in the attention
    layers only (the context sums are the sums of position + 1)."""
    tokens = prefill_tokens + generated_tokens
    n = layer_counts(cfg)
    return (2.0 * active_params(cfg) * tokens
            + 2.0 * params(cfg)["head"] * generated_tokens
            + n["conv"] * float(conv_flops_per_token(cfg)) * tokens
            + n["attention"] * float(attention_flops_per_key(cfg))
            * (prefill_context_sum + generated_context_sum))
