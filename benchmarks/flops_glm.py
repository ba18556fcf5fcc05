"""Operations and bytes the latent-attention, routed-expert decoder
requires, from the configuration's published keys alone
(`benchmarks/configs/glm-4.7-flash.json` names this module as `flops`).

As in `flops.py`, every function counts what the algorithm needs, not
what a program happens to execute: padding rows, a flat step's unused
width, the absorbed form's wider contraction and an expert's weights
read twice are not counted. One multiply-add is two operations.
"""

from __future__ import annotations

BF16 = 2      # bytes of a weight, an activation and a cached value


def params(cfg: dict) -> dict:
    """Matrix parameters of the parts of one layer, and of the head.
    Norm scales and the router's bias take no matrix product."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
           + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                        + cfg["v_head_dim"])
           + h * cfg["v_head_dim"] * d)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {"mla": mla, "expert": expert,
            "shared": expert * cfg["n_shared_experts"],
            "router": d * cfg["n_routed_experts"],
            "dense_ffn": 3 * d * cfg["intermediate_size"],
            "head": d * cfg["vocab_size"]}


def layer_counts(cfg: dict) -> tuple:
    """(dense layers, expert layers) as run."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def active_params(cfg: dict) -> int:
    """Matrix parameters one token passes through below the head: every
    layer's attention, the dense layers' FFN, and of an expert layer the
    router, the shared experts and the `num_experts_per_tok` routed."""
    p = params(cfg)
    dense, routed = layer_counts(cfg)
    per_expert_layer = (p["router"] + p["shared"]
                        + cfg["num_experts_per_tok"] * p["expert"])
    return ((dense + routed) * p["mla"] + dense * p["dense_ffn"]
            + routed * per_expert_layer)


def attention_flops_per_key(cfg: dict) -> int:
    """One query of one layer against one key, all heads, in the
    published form: q.k over the nope and rope dims, then p.v."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * 2 * (qk + cfg["v_head_dim"])


def serve_flops_active(cfg: dict, prefill_tokens: float,
                       generated_tokens: float, prefill_context_sum: float,
                       generated_context_sum: float) -> float:
    """Forward operations the model needs for the tokens a serving
    window computed: 2 per active parameter per computed token, the
    head once per generated token, and attention per key attended (the
    context sums are the sums of position + 1, as `flops.serve_flops`
    takes them)."""
    return (2.0 * active_params(cfg) * (prefill_tokens + generated_tokens)
            + 2.0 * params(cfg)["head"] * generated_tokens
            + float(attention_flops_per_key(cfg)) * cfg["num_hidden_layers"]
            * (prefill_context_sum + generated_context_sum))


def moe_need(cfg: dict, assignments: float, active_experts: float) -> dict:
    """The routed experts' work for `assignments` (token, expert) pairs
    that touched `active_experts` (layer, expert) pairs: three matrix
    products a pair; each touched expert's three matrices read once,
    each pair's input read and output written once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": 6.0 * d * f * assignments,
            "bytes": (active_experts * 3.0 * d * f * BF16
                      + assignments * 2.0 * d * BF16)}


def latent_row_bytes(cfg: dict) -> int:
    """One cached row as the pool holds it: kv_lora_rank +
    qk_rope_head_dim values in whole 128-lane tiles."""
    values = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-values // 128) * 128 * BF16


def mla_need(cfg: dict, keys_attended: float, kv_tokens_read: float) -> dict:
    """The absorbed attention of ONE layer: each query against each key
    it attends contracts the row (kv_lora_rank + qk_rope_head_dim) and
    accumulates the latent (kv_lora_rank), every head; each row of a
    step's contexts is read once."""
    h = cfg["num_attention_heads"]
    width = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return {"flops": h * 2.0 * width * keys_attended,
            "bytes": float(latent_row_bytes(cfg)) * kv_tokens_read}
