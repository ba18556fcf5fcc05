"""Operations and bytes the parallel hybrid decoder requires, from the
configuration's keys alone (`benchmarks/configs/falcon-h1-34b.json`
names this module as `flops`).

As in `flops.py`, every function counts what the algorithm needs, not
what a program happens to execute: padding rows, a flat step's unused
width and a slot's state moved though no token walked it are not
counted. One multiply-add is two operations.
"""

from __future__ import annotations

BF16 = 2      # bytes of a weight, an activation and a cached value
F32 = 4       # bytes of a state value, and of what the scan takes


def params(cfg: dict) -> dict:
    """Matrix parameters of one layer's attention, Mamba-2 mixer and
    MLP, and of the head. Norm scales, the convolution and the scan's
    per-head leaves take no matrix product."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    ds = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"attention": d * (q + 2 * kv) + q * d,
            "mamba": d * (2 * ds + 2 * gn + cfg["mamba_n_heads"]) + ds * d,
            "mlp": 3 * d * f,
            "head": d * cfg["vocab_size"]}


def active_params(cfg: dict) -> int:
    """Matrix parameters one token passes through below the head: every
    one but the token table (a lookup)."""
    p = params(cfg)
    return cfg["num_hidden_layers"] * (p["attention"] + p["mamba"]
                                       + p["mlp"])


def attention_flops_per_key(cfg: dict) -> int:
    """One query of one layer against one key, all heads: q.k and p.v
    over the head."""
    return cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


def ssd_flops_per_token(cfg: dict) -> int:
    """One token of one layer's scan, all heads: the state's update
    (x B^T, a multiply-add an entry) and its read (C S, another)."""
    return (cfg["mamba_n_heads"] * 4 * cfg["mamba_d_state"]
            * cfg["mamba_d_head"])


def serve_flops_active(cfg: dict, prefill_tokens: float,
                       generated_tokens: float, prefill_context_sum: float,
                       generated_context_sum: float) -> float:
    """Forward operations the model needs for the tokens a serving
    window computed: 2 per active parameter per computed token, the
    head once per generated token, attention per key a query head
    (the context sums are the sums of position + 1), and the scan's
    operations a token, in every layer."""
    tokens = prefill_tokens + generated_tokens
    layers = cfg["num_hidden_layers"]
    return (2.0 * active_params(cfg) * tokens
            + 2.0 * params(cfg)["head"] * generated_tokens
            + layers * float(attention_flops_per_key(cfg))
            * (prefill_context_sum + generated_context_sum)
            + layers * float(ssd_flops_per_token(cfg)) * tokens)


def ssd_state_bytes(cfg: dict) -> int:
    """What one slot keeps of one layer's scan: the state (float32) and
    the convolution's tail (bf16)."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    conv = h * p + 2 * cfg["mamba_n_groups"] * n
    return h * n * p * F32 + (cfg["mamba_d_conv"] - 1) * conv * BF16


def ssd_need(cfg: dict, tokens: float, slots: float) -> dict:
    """ONE layer's scan in a step over `tokens` real tokens of `slots`
    sequences: each slot's state and tail read and written once; a
    token's x, B, C, delta in and its y out (float32, as the kernel
    takes them)."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"flops": float(ssd_flops_per_token(cfg)) * tokens,
            "bytes": (slots * 2.0 * ssd_state_bytes(cfg)
                      + tokens * (2.0 * h * p + 2.0 * gn + h) * F32)}
