"""Operations and bytes the decoder-hybrid-decoder requires, from the
configuration's keys alone (`benchmarks/configs/phi-4-mini-flash.json`
names this module as `flops`).

As in `flops.py`, every function counts what the algorithm needs, not
what a program happens to execute: padding rows, a flat step's unused
width, the pair layout's wider contraction (a query head zero on its
partner's lanes) and a slot's state copied though no token walked it are
not counted. One multiply-add is two operations.
"""

from __future__ import annotations

BF16 = 2      # bytes of a weight, an activation and a cached value
F32 = 4       # bytes of a state value, and of B and C


def kinds(cfg: dict) -> dict:
    """How many layers of each kind."""
    return {k: cfg["layer_kinds"].count(k)
            for k in ("mamba", "window", "full", "gmu", "cross")}


def params(cfg: dict) -> dict:
    """Matrix parameters of one layer of each kind, of the FFN every
    layer has, and of the head. Norms, biases, the convolution's taps,
    A, D and the lambda vectors take no matrix product."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dn, n, r = cfg["ssm_d_inner"], cfg["ssm_d_state"], cfg["ssm_dt_rank"]
    kvd = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    attn = d * (d + 2 * kvd) + d * d
    return {"ffn": 3 * d * f,
            "mamba": d * 2 * dn + dn * (r + 2 * n) + r * dn + dn * d,
            "gmu": 2 * d * dn,
            "window": attn, "full": attn,
            "cross": 2 * d * d,
            "head": d * cfg["vocab_size"]}


def active_params(cfg: dict) -> int:
    """Matrix parameters one token passes through below the head: every
    one but the token table (a lookup)."""
    p, k = params(cfg), kinds(cfg)
    return (cfg["num_hidden_layers"] * p["ffn"]
            + sum(k[name] * p[name] for name in k))


def attention_flops_per_key(cfg: dict) -> int:
    """One query of one attention layer against one key, all heads: q.k
    over the head (64), then p.V over the pair's value (128)."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"] * 2 * (hd + 2 * hd)


def _clipped(tokens: float, context_sum: float, window: int) -> float:
    """The sum of min(position + 1, window) over `tokens` tokens whose
    positions + 1 sum to `context_sum`, taking them as runs from
    position 0 of one equivalent length L (a run's positions + 1 sum to
    L (L + 1) / 2): a run keeps its sum below the window and gains
    `window` a token past it."""
    if tokens <= 0:
        return 0.0
    run = max(2.0 * context_sum / tokens - 1.0, 1.0)
    if run <= window:
        return context_sum
    runs = tokens / run
    return runs * (window * (window + 1) / 2.0 + (run - window) * window)


def serve_flops_active(cfg: dict, prefill_tokens: float,
                       generated_tokens: float, prefill_context_sum: float,
                       generated_context_sum: float) -> float:
    """Forward operations the model needs for the tokens a serving
    window computed: 2 per active parameter per computed token, the
    head once per generated token, the scan's 9 per (channel, state) a
    token, and attention per key attended: the whole context in the
    full and cross layers, clipped to the window in the window layers
    (the context sums are the sums of position + 1, as
    `flops.serve_flops` takes them; a generated token stands past the
    prompt, so its clip is min(its context, window) on the mean)."""
    k, w = kinds(cfg), cfg["sliding_window"]
    tokens = prefill_tokens + generated_tokens
    whole = prefill_context_sum + generated_context_sum
    mean = generated_context_sum / generated_tokens if generated_tokens else 0
    clipped = (_clipped(prefill_tokens, prefill_context_sum, w)
               + generated_tokens * min(mean, w))
    return (2.0 * active_params(cfg) * tokens
            + 2.0 * params(cfg)["head"] * generated_tokens
            + k["mamba"] * ssm_need(cfg, tokens, 0)["flops"]
            + float(attention_flops_per_key(cfg))
            * ((k["full"] + k["cross"]) * whole + k["window"] * clipped))


def ssm_need(cfg: dict, ssm_tokens: float, state_slots: float) -> dict:
    """The selective scan of ONE state-space layer over `ssm_tokens`
    real tokens of `state_slots` sequences: 9 operations a (channel,
    state) a token (the decay's product and exponential, the input's two
    products, the update's two, the output's two and the skip); each
    slot's state (float32) and tail (bf16) read and written once; a
    token's u', delta and z in and its gated output out (bf16), its B
    and C (float32)."""
    dn, n, k = cfg["ssm_d_inner"], cfg["ssm_d_state"], cfg["ssm_d_conv"]
    return {"flops": 9.0 * dn * n * ssm_tokens,
            "bytes": (state_slots * 2.0 * (dn * n * F32
                                           + dn * (k - 1) * BF16)
                      + ssm_tokens * (4.0 * dn * BF16 + 2.0 * n * F32))}


def kv_row_bytes(cfg: dict) -> int:
    """One cached row: every key head's k and v."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_key_value_heads"] * 2 * hd * BF16


def attn_need(cfg: dict, keys_attended: float, kv_rows_read: float) -> dict:
    """The differential attention of ONE layer: each query against each
    key it attends, every head (`attention_flops_per_key`); each cached
    row of a step's contexts (clipped to the window where there is one)
    read once."""
    return {"flops": float(attention_flops_per_key(cfg)) * keys_attended,
            "bytes": float(kv_row_bytes(cfg)) * kv_rows_read}
