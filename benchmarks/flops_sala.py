"""Operations and bytes the sparse-and-linear decoder requires, from the
configuration's keys alone (`benchmarks/configs/minicpm-sala.json` names
this module as `flops`).

As in `flops.py`, every function counts what the algorithm needs, not
what a program happens to execute: padding rows, a flat step's unused
width, a masked span's keys that no query kept and a slot's state moved
though no token walked it are not counted. One multiply-add is two
operations.
"""

from __future__ import annotations

BF16 = 2      # bytes of a weight, an activation and a cached value
F32 = 4       # bytes of a state value


def kinds(cfg: dict) -> dict:
    return {k: cfg["mixer_types"].count(k)
            for k in ("minicpm4", "lightning-attn")}


def params(cfg: dict) -> dict:
    """Matrix parameters of one layer of each kind, of the FFN every
    layer has, and of the head. Norm scales take no matrix product."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    w = cfg["num_attention_heads"] * cfg["head_dim"]
    kvw = cfg["num_key_value_heads"] * cfg["head_dim"]
    lw = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return {"ffn": 3 * d * f,
            "minicpm4": d * w + d * 2 * kvw + d * w + w * d,
            "lightning-attn": d * 3 * lw + d * lw + lw * d,
            "head": d * cfg["vocab_size"]}


def active_params(cfg: dict) -> int:
    """Matrix parameters one token passes through below the head: every
    one but the token table (a lookup)."""
    p, k = params(cfg), kinds(cfg)
    return (cfg["num_hidden_layers"] * p["ffn"]
            + sum(k[name] * p[name] for name in k))


def attention_flops_per_key(cfg: dict) -> int:
    """One query of one sparse layer against one KEPT key, all heads:
    q.k and p.v over the head."""
    return cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


def index_flops_per_row(cfg: dict) -> int:
    """One query against one compressed key, all heads: q.K~."""
    return cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def lightning_flops_per_token(cfg: dict) -> int:
    """One token of one lightning layer, all heads: the state's update
    (k^T v, a multiply-add an entry) and its read (q S, another)."""
    hd = cfg["lightning_head_dim"]
    return cfg["lightning_nh"] * 4 * hd * hd


def kept_keys(cfg: dict, context: float) -> float:
    """Keys a query at `context` (position + 1) attends in a sparse
    layer, on the mean over a block's offsets: all below dense_len, else
    the first blocks, the top blocks and the local window's."""
    sel = cfg["sparse"]
    if context <= sel["dense_len"]:
        return context
    blk = sel["block"]
    return min(context, (sel["init_blocks"] + sel["topk"]) * blk
               + sel["local"] + blk / 2.0)


def serve_flops_active(cfg: dict, prefill_tokens: float,
                       generated_tokens: float, prefill_context_sum: float,
                       generated_context_sum: float) -> float:
    """Forward operations the model needs for the tokens a serving
    window computed: 2 per active parameter per computed token, the
    head once per generated token, the lightning layers' 4 . hd . hd a
    head a token, and the sparse layers per KEPT key a query head plus
    the indexer's product a compressed row (one a `stride` tokens of the
    context past dense_len). The context sums are the sums of position
    + 1; a token's kept keys are taken at the mean context of its
    kind."""
    k, sel = kinds(cfg), cfg["sparse"]
    tokens = prefill_tokens + generated_tokens
    attn = index = 0.0
    for n, total in ((prefill_tokens, prefill_context_sum),
                     (generated_tokens, generated_context_sum)):
        if n:
            mean = total / n
            attn += n * kept_keys(cfg, mean)
            if mean > sel["dense_len"]:
                index += n * mean / sel["stride"]
    return (2.0 * active_params(cfg) * tokens
            + 2.0 * params(cfg)["head"] * generated_tokens
            + k["lightning-attn"] * float(lightning_flops_per_token(cfg))
            * tokens
            + k["minicpm4"] * (float(attention_flops_per_key(cfg)) * attn
                               + float(index_flops_per_row(cfg)) * index))


def kv_row_bytes(cfg: dict) -> int:
    """One cached row: every kv head's k and v."""
    return cfg["num_key_value_heads"] * 2 * cfg["head_dim"] * BF16


def index_row_bytes(cfg: dict) -> int:
    """One compressed row: every kv head's mean key."""
    return cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def sparse_need(cfg: dict, keys: float, rows_read: float,
                index_rows: float, queries: float) -> dict:
    """ONE sparse layer of a step: `keys` kept keys attended (summed
    over the real queries, a kv head), `rows_read` cached rows read
    after selection (a kv head: every head reads as many, and a row of
    `kv_row_bytes` holds all of them), `index_rows` compressed rows
    scored, by `queries` real queries past dense_len."""
    return {"flops": (float(attention_flops_per_key(cfg)) * keys
                      + float(index_flops_per_row(cfg)) * index_rows
                      * max(queries, 1.0)),
            "bytes": (float(kv_row_bytes(cfg)) * rows_read
                      + float(index_row_bytes(cfg)) * index_rows)}


def lightning_need(cfg: dict, tokens: float, state_slots: float) -> dict:
    """ONE lightning layer of a step over `tokens` real tokens of
    `state_slots` sequences: each slot's state (float32) read and
    written once; a token's q, k, v in and its output out (float32, as
    the kernel takes them)."""
    h, hd = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return {"flops": float(lightning_flops_per_token(cfg)) * tokens,
            "bytes": (state_slots * 2.0 * h * hd * hd * F32
                      + tokens * 4.0 * h * hd * F32)}
