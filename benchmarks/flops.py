"""Operations and bytes the model requires, from its shapes alone.

Every function counts what the algorithm needs, not what a program
happens to execute: recomputation, padding rows and a flat step's unused
width are not counted. One multiply-add is two operations.
"""

from __future__ import annotations


def causal_lm_params(cfg: dict) -> dict:
    """Parameter counts of the decoder the configuration describes:
    tied token table, sinusoid positions (no parameters), per layer four
    attention projections and two feed-forward matrices with biases and
    two LayerNorms, one final LayerNorm."""
    d, f, v, n = (cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"],
                  cfg["n_layer"])
    per_layer = 4 * (d * d + d) + (d * f + f) + (f * d + d) + 4 * d
    return {"embed": v * d, "layers": n * per_layer, "final_ln": 2 * d,
            "total": v * d + n * per_layer + 2 * d}


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of one training step (copied arithmetic of
    paddle_tpu/obs/goodput.py causal_lm_step_flops): 6 per parameter per
    token, plus causal attention 6*B*T^2*D per layer (the causal half of
    QK^T and PV, forward 2*T^2*D, backward twice that)."""
    tokens = batch * seq
    p = causal_lm_params(cfg)["total"]
    return (6.0 * tokens * p
            + 6.0 * batch * float(seq) ** 2 * cfg["n_embd"] * cfg["n_layer"])


def serve_flops(cfg: dict, prefill_tokens: int, generated_tokens: int,
                prefill_context_sum: float,
                generated_context_sum: float) -> float:
    """Forward operations for the tokens a serving window computed.
    Every computed token passes the layers (2 per layer parameter); only
    a token that is sampled from passes the vocabulary head, which is
    once per generated token. A token at position p attends p+1 keys:
    QK^T and PV, 4*D per key per layer. The context sums are the sums of
    p+1 over the prefill tokens computed and over the tokens generated."""
    p = causal_lm_params(cfg)
    d, n = cfg["n_embd"], cfg["n_layer"]
    body = 2.0 * (p["layers"] + p["final_ln"])
    head = 2.0 * p["embed"]
    return ((prefill_tokens + generated_tokens) * body
            + generated_tokens * head
            + 4.0 * d * n * (prefill_context_sum + generated_context_sum))


def flash_flops(batch: int, seq: int, heads: int, head_dim: int) -> dict:
    """Causal flash attention of one layer (arithmetic of
    tools/flash_roofline.py kernel_rates): forward 2*B*T^2*d*H, the
    causal half of QK^T and PV; backward twice the forward."""
    fwd = 2.0 * batch * seq * seq * head_dim * heads
    return {"fwd": fwd, "bwd": 2.0 * fwd}


def flash_bytes(batch: int, seq: int, heads: int, head_dim: int,
                itemsize: int = 2) -> dict:
    """The least HBM traffic of one layer's attention: forward reads q,
    k, v and writes o once; backward reads q, k, v, o, do and writes dq,
    dk, dv once. Re-reads of k and v per query block are the kernel's
    choice, not the algorithm's need."""
    one = batch * seq * heads * head_dim * itemsize
    return {"fwd": 4.0 * one, "bwd": 8.0 * one}
