"""The latent ragged kernel's share of its roofline in the traced slice:
the least time the chip could take for the absorbed attention of the
slice's steps, every layer (the larger of the operations of the keys
attended over the bf16 peak and the cached rows of the steps' contexts,
read once, over the HBM bandwidth) over the device time of the kernel's
calls (`ragged_latent_attention`, the name its Pallas call carries).
Need is a step's mean over the slice's steps (`engine.step`'s
`attn_keys` and `kv_tokens_read`), time a step's mean over the
executions the trace shows."""

import importlib
import sys

from benchmarks import scope_reduce, trace_reduce

KERNEL = "ragged_latent_attention"


def read(obs):
    if (not obs.get("trace") or obs.get("peaks") is None
            or "flops" not in obs["config"]):
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    KERNEL)
    counts = scope_reduce.slice_counts(obs, ("attn_keys", "kv_tokens_read"))
    runs = scope_reduce.device_steps(obs)
    if not kernels or not counts or not runs:
        return None
    cfg = obs["config"]
    need = importlib.import_module(cfg["flops"]).mla_need(
        cfg, counts["attn_keys"] / counts["steps"],
        counts["kv_tokens_read"] / counts["steps"])
    layers = cfg["num_hidden_layers"]
    by_flops = layers * need["flops"] / obs["peaks"]["bf16_flops"]
    by_bytes = layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    spent = sum(v["total_s"] for v in kernels.values()) / runs
    calls = sum(v["count"] for v in kernels.values())
    print(f"mla_roofline_pct: a step of the slice's {counts['steps']} "
          f"attends {counts['attn_keys'] / counts['steps']} keys and reads "
          f"{counts['kv_tokens_read'] / counts['steps']} cached rows a "
          f"layer; {calls} kernel calls over {runs} executions, {spent} s "
          f"a step; bound by "
          f"{'compute' if by_flops >= by_bytes else 'memory'} "
          f"({by_flops} s against {by_bytes} s)", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / spent
