"""The grouped-query attention's share of its roofline in the traced
slice, over the window layers and the full layers: for each kind the
larger of the operations of the keys attended over the bf16 peak and
the cached rows read, once, over the HBM bandwidth (the configuration's
`attn_need`; rows and keys clipped to the window in a window layer),
summed over the layers, over the device time of the ragged kernel's
calls in the step (`ragged_gqa_window` and `ragged_gqa_full`, the names
its Pallas calls carry there). Need is a step's mean over the slice's
steps (`engine.step`'s `attn_keys_*` and `kv_rows_*`), time a step's
mean over the executions the trace shows. None where the step has no
call of either name."""

import importlib
import sys

from benchmarks import scope_reduce, trace_reduce

KERNELS = {"window": "ragged_gqa_window", "full": "ragged_gqa_full"}
FIELDS = ("attn_keys_full", "kv_rows_full", "attn_keys_window",
          "kv_rows_window")
KIND_OF = {"window": "sliding_attention", "full": "full_attention"}


def read(obs):
    cfg = obs["config"]
    if (not obs.get("trace") or obs.get("peaks") is None
            or "flops" not in cfg or "layer_types" not in cfg):
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    *KERNELS.values())
    counts = scope_reduce.slice_counts(obs, FIELDS)
    runs = scope_reduce.device_steps(obs)
    if not kernels or not counts or not runs:
        return None
    flops = importlib.import_module(cfg["flops"])
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    least, said = 0.0, []
    for kind in KERNELS:
        layers = kinds.count(KIND_OF[kind])
        need = flops.attn_need(
            cfg, counts[f"attn_keys_{kind}"] / counts["steps"],
            counts[f"kv_rows_{kind}"] / counts["steps"])
        by_flops = need["flops"] / obs["peaks"]["bf16_flops"]
        by_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
        least += layers * max(by_flops, by_bytes)
        spent = sum(v["total_s"] for k, v in kernels.items()
                    if KERNELS[kind] in k) / runs
        said.append(f"{layers} {kind} layers each {by_flops} s of "
                    f"operations against {by_bytes} s of bytes, their "
                    f"calls {spent} s a step")
    spent = sum(v["total_s"] for v in kernels.values()) / runs
    calls = sum(v["count"] for v in kernels.values())
    print(f"gqa_attn_roofline_pct: over the slice's {counts['steps']} "
          f"steps, a step: {'; '.join(said)}; {calls} kernel calls over "
          f"{runs} executions, {spent} s a step", file=sys.stderr)
    return 100.0 * least / spent
