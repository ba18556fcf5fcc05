"""The differential attention's share of its roofline in the traced
slice, over the layers that read the one paged pool (full and cross)
and the window layers: for each kind the larger of the operations of
the keys attended over the bf16 peak and the cached rows read, once,
over the HBM bandwidth (`flops_phi4flash.attn_need`; rows and keys
clipped to the window in a window layer), summed over the layers, over
the device time of the ragged kernel's calls in the step
(`ragged_diff_attention`, the name its Pallas call carries there). Need
is a step's mean over the slice's steps (`engine.step`'s `attn_keys_*`
and `kv_rows_*`), time a step's mean over the executions the trace
shows."""

import importlib
import sys

from benchmarks import scope_reduce, trace_reduce

KERNEL = "ragged_diff_attention"
FIELDS = ("attn_keys_full", "kv_rows_full", "attn_keys_window",
          "kv_rows_window")


def read(obs):
    cfg = obs["config"]
    if (not obs.get("trace") or obs.get("peaks") is None
            or "flops" not in cfg or "layer_kinds" not in cfg):
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    KERNEL)
    counts = scope_reduce.slice_counts(obs, FIELDS)
    runs = scope_reduce.device_steps(obs)
    if not kernels or not counts or not runs:
        return None
    flops = importlib.import_module(cfg["flops"])
    kinds = cfg["layer_kinds"]
    least, said = 0.0, []
    for kind, layers in (("full", kinds.count("full") + kinds.count("cross")),
                         ("window", kinds.count("window"))):
        need = flops.attn_need(
            cfg, counts[f"attn_keys_{kind}"] / counts["steps"],
            counts[f"kv_rows_{kind}"] / counts["steps"])
        by_flops = need["flops"] / obs["peaks"]["bf16_flops"]
        by_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
        least += layers * max(by_flops, by_bytes)
        said.append(f"{layers} {kind} layers each {by_flops} s of "
                    f"operations against {by_bytes} s of bytes")
    spent = sum(v["total_s"] for v in kernels.values()) / runs
    calls = sum(v["count"] for v in kernels.values())
    print(f"hybrid_attn_roofline_pct: over the slice's {counts['steps']} "
          f"steps, a step: {'; '.join(said)}; {calls} kernel calls over "
          f"{runs} executions, {spent} s a step", file=sys.stderr)
    return 100.0 * least / spent
