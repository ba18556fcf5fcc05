"""The block-sparse attention's share of its roofline in the traced
slice: the least time the chip could take for the sparse layers'
attention of the slice's steps (every sparse layer: the larger of the
operations of the KEPT keys attended over the bf16 peak and the cached
rows read after selection, once, over the HBM bandwidth;
`flops_sala.sparse_need`) over the device time of the ragged kernel's
calls there (`ragged_sparse_attention`, the name its Pallas call
carries: one call a kv head a layer). Need is a step's mean over the
slice's steps (`engine.step`'s `sparse_keys` and `sparse_rows_read`, a
kv head: a decode row's kept blocks, a chunk row's whole context, which
its masked cells do read), time a step's mean over the executions the
trace shows. The selection before the call (scoring the index pool,
top-k) runs as XLA operations under the scope `sparse_select`, which a
trace does not name: its rows (`index_rows_read`) and its time are in
neither side of this share (PERF.md, Open questions)."""

import importlib
import sys

from benchmarks import scope_reduce, trace_reduce

KERNEL = "ragged_sparse_attention"
FIELDS = ("sparse_keys", "sparse_rows_read")


def read(obs):
    cfg = obs["config"]
    if (not obs.get("trace") or obs.get("peaks") is None
            or "flops" not in cfg or "mixer_types" not in cfg):
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    KERNEL)
    counts = scope_reduce.slice_counts(obs, FIELDS)
    runs = scope_reduce.device_steps(obs)
    if not kernels or not counts or not runs:
        return None
    need = importlib.import_module(cfg["flops"]).sparse_need(
        cfg, counts["sparse_keys"] / counts["steps"],
        counts["sparse_rows_read"] / counts["steps"], 0.0, 0.0)
    layers = cfg["mixer_types"].count("minicpm4")
    by_flops = layers * need["flops"] / obs["peaks"]["bf16_flops"]
    by_bytes = layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    spent = sum(v["total_s"] for v in kernels.values()) / runs
    calls = sum(v["count"] for v in kernels.values())
    print(f"sparse_attn_roofline_pct: a step of the slice's "
          f"{counts['steps']} attends "
          f"{counts['sparse_keys'] / counts['steps']} kept keys and reads "
          f"{counts['sparse_rows_read'] / counts['steps']} cached rows a "
          f"layer and kv head; {calls} kernel calls over {runs} executions, "
          f"{spent} s a step; bound by "
          f"{'compute' if by_flops >= by_bytes else 'memory'} "
          f"({by_flops} s against {by_bytes} s)", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / spent
