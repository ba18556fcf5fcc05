"""The SSD scan's share of its roofline in the traced slice: the least
time the chip could take for the scans of the slice's steps, every
layer (the larger of 4 operations a (head, state, value) a real token
over the bf16 peak and the bytes of the slots' state and tail, read and
written once, and of the tokens' x, B, C, delta and output, over the
HBM bandwidth; `flops_falconh1.ssd_need`) over the device time of the
kernel's calls (`ragged_ssd`, the name its Pallas call carries). Need
is a step's mean over the slice's steps (`engine.step`'s `ssm_tokens`
and `state_slots`: real tokens and slots, however many padded tiles the
kernel walks), time a step's mean over the executions the trace
shows."""

import importlib
import sys

from benchmarks import scope_reduce, trace_reduce

KERNEL = "ragged_ssd"


def read(obs):
    cfg = obs["config"]
    if (not obs.get("trace") or obs.get("peaks") is None
            or "flops" not in cfg or "mamba_n_groups" not in cfg):
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    KERNEL)
    counts = scope_reduce.slice_counts(obs, ("ssm_tokens", "state_slots"))
    runs = scope_reduce.device_steps(obs)
    if not kernels or not counts or not runs:
        return None
    need = importlib.import_module(cfg["flops"]).ssd_need(
        cfg, counts["ssm_tokens"] / counts["steps"],
        counts["state_slots"] / counts["steps"])
    layers = cfg["num_hidden_layers"]
    by_flops = layers * need["flops"] / obs["peaks"]["bf16_flops"]
    by_bytes = layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    spent = sum(v["total_s"] for v in kernels.values()) / runs
    calls = sum(v["count"] for v in kernels.values())
    print(f"ssd_roofline_pct: a step of the slice's {counts['steps']} scans "
          f"{counts['ssm_tokens'] / counts['steps']} real tokens of "
          f"{counts['state_slots'] / counts['steps']} slots a layer; {calls} "
          f"kernel calls over {runs} executions, {spent} s a step; bound by "
          f"{'compute' if by_flops >= by_bytes else 'memory'} "
          f"({by_flops} s against {by_bytes} s)", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / spent
