"""The routed experts' share of their roofline in the traced slice: the
least time the chip could take for the slice's (token, expert) pairs
(the larger of their operations over the bf16 peak and the bytes of the
experts they touched, plus activations, over the HBM bandwidth) over the
device time of every operation under the program's `moe_experts` scope:
sort, gather, the grouped products, un-sort and sum, whatever implements
them. Need is a step's mean over the slice's steps (the program's
`engine.step` fields), time a step's mean over the executions the trace
shows. Which of the two bounds it goes to standard error."""

import importlib
import sys

from benchmarks import scope_reduce


def read(obs):
    scope = (obs.get("scope_s") or {}).get("moe_experts")
    if (obs.get("peaks") is None or not scope or not scope["total_s"]
            or "flops" not in obs["config"]):
        return None
    counts = scope_reduce.slice_counts(
        obs, ("moe_assignments", "moe_active_experts"))
    runs = scope_reduce.device_steps(obs)
    if not counts or not runs:
        return None
    need = importlib.import_module(obs["config"]["flops"]).moe_need(
        obs["config"], counts["moe_assignments"] / counts["steps"],
        counts["moe_active_experts"] / counts["steps"])
    by_flops = need["flops"] / obs["peaks"]["bf16_flops"]
    by_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    spent = scope["total_s"] / runs
    print(f"moe_roofline_pct: a step of the slice's {counts['steps']} has "
          f"{counts['moe_assignments'] / counts['steps']} pairs on "
          f"{counts['moe_active_experts'] / counts['steps']} (layer, expert)"
          f" pairs; {spent} s a step under moe_experts over {runs} "
          f"executions; bound by "
          f"{'compute' if by_flops >= by_bytes else 'memory'} "
          f"({by_flops} s against {by_bytes} s)", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / spent
