"""Lightning attention's share of its roofline in the traced slice: the
least time the chip could take for the lightning layers of the slice's
steps (every lightning layer: the larger of 4 . 128 . 128 operations a
head a real token over the bf16 peak and the bytes of the slots' state,
read and written once, and of the tokens' q, k, v and output, over the
HBM bandwidth; `flops_sala.lightning_need`) over the device time of the
kernel's calls (`ragged_lightning_attention`, the name its Pallas call
carries). Need is a step's mean over the slice's steps (`engine.step`'s
`la_tokens` and `state_slots`: real tokens and slots, however many
padded tiles the kernel walks), time a step's mean over the executions
the trace shows."""

import importlib
import sys

from benchmarks import scope_reduce, trace_reduce

KERNEL = "ragged_lightning_attention"


def read(obs):
    cfg = obs["config"]
    if (not obs.get("trace") or obs.get("peaks") is None
            or "flops" not in cfg or "mixer_types" not in cfg):
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    KERNEL)
    counts = scope_reduce.slice_counts(obs, ("la_tokens", "state_slots"))
    runs = scope_reduce.device_steps(obs)
    if not kernels or not counts or not runs:
        return None
    need = importlib.import_module(cfg["flops"]).lightning_need(
        cfg, counts["la_tokens"] / counts["steps"],
        counts["state_slots"] / counts["steps"])
    layers = cfg["mixer_types"].count("lightning-attn")
    by_flops = layers * need["flops"] / obs["peaks"]["bf16_flops"]
    by_bytes = layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    spent = sum(v["total_s"] for v in kernels.values()) / runs
    calls = sum(v["count"] for v in kernels.values())
    print(f"lightning_roofline_pct: a step of the slice's {counts['steps']} "
          f"walks {counts['la_tokens'] / counts['steps']} real tokens of "
          f"{counts['state_slots'] / counts['steps']} slots a layer; {calls} "
          f"kernel calls over {runs} executions, {spent} s a step; bound by "
          f"{'compute' if by_flops >= by_bytes else 'memory'} "
          f"({by_flops} s against {by_bytes} s)", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / spent
