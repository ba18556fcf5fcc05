"""The flash kernels' share of their roofline in the traced slice: the
least time the chip could take for the attention of the steps seen (the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth) over the device time of the kernels' events. In the trainer's
step every Pallas kernel (`tpu_custom_call`) is a flash kernel: forward,
dq, dk/dv, one each a layer. A forward pass run again for the backward
is time spent and not work needed. Which of the two bounds it goes to
standard error."""

import sys

from benchmarks import flops, trace_reduce


def read(obs):
    if not obs.get("trace") or obs.get("peaks") is None:
        return None
    kernels = trace_reduce.matching(trace_reduce.op_sums(obs["trace"]),
                                    "tpu_custom_call")
    steps = trace_reduce.matching(trace_reduce.program_sums(obs["trace"]),
                                  "step_fn")
    if not kernels or not steps:
        return None
    cfg, mix, peaks = obs["config"], obs["traffic"], obs["peaks"]
    shape = (mix["batch"], mix["seq"], cfg["n_head"],
             cfg["n_embd"] // cfg["n_head"])
    need_f, need_b = flops.flash_flops(*shape), flops.flash_bytes(*shape)
    layer_steps = cfg["n_layer"] * max(v["count"] for v in steps.values())
    spent = sum(v["total_s"] for v in kernels.values())
    calls = sum(v["count"] for v in kernels.values())
    by_flops = layer_steps * (need_f["fwd"] + need_f["bwd"]) / peaks[
        "bf16_flops"]
    by_bytes = layer_steps * (need_b["fwd"] + need_b["bwd"]) / peaks[
        "hbm_bytes_per_s"]
    print(f"flash_roofline: {layer_steps} layer-steps, {calls} kernel "
          f"calls, {spent} s; bound by "
          f"{'compute' if by_flops >= by_bytes else 'memory'} "
          f"({by_flops} s against {by_bytes} s)", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / spent
