"""Mean device time of one execution of the engine's step program in
the traced slice. The median goes to standard error."""

import sys

from benchmarks import trace_reduce


def read(obs):
    if not obs.get("trace"):
        return None
    found = trace_reduce.matching(trace_reduce.program_sums(obs["trace"]),
                                  "step_fn")
    if not found:
        return None
    name, v = max(found.items(), key=lambda kv: kv[1]["total_s"])
    print(f"step_device_ms: {name} ran {v['count']} times, median "
          f"{v['median_s'] * 1e3} ms", file=sys.stderr)
    return 1e3 * v["total_s"] / v["count"]
