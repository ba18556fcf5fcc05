"""The two gaps around the device's step, from one kept trace, on one clock.

    python3 benchmarks/run.py --workload <cell> ... --trace 1 --keep-trace 1
    python3 benchmarks/handoff.py .bench_trace/<cell>

`step_transfer_ms` is a difference of two means on two clocks (the ring's
`engine.dispatch` start to `engine.fetch` end, less the trace's device time
a step). In a kept trace the program's spans lie on the `/host:CPU` plane
(`host_spans.load_annotations`) beside the device's executions
(`trace_reduce.load_xplane`) in ONE file, so here each whole execution of
the step program is set against the spans of its own engine step:

    launch_to_start_ms   the execution's start - `engine.dispatch`'s start
    end_to_ready_ms      `engine.wait`'s end   - the execution's end
    fetch_after_ready_ms `engine.fetch`'s end  - `engine.wait`'s end

and their sum is, step by step, what `step_transfer_ms` holds. `reduce` is
what a later change to `tracing.TraceSlice.reduce` can call once it hands
the host plane to the readers: `launch_to_start_ms` and `end_to_ready_ms`
are then two per-layer metrics of three lines each.
"""

from __future__ import annotations

import bisect
import json
import statistics
import sys

if __package__ in (None, ""):    # run by hand, from anywhere
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import host_spans, trace_reduce  # noqa: E402
from benchmarks.span_reduce import percentile  # noqa: E402

GAPS = ("launch_to_start_ms", "end_to_ready_ms", "fetch_after_ready_ms")


def whole_executions(device_events: list) -> list:
    """The step program's executions on the first device but the first
    and the last, which the profiler's start and stop may have cut:
    `span_reduce.device_cycles`'s rule."""
    found = trace_reduce.matching(trace_reduce.program_sums(device_events),
                                  "step_fn")
    if not found:
        return []
    name = max(found, key=lambda k: found[k]["total_s"])
    plane = min(e.plane for e in device_events)
    runs = sorted((e for e in device_events
                   if e.plane == plane and e.name == name
                   and e.line == trace_reduce.MODULES_LINE),
                  key=lambda e: e.start_ns)
    return runs[1:-1]


def end(s) -> float:
    return s.start_ns + s.dur_ns


def next_after(spans: list, starts: list, at: float):
    i = bisect.bisect_left(starts, at)
    return spans[i] if i < len(spans) else None


def reduce(device_events: list, spans: list) -> dict:
    """Per whole execution of the step program, the three gaps in ms
    (mean and 95th percentile, nearest rank), their sum against
    `step_transfer_ms` taken over the same steps, and how many
    executions lie outside their step's brackets (0 on one clock)."""
    loop = host_spans.loop_line(spans)
    named = {n: [s for s in loop if s.name == n]
             for n in ("engine.dispatch", "engine.wait", "engine.fetch")}
    starts = {n: [s.start_ns for s in v] for n, v in named.items()}
    rows, outside, unmatched = [], 0, 0
    for run in whole_executions(device_events):
        i = bisect.bisect_right(starts["engine.dispatch"], run.start_ns) - 1
        dispatch = named["engine.dispatch"][i] if i >= 0 else None
        fetch = dispatch and next_after(named["engine.fetch"],
                                        starts["engine.fetch"],
                                        end(dispatch))
        wait = fetch and next_after(named["engine.wait"],
                                    starts["engine.wait"], fetch.start_ns)
        if not wait or end(wait) > end(fetch) \
                or wait.stats.get("step") != dispatch.stats.get("step"):
            unmatched += 1
            continue
        if end(run) > end(wait):
            outside += 1
        rows.append({
            "launch_to_start_ms": (run.start_ns - dispatch.start_ns) / 1e6,
            "end_to_ready_ms": (end(wait) - end(run)) / 1e6,
            "fetch_after_ready_ms": (end(fetch) - end(wait)) / 1e6,
            "around_ms": (end(fetch) - dispatch.start_ns) / 1e6,
            "device_ms": run.dur_ns / 1e6})
    out = {"executions": len(rows), "unmatched": unmatched,
           "outside_their_brackets": outside}
    if not rows:
        return out
    for gap in GAPS:
        values = [r[gap] for r in rows]
        out[gap] = {"mean": statistics.fmean(values),
                    "p95": percentile(values, 95), "min": min(values)}
    out["step_device_ms"] = statistics.fmean(r["device_ms"] for r in rows)
    out["step_transfer_ms"] = (statistics.fmean(r["around_ms"] for r in rows)
                               - out["step_device_ms"])
    out["sum_of_gaps_ms"] = sum(out[gap]["mean"] for gap in GAPS)
    return out


def main(argv) -> int:
    path = trace_reduce.find_xplane(argv[1])
    out = reduce(trace_reduce.load_xplane(path),
                 host_spans.load_annotations(path))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
