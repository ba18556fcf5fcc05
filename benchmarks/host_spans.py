"""What the host was doing while the device was idle, from one kept trace.

    python3 benchmarks/run.py --workload <cell> ... --trace 1 --keep-trace 1
    python3 benchmarks/host_spans.py .bench_trace/<cell>

The program's spans (`paddle_tpu.profiler.profiler.annotate`) are also
`jax.profiler.TraceAnnotation`s, so a profiler session holds them on the
`/host:CPU` plane, on the line of the thread that opened each, on the
same clock as the device's operations. `span_reduce.py` sets the ring's
means against the trace's totals; this sets span against gap, one by
one: the true overlap. `attribute` is what a later change to
`trace_reduce.idle_gaps` can call to name a gap by what the host was
doing in it, where today it names the operations on either side.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import NamedTuple

if __package__ in (None, ""):    # run by hand, from anywhere
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import trace_reduce  # noqa: E402

HOST_PREFIX = "/host:"
SPAN_PREFIXES = ("engine.", "frontdoor.", "obs.")
UNNAMED = "unnamed"


class Span(NamedTuple):
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


def load_annotations(xplane_path: str) -> list:
    """The host plane's events named `engine.*`, `frontdoor.*` or
    `obs.*`, with their stats (`step`, where the span carries one)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    out.append(Span(line.name, ev.name, float(ev.start_ns),
                                    float(ev.duration_ns), dict(ev.stats)))
    return out


def loop_line(spans: list) -> list:
    """The spans of the thread that opened the most `engine.step`s: the
    engine loop's. Another thread's spans (`obs.scrape`) overlap them
    and explain no gap of a loop that does not wait for them."""
    count = defaultdict(int)
    for s in spans:
        if s.name == "engine.step":
            count[s.line] += 1
    if not count:
        return []
    line = max(count, key=count.get)
    return sorted((s for s in spans if s.line == line),
                  key=lambda s: (s.start_ns, -s.dur_ns))


def innermost(spans: list) -> list:
    """One thread's nested spans (by start, outer first) as disjoint
    (name, start, end) pieces, each named by the innermost span that
    covers it; `engine.step`'s own time is `engine.step (self)`."""
    pieces, stack = [], []       # the open spans, outermost first
    at = 0.0                     # all before `at` is given out

    def give(upto: float) -> None:
        nonlocal at
        if stack and upto > at:
            top = stack[-1].name
            pieces.append((top + (" (self)" if top == "engine.step" else ""),
                           at, upto))
        at = max(at, upto)

    for s in spans:
        while stack and stack[-1].start_ns + stack[-1].dur_ns <= s.start_ns:
            give(stack[-1].start_ns + stack[-1].dur_ns)
            stack.pop()
        give(s.start_ns)
        stack.append(s)
    while stack:
        give(stack[-1].start_ns + stack[-1].dur_ns)
        stack.pop()
    return pieces


def split(gap: tuple, pieces: list, into: dict) -> None:
    """Add the gap's nanoseconds to `into`, by the piece that covers
    each; what no piece covers goes under `unnamed`."""
    a, b = gap
    left = b - a
    for name, p0, p1 in pieces:
        if p1 <= a:
            continue
        if p0 >= b:
            break
        part = min(b, p1) - max(a, p0)
        into[name] += part
        left -= part
    into[UNNAMED] += left


def attribute(device_events: list, spans: list) -> dict:
    """Each idle gap of the first device, split among the host spans
    that cover it and summed by span name, in seconds; the remainder
    under `unnamed`. The idle time before the first and after the last
    operation, which the trace's extent cannot see, is bracketed by the
    first and last span and reported apart."""
    planes = defaultdict(list)
    for e in device_events:
        if e.line == trace_reduce.OPS_LINE:
            planes[e.plane].append(e)
    pieces = innermost(loop_line(spans))
    if not planes or not pieces:
        return {}
    ops = sorted(planes[sorted(planes)[0]], key=lambda e: e.start_ns)
    gaps, before, after = (defaultdict(float) for _ in range(3))
    cur_end = None
    for e in ops:
        if cur_end is not None and e.start_ns > cur_end:
            split((cur_end, e.start_ns), pieces, gaps)
        cur_end = max(cur_end or 0.0, e.start_ns + e.dur_ns)
    if pieces[0][1] < ops[0].start_ns:
        split((pieces[0][1], ops[0].start_ns), pieces, before)
    if pieces[-1][2] > cur_end:
        split((cur_end, pieces[-1][2]), pieces, after)

    def seconds(d):
        return {k: v / 1e9 for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1]) if v}
    return {"idle_gaps": seconds(gaps), "before_first_op": seconds(before),
            "after_last_op": seconds(after)}


def main(argv) -> int:
    path = trace_reduce.find_xplane(argv[1])
    device = trace_reduce.load_xplane(path)
    spans = load_annotations(path)
    out = attribute(device, spans)
    steps = trace_reduce.matching(trace_reduce.program_sums(device),
                                  "step_fn")
    n = max((v["count"] for v in steps.values()), default=0)
    out["executions"] = n
    out["extent_s"] = trace_reduce.extent_seconds(device)
    out["busy_s"] = trace_reduce.busy_seconds(device)
    if n:
        out["idle_ms_per_execution"] = {
            k: 1e3 * v / n for k, v in out.get("idle_gaps", {}).items()}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
