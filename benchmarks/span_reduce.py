"""From the program's span ring to the host's share of a serving step.

The program (`paddle_tpu/profiler/profiler.py`) keeps one bounded ring
of finished spans, on its own epoch-anchored clock in microseconds:
`{"name", "ts", "dur", "tid", "args"}`. The serving loop writes one span
a layer boundary (`engine.step` and its seven children, `frontdoor.*`
around them, `obs.scrape` on the handler's thread) and one `request`
record a finished request. The ring is module state of the program, so
it is still there when the runner has freed the engine; a reader is
plain Python in the same process and reads it after the run.

The window on the ring's clock: the closed-loop runner scrapes
`/metrics` immediately before it opens the window, again when it has
closed it, and once more for the compile count. So the window is
`window_s` seconds from the end of the first of the ring's last three
`obs.scrape` spans: the convention of the counter metrics, which are
deltas between those scrapes. The traced slice is the last
`trace_window_s` seconds of it. All means are over the steps whose
`engine.step` starts in the interval, and all sums are of spans on the
engine loop's thread. On the device's side of the slice only whole
cycles of the step program count (`device_cycles`).

A program without the spans (the parent of the PR that added them)
leaves the ring empty: every reader then returns None and says why.
"""

from __future__ import annotations

import bisect
import math
import statistics
from collections import defaultdict

from benchmarks import trace_reduce
from benchmarks.common import log as say

STEP = "engine.step"
CHILDREN = ("engine.plan", "engine.flush", "engine.pack", "engine.dispatch",
            "engine.fetch", "engine.sample", "engine.publish")
SELF = "engine.step (self)"
# the spans each layer's `host_step_ms.<layer>` sums; the time between
# `engine.dispatch`'s start and `engine.fetch`'s end is the device's
# step and the transfers around it, and is `step_transfer_ms`'s
LAYERS = {
    "frontdoor": ("frontdoor.control", "frontdoor.finish",
                  "frontdoor.snapshot", "frontdoor.wait"),
    "scheduler": ("engine.plan",),
    "cache": ("engine.flush",),
    "step": ("engine.pack", "engine.sample", "engine.publish", SELF),
}
SCRAPE = "obs.scrape"
MIN_STEPS = 20


def ring() -> list:
    """The program's ring as it stands."""
    from paddle_tpu.profiler.profiler import get_events
    return get_events()


def percentile(values, q: float) -> float:
    """Nearest rank, a value that was measured: the rule of
    `runners/serve_closed.percentile`."""
    ordered = sorted(values)
    return ordered[max(1, math.ceil(q / 100.0 * len(ordered))) - 1]


def end(ev: dict) -> float:
    return ev["ts"] + ev["dur"]


def last_scrapes(events: list) -> list:
    return sorted((e for e in events if e["name"] == SCRAPE),
                  key=lambda e: e["ts"])[-3:]


def find_window(events: list, window_s: float):
    """(start, end) of the window in the ring's microseconds, or
    (None, why)."""
    scrapes = last_scrapes(events)
    if len(scrapes) < 3:
        return None, (f"the ring holds {len(scrapes)} {SCRAPE} spans, and "
                      "the window lies between the first two of three")
    start = end(scrapes[0])
    stop = start + window_s * 1e6
    if scrapes[1]["ts"] < stop:
        return None, (f"the second scrape began {window_s} s - "
                      f"{(stop - scrapes[1]['ts']) / 1e6} s after the first "
                      "ended: these are not the window's scrapes")
    return (start, stop), None


def loop_spans(events: list) -> list:
    """The spans of the engine loop's thread (the one that ran the most
    steps: a process may hold an older engine's), by start."""
    tids = defaultdict(int)
    for e in events:
        if e["name"] == STEP:
            tids[e["tid"]] += 1
    if not tids:
        return []
    tid = max(tids, key=tids.get)
    return sorted((e for e in events
                   if e["tid"] == tid and e["name"] not in (SCRAPE, "request")),
                  key=lambda e: e["ts"])


class Steps:
    """The steps that start in [t0, t1), each with its children (found
    by containment, so a step number that restarts after the warm-up's
    reset does no harm), and the loop's other spans in the interval."""

    def __init__(self, spans: list, t0: float, t1: float):
        self.steps = [e for e in spans
                      if e["name"] == STEP and t0 <= e["ts"] < t1]
        starts = [s["ts"] for s in self.steps]
        self.children = [[] for _ in self.steps]
        self.others = []
        for e in spans:
            if e["name"] in CHILDREN:
                i = bisect.bisect_right(starts, e["ts"]) - 1
                if i >= 0 and e["ts"] < end(self.steps[i]):
                    self.children[i].append(e)
            elif e["name"] != STEP and t0 <= e["ts"] < t1:
                self.others.append(e)
        self.durs = self._durations()

    def __len__(self) -> int:
        return len(self.steps)

    def _durations(self) -> dict:
        """{span name: [dur in ms, ...]}; a step's self time is its
        span less what its children cover."""
        out = defaultdict(list)
        for st, kids in zip(self.steps, self.children):
            out[STEP].append(st["dur"] / 1e3)
            out[SELF].append((st["dur"] - sum(k["dur"] for k in kids)) / 1e3)
            for k in kids:
                out[k["name"]].append(k["dur"] / 1e3)
        for e in self.others:
            out[e["name"]].append(e["dur"] / 1e3)
        return out

    def cycle_ms(self) -> float:
        """Mean time from one step's start to the next one's."""
        return ((self.steps[-1]["ts"] - self.steps[0]["ts"]) / 1e3
                / (len(self.steps) - 1))

    def per_step_ms(self, names) -> float:
        return (sum(sum(self.durs.get(n, ())) for n in names)
                / len(self.steps))

    def dispatch_to_fetch_ms(self):
        """Mean of `engine.dispatch`'s start to `engine.fetch`'s end:
        uploads, launch, the device's step and the logits' download."""
        spans = []
        for kids in self.children:
            by = {k["name"]: k for k in kids}
            if "engine.dispatch" in by and "engine.fetch" in by:
                spans.append((end(by["engine.fetch"])
                              - by["engine.dispatch"]["ts"]) / 1e3)
        return statistics.fmean(spans) if spans else None


def span_table(steps: Steps) -> list:
    """Rows (name, count, mean ms, p95 ms, share of the cycle %)."""
    total = (len(steps) - 1) * steps.cycle_ms()
    rows = []
    for name, d in sorted(steps.durs.items(),
                          key=lambda kv: -sum(kv[1])):
        rows.append((name, len(d), statistics.fmean(d), percentile(d, 95),
                     100.0 * sum(d) / total))
    return rows


def counts(ev: dict) -> dict:
    """A span's counts: its args without the step number."""
    return {k: v for k, v in ev["args"].items() if k != "step"}


def stalled(spans: list, steps: Steps, factor: float = 3.0) -> list:
    """Every step whose cycle (its start to the next one's) is over
    `factor` times the median, with the loop's spans in that cycle."""
    starts = [s["ts"] for s in steps.steps]
    cycles = [b - a for a, b in zip(starts, starts[1:])]
    if not cycles:
        return []
    limit = factor * statistics.median(cycles)
    out = []
    for st, a, c in zip(steps.steps, starts, cycles):
        if c > limit:
            out.append({"step": st["args"].get("step"), "cycle_ms": c / 1e3,
                        "spans": [(e["name"], (e["ts"] - a) / 1e3,
                                   e["dur"] / 1e3, counts(e)) for e in spans
                                  if a <= e["ts"] < a + c]})
    return out


def request_split(events: list, t0: float, t1: float) -> dict:
    """The time to first token of the requests that arrived in [t0, t1)
    split at each boundary, in ms: {phase: [..]}, and the records."""
    recs = [e["args"] for e in events if e["name"] == "request"
            and t0 <= e["args"]["arrival"] < t1
            and e["args"]["admitted"] is not None]
    phases = (("frontdoor_wait", "arrival", "enqueued"),
              ("scheduler_wait", "enqueued", "admitted"),
              ("prefill", "admitted", "first_token"),
              ("first_write", "first_token", "first_write"),
              ("queue_wait", "arrival", "admitted"))
    out = {name: [(r[b] - r[a]) / 1e3 for r in recs
                  if r[a] is not None and r[b] is not None]
           for name, a, b in phases}
    return {"phases": out, "records": recs}


def device_cycles(trace):
    """(idle ms a cycle, device ms an execution, cycles) of the first
    device over the WHOLE cycles of the step program that the slice
    holds: from the second execution's start to the last one's. The
    profiler starts and stops mid-cycle, so the first and the last
    execution it shows may be cut short (a slice of 28 cycles showed 29
    executions, one of no length), and a mean over all of them, or an
    idle time divided by their number, is off by a cycle's share."""
    found = trace_reduce.matching(trace_reduce.program_sums(trace or ()),
                                  "step_fn")
    if not found:
        return None
    name = max(found, key=lambda k: found[k]["total_s"])
    plane = min(e.plane for e in trace)
    runs = sorted((e for e in trace if e.plane == plane and e.name == name
                   and e.line == trace_reduce.MODULES_LINE),
                  key=lambda e: e.start_ns)
    if len(runs) < 4:
        return None
    t0, t1 = runs[1].start_ns, runs[-1].start_ns
    busy = trace_reduce.union_ns(
        (max(e.start_ns, t0), min(e.start_ns + e.dur_ns, t1))
        for e in trace if e.plane == plane
        and e.line == trace_reduce.OPS_LINE
        and e.start_ns < t1 and e.start_ns + e.dur_ns > t0)
    whole = runs[1:-1]
    return ((t1 - t0 - busy) / len(whole) / 1e6,
            statistics.fmean(e.dur_ns for e in whole) / 1e6, len(whole))


def reduce(events: list, observed: dict) -> dict:
    """Every number the readers return, or {"why": ...} when the ring
    cannot give them. Prints what the numbers hide."""
    window, why = find_window(events, observed["window_s"])
    if window is None:
        return {"why": why}
    t0, t1 = window
    spans = loop_spans(events)
    steps = Steps(spans, t0, t1)
    if len(steps) < MIN_STEPS:
        return {"why": f"{len(steps)} steps started in the window, under "
                       f"{MIN_STEPS}"}
    out = {f"host_step_ms.{layer}": steps.per_step_ms(names)
           for layer, names in LAYERS.items()}
    say(f"host spans over the window: {len(steps)} steps, cycle "
        f"{steps.cycle_ms()} ms")
    for row in span_table(steps):
        say("  span %-20s count %5d mean %9.3f ms p95 %9.3f ms "
            "share of the cycle %6.2f%%" % row)
    say(f"  span {SCRAPE} (the handler's thread, in no sum; the last three "
        "delimit the window): "
        + ", ".join(f"{e['dur'] / 1e3} ms {e['args'].get('bytes')} bytes"
                    for e in last_scrapes(events)))
    for s in stalled(spans, steps):
        say(f"  stalled step {s['step']}: cycle {s['cycle_ms']} ms, spans "
            "(name, ms after the step's start, ms long, counts): "
            + ", ".join("%s %.1f %.1f %s" % x for x in s["spans"]))

    split = request_split(events, t0, t1)
    for name, values in split["phases"].items():
        if values:
            say(f"  requests {name}: n {len(values)} median "
                f"{statistics.median(values)} ms p95 "
                f"{percentile(values, 95)} ms")
    recs = split["records"]
    if recs:
        say("  chunk steps before the first token: median "
            f"{statistics.median(r['chunk_steps'] for r in recs)}, most "
            f"{max(r['chunk_steps'] for r in recs)}")
        slow = sorted((r for r in recs if r["first_token"] is not None),
                      key=lambda r: r["first_token"] - r["arrival"],
                      reverse=True)[:3]
        for r in slow:
            say(f"  slowest to a first token: req {r['req']} prompt "
                f"{r['prompt']} cached {r['cached']} admitted in step "
                f"{r['admit_step']} first token in step "
                f"{r['first_token_step']} after {r['chunk_steps']} chunk "
                f"steps, {(r['first_token'] - r['arrival']) / 1e3} ms; "
                f"preemptions {r['preemptions']}, finished "
                f"{r['reason']!r} after "
                f"{(r['finished'] - r['arrival']) / 1e3} ms")
    waits = split["phases"]["queue_wait"]
    out["queue_wait_p95_ms"] = percentile(waits, 95) if waits else None

    # the traced slice: what the device's clock can be set against
    cycles = device_cycles(observed.get("trace"))
    if cycles is None or not observed.get("trace_window_s"):
        out["why_no_slice"] = ("the trace holds under four executions of "
                               "the step program")
        return out
    idle, device_ms, whole = cycles
    sl = Steps(spans, t1 - observed["trace_window_s"] * 1e6, t1)
    around = sl.dispatch_to_fetch_ms() if len(sl) else None
    if around is None:
        out["why_no_slice"] = "no step started in the traced slice"
        return out
    out["step_transfer_ms"] = around - device_ms
    named = sum(sl.per_step_ms(names) for names in LAYERS.values())
    out["idle_unnamed_ms"] = idle - named - out["step_transfer_ms"]
    say(f"traced slice: {len(sl)} steps started in it and the device ran "
        f"{whole} whole cycles; idle {idle} ms a cycle = host spans {named} ms "
        f"+ transfers around the device's step {out['step_transfer_ms']} ms "
        f"(dispatch to fetch {around} ms less {device_ms} ms on the device) "
        f"+ unnamed {out['idle_unnamed_ms']} ms")
    return out


def metric(observed: dict, name: str):
    """What `layer_metrics/<name>.py` returns. The ring is reduced once
    a run and kept on the harness's own `observed`."""
    if "_span_reduce" not in observed:
        observed["_span_reduce"] = reduce(ring(), observed)
        for key in ("why", "why_no_slice"):
            if key in observed["_span_reduce"]:
                say(f"span_reduce: {observed['_span_reduce'][key]}")
    return observed["_span_reduce"].get(name)
