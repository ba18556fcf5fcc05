"""From the program's span ring to who held the interpreter around the
device's step, and what the hand-off to the handlers costs.

`span_reduce.py` says how long each span of the engine loop is. Since
the PR that added this file every span also says how long its thread
RAN (`cpu`, the thread's CPU time between the span's two stamps), so
`dur - cpu` is how long the thread was off the CPU: blocked in a call
that waits (the device, a socket, `_work.wait`), or waiting for the
interpreter while another thread (the event loop's, writing the last
step's frames; whichever ran a collection) held it. For the loop's
spans that wait on nothing of their own (`OWN`) it is the wait for the
interpreter; for `engine.dispatch` it is that plus whatever the call
itself blocks on, which the steps whose dispatch nothing of another
thread overlapped tell apart. Three more things are new on the ring:
`engine.wait` inside `engine.fetch` (`block_until_ready` on the step's
picks: what is left of the fetch comes after the device is known to be
done), one `frontdoor.deliver` a hand-over on the event loop's thread
(`step`, `streams`, `frames`, `wake_us`), and the collector's stamps
(`gc_us` on `engine.step`, a `runtime.gc` record a collection of 0.2 ms
or more, under the thread it ran on).

Ring only; the window and its steps are found as `span_reduce` finds
them, means are over the steps that start in the window. A ring without
the new fields (the parent's) gives None for each number and says why.
"""

from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

from benchmarks import span_reduce as sr
from benchmarks.common import log as say

DISPATCH, FETCH, WAIT, SAMPLE = ("engine.dispatch", "engine.fetch",
                                 "engine.wait", "engine.sample")
DELIVER, GC = "frontdoor.deliver", "runtime.gc"
# the loop's spans that wait on nothing of their own: off the CPU in
# one of these, the loop's thread wanted the interpreter and had it not
OWN = ("engine.plan", "engine.flush", "engine.pack", "engine.sample",
       "engine.publish", sr.SELF, "frontdoor.control", "frontdoor.finish",
       "frontdoor.snapshot")
METRICS = ("loop_off_cpu_ms", "dispatch_off_cpu_ms", "fetch_after_ready_ms",
           "deliver_p95_ms")


def off_cpu(ev: dict) -> float:
    return ev["dur"] - ev["cpu"]


def by_name(steps: sr.Steps) -> dict:
    """{span name: [(cpu, off the CPU) in us, ...]} over the window's
    steps and the loop's other spans; a step's own line is its span
    less what its children cover, as `span_reduce` has it."""
    out = defaultdict(list)
    for st, kids in zip(steps.steps, steps.children):
        out[sr.STEP].append((st["cpu"], off_cpu(st)))
        out[sr.SELF].append((st["cpu"] - sum(k["cpu"] for k in kids),
                             off_cpu(st) - sum(off_cpu(k) for k in kids)))
        for k in kids:
            out[k["name"]].append((k["cpu"], off_cpu(k)))
    for e in steps.others:
        if e.get("cpu") is not None:
            out[e["name"]].append((e["cpu"], off_cpu(e)))
    return out


def inside(outer: dict, spans: list, starts: list):
    """The first of `spans` (sorted by start; `starts` their stamps)
    that lies inside `outer`, or None."""
    i = bisect.bisect_left(starts, outer["ts"])
    if i < len(spans) and sr.end(spans[i]) <= sr.end(outer):
        return spans[i]
    return None


class Intervals:
    """Records by start, and whether any of them overlaps a span."""

    def __init__(self, records: list):
        records = sorted(records, key=lambda e: e["ts"])
        self.starts = [e["ts"] for e in records]
        self.ends = []          # the latest end up to each record
        for e in records:
            self.ends.append(max(sr.end(e), self.ends[-1] if self.ends
                                 else sr.end(e)))

    def overlap(self, ev: dict) -> bool:
        i = bisect.bisect_left(self.starts, sr.end(ev))
        return i > 0 and self.ends[i - 1] > ev["ts"]


def deliveries(events: list, spans: list, t0: float, t1: float) -> list:
    """(record, ms from its step's `engine.sample` opening to its end)
    for the window's hand-overs that an engine step caused. Step numbers
    restart at the warm-up's reset: the sample meant is the last of that
    number to open before the record."""
    samples = defaultdict(list)
    for e in spans:
        if e["name"] == SAMPLE:
            samples[e["args"]["step"]].append(e["ts"])
    out = []
    for d in events:
        if d["name"] != DELIVER or not t0 <= d["ts"] < t1:
            continue
        opened = samples.get(d["args"]["step"], ())
        i = bisect.bisect_right(opened, d["ts"])
        if i:
            out.append((d, (sr.end(d) - opened[i - 1]) / 1e3))
    return out


def reduce(events: list, observed: dict) -> dict:
    """{metric: value} and {"why": {metric: reason}} for what the ring
    cannot give. Prints what the four numbers hide."""
    window, reason = sr.find_window(events, observed["window_s"])
    if window is None:
        return {"why": dict.fromkeys(METRICS, reason)}
    t0, t1 = window
    spans = sr.loop_spans(events)
    steps = sr.Steps(spans, t0, t1)
    n = len(steps)
    if n < sr.MIN_STEPS:
        return {"why": dict.fromkeys(
            METRICS, f"{n} steps started in the window, under "
                     f"{sr.MIN_STEPS}")}
    out, why = {}, {}
    tid = steps.steps[0]["tid"]

    if any(e.get("cpu") is None for e in steps.steps):
        why["loop_off_cpu_ms"] = why["dispatch_off_cpu_ms"] = (
            "the ring's spans carry no `cpu`")
    else:
        table = by_name(steps)
        out["loop_off_cpu_ms"] = sum(
            off for name in OWN for _, off in table.get(name, ())) / n / 1e3
        out["dispatch_off_cpu_ms"] = statistics.fmean(
            off for _, off in table[DISPATCH]) / 1e3
        say(f"thread CPU time over the window's {n} steps, ms a step "
            "(cpu: the loop's thread ran; off: it was blocked or waited "
            "for the interpreter):")
        for name, rows in sorted(table.items(),
                                 key=lambda kv: -sum(c + o for c, o in kv[1])):
            say("  span %-20s count %5d cpu %8.3f off %8.3f" % (
                name, len(rows), sum(c for c, _ in rows) / n / 1e3,
                sum(o for _, o in rows) / n / 1e3))
        # the launch alone: a dispatch that no hand-over's delivery and
        # no collection of another thread overlapped
        theirs = Intervals([e for e in events if e["name"] == DELIVER
                            or (e["name"] == GC and e["tid"] != tid)])
        launches = [k for kids in steps.children for k in kids
                    if k["name"] == DISPATCH]
        alone = [k for k in launches if not theirs.overlap(k)]
        for label, group in (("alone", alone), ("all", launches)):
            say(f"  {DISPATCH} {label}: n {len(group)}" + (
                f" mean dur {statistics.fmean(k['dur'] for k in group) / 1e3}"
                " ms, off the CPU "
                f"{statistics.fmean(map(off_cpu, group)) / 1e3} ms"
                if group else ""))
        ticks = [c for rows in table.values() for c, _ in rows if c > 0]
        say(f"  the smallest `cpu` above zero: {min(ticks) / 1e3} ms (the "
            "thread clock's grain on this machine: below it one span's "
            "`cpu` says nothing, the mean over the window's steps does)")
        gc_us = [st["args"].get("gc_us", 0.0) for st in steps.steps]
        say(f"  collections: {sum(gc_us) / n / 1e3} ms a step, the most in "
            f"one step {max(gc_us) / 1e3} ms")

    waits = [e for e in spans if e["name"] == WAIT]
    starts = [e["ts"] for e in waits]
    pairs = [(k, inside(k, waits, starts)) for kids in steps.children
             for k in kids if k["name"] == FETCH]
    pairs = [(f, w) for f, w in pairs if w is not None]
    if not pairs:
        why["fetch_after_ready_ms"] = f"the ring holds no {WAIT} span"
    else:
        out["fetch_after_ready_ms"] = statistics.fmean(
            f["dur"] - w["dur"] for f, w in pairs) / 1e3
        say(f"  {FETCH} {statistics.fmean(f['dur'] for f, _ in pairs) / 1e3}"
            f" ms = {WAIT} "
            f"{statistics.fmean(w['dur'] for _, w in pairs) / 1e3} ms + "
            f"after the device was done {out['fetch_after_ready_ms']} ms "
            f"(p95 {sr.percentile([f['dur'] - w['dur'] for f, w in pairs], 95) / 1e3}"
            f" ms), over {len(pairs)} steps")

    handed = deliveries(events, spans, t0, t1)
    if not handed:
        why["deliver_p95_ms"] = (f"the ring holds no {DELIVER} record of a "
                                 "step in the window")
    else:
        out["deliver_p95_ms"] = sr.percentile([ms for _, ms in handed], 95)
        recs = [d for d, _ in handed]
        wake = [d["args"]["wake_us"] / 1e3 for d in recs]
        pushed = sum(e["args"].get("emitted", 0) + e["args"].get("closed", 0)
                     for e in spans if t0 <= e["ts"] < t1)
        say(f"  {DELIVER}: {len(recs)} hand-overs, "
            f"{sum(d['args']['frames'] for d in recs)} frames written (the "
            f"loop's spans of the window pushed {pushed}: `emitted` + "
            f"`closed`) to "
            f"{statistics.fmean(d['args']['streams'] for d in recs)} streams "
            f"each; from `engine.sample`'s start to the last frame written "
            f"median {statistics.median(ms for _, ms in handed)} ms p95 "
            f"{out['deliver_p95_ms']} ms; wake-up median "
            f"{statistics.median(wake)} ms p95 {sr.percentile(wake, 95)} ms; "
            f"the delivery itself median "
            f"{statistics.median(d['dur'] for d in recs) / 1e3} ms, mean "
            f"{statistics.fmean(d['dur'] for d in recs) / 1e3} ms, of it on "
            f"the CPU {statistics.fmean(d['cpu'] for d in recs) / 1e3} ms")

    told = sorted((e for e in events if e["name"] in (DELIVER, GC)),
                  key=lambda e: e["ts"])
    for e in told:
        if e["name"] == GC and t0 <= e["ts"] < t1:
            say(f"  {GC} {(e['ts'] - t0) / 1e3} ms into the window: "
                f"{e['dur'] / 1e3} ms, generation "
                f"{e['args']['generation']}, collected "
                f"{e['args']['collected']}, on "
                f"{'the loop' if e['tid'] == tid else 'another'}'s thread")
    for s in sr.stalled(told, steps):
        say(f"  stalled step {s['step']}: cycle {s['cycle_ms']} ms; other "
            "threads' records in it (name, ms after the step's start, ms "
            "long, counts): "
            + (", ".join("%s %.1f %.1f %s" % x for x in s["spans"])
               or "none"))
    out["why"] = why
    return out


def metric(observed: dict, name: str):
    """What `layer_metrics/<name>.py` returns. The ring is reduced once
    a run and kept on the harness's own `observed`."""
    if "_handoff_reduce" not in observed:
        observed["_handoff_reduce"] = reduce(sr.ring(), observed)
    got = observed["_handoff_reduce"]
    if name in got["why"]:
        say(f"handoff_reduce: no {name}: {got['why'][name]}")
    return got.get(name)
