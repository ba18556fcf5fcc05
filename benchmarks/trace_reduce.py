"""From a profiler trace to numbers. The reduction lives here so that
every PR computes the same number in the same way.

A trace is a list of `Event`s; `load_xplane` reads one from the
`.xplane.pb` the JAX profiler writes, with nothing but JAX. On a TPU
plane (`/device:TPU:<n>`) the line "XLA Ops" holds one event per
operation the device ran and "XLA Modules" one per execution of a
compiled program; the tests build the same shape by hand.
"""

from __future__ import annotations

import glob
import os
import statistics
from collections import defaultdict
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, device_prefix: str = DEVICE_PREFIX) -> list:
    """Events of the device planes' op and module lines."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith(device_prefix):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, short_name(ev.name),
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction, `%fusion.3 =
    bf16[...] fusion(...)`: keep the result's name, and for a custom call
    its target, which is how a Pallas kernel shows."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    head = head.lstrip("%")
    for key in ("custom_call_target=", "kernel_name="):
        if key in rest:
            target = rest.split(key, 1)[1].split(",")[0].strip('"} ')
            return f"{head} {target}"[:120]
    return head[:120]


def describe_xplane(path: str, limit: int = 8) -> list:
    """Planes, lines and a few event names: for looking at a trace by
    hand before trusting a reduction of it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name[:300] for e in events[:5000]})[:limit]
            stats = [[str(k), str(v)[:200]] for k, v in
                     (events[0].stats if events else [])][:20]
            rows.append({"plane": plane.name, "line": line.name,
                         "events": len(events), "names": names,
                         "first_event_stats": stats})
    return rows


def union_ns(intervals) -> float:
    """Total length covered by (start, end) intervals that may overlap."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _by_plane(events, line):
    planes = defaultdict(list)
    for ev in events:
        if ev.line == line:
            planes[ev.plane].append(ev)
    return planes


def busy_seconds(events) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    device planes present."""
    planes = _by_plane(events, OPS_LINE)
    if not planes:
        return 0.0
    per = [union_ns((e.start_ns, e.start_ns + e.dur_ns) for e in evs)
           for evs in planes.values()]
    return sum(per) / len(per) / 1e9


def extent_seconds(events) -> float:
    """From the first operation's start to the last one's end, over all
    device planes, on the trace's own clock: the window that
    `busy_seconds` is a share of. Both come from the same events, so
    the share cannot pass 1 and needs no clamp; idle time before the
    first operation and after the last is not seen."""
    ops = [e for e in events if e.line == OPS_LINE]
    if not ops:
        return 0.0
    return (max(e.start_ns + e.dur_ns for e in ops)
            - min(e.start_ns for e in ops)) / 1e9


def _sums(events, line) -> dict:
    """{name: {"count", "total_s", "median_s"}} over one line, all
    device planes together."""
    durs = defaultdict(list)
    for ev in events:
        if ev.line == line:
            durs[ev.name].append(ev.dur_ns / 1e9)
    return {name: {"count": len(d), "total_s": sum(d),
                   "median_s": statistics.median(d)}
            for name, d in durs.items()}


def program_sums(events) -> dict:
    return _sums(events, MODULES_LINE)


def op_sums(events) -> dict:
    return _sums(events, OPS_LINE)


def matching(sums: dict, *needles: str) -> dict:
    """The entries whose name contains any of the needles."""
    return {k: v for k, v in sums.items() if any(n in k for n in needles)}


def family(name: str) -> str:
    """`_step_fn.26 tpu_custom_call` -> `_step_fn.* tpu_custom_call`,
    `copy.202` -> `copy.*`: an operation that every layer runs shows
    under one numbered name a layer."""
    head, sep, target = name.partition(" ")
    stem, dot, number = head.rpartition(".")
    if dot and number.isdigit():
        head = stem + ".*"
    return head + sep + target


def top_ops(events, n: int = 10, merge=family) -> list:
    """The operations that took most device time, by family; with
    `merge=str`, one by one."""
    total = defaultdict(float)
    for name, v in op_sums(events).items():
        total[merge(name)] += v["total_s"]
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[name, s] for name, s in ranked[:n]]


def idle_gaps(events, n: int = 10) -> list:
    """The longest idle stretches of the first device, named by the
    operations before and after each (the program has no host spans yet
    to name them by what the host was doing), summed by name."""
    planes = _by_plane(events, OPS_LINE)
    if not planes:
        return []
    evs = sorted(planes[sorted(planes)[0]], key=lambda e: e.start_ns)
    total, cur_end, last = defaultdict(float), None, None
    for ev in evs:
        if cur_end is not None and ev.start_ns > cur_end:
            total[f"after {last} before {ev.name}"] += (
                ev.start_ns - cur_end) / 1e9
        if cur_end is None or ev.start_ns + ev.dur_ns > cur_end:
            cur_end, last = ev.start_ns + ev.dur_ns, ev.name
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[name, s] for name, s in ranked[:n]]


if __name__ == "__main__":      # look at a kept trace by hand
    import json
    import sys
    for row in describe_xplane(find_xplane(sys.argv[1]),
                               int(sys.argv[2]) if len(sys.argv) > 2 else 8):
        print(json.dumps(row))
