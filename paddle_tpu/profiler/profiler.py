"""Event profiler + device-trace wrappers.

Host tier ≈ reference RecordEvent/EnableProfiler
(/root/reference/paddle/fluid/platform/profiler.h:72,117-126; tables
printed by DisableProfiler with a sort key). Device tier wraps
jax.profiler (≈ CUPTI device tracer, platform/device_tracer.h:39) — the
captured trace dir is TensorBoard/perfetto-loadable.

ONE span store: a process-wide bounded ring (`_events`). `annotate`
spans — the serving loop's layer boundaries (OBSERVABILITY.md "Host
spans") and the request tracer's `request` records — land in it
ALWAYS, so it outlives the engine and front end that wrote it;
`RecordEvent` spans land in it only between `start_profiler` and
`stop_profiler` (which clear it first: the table is of that interval).
"""

from __future__ import annotations

import contextlib
import functools
import gc
import json
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax

from paddle_tpu.utils.log import vlog

# The busiest serving cell's window and its tail (some 50 s) fit nearly
# three times over: a step leaves 12 spans (`engine.step`, its seven
# children, `engine.wait`, `frontdoor.control`, `.finish`, `.deliver`)
# and two steps in three a request record, 570 entries a second at the
# 45 steps a second of a 22.2 ms cycle (PERF.md, PR 32), so the ring
# reaches back 143 s (at 65,536 it was 115; PR 30: at 16,384 it no
# longer reached back over a 40 s window and its tail); at 50 idle
# `frontdoor.wait` spans a second it reaches back 27 minutes
RING_SPANS = 81920
# re-entrant: a collection can start on a thread that holds the lock
# (`get_events` builds a list), and its callback files a record
_lock = threading.RLock()
# completed spans: name/ts/dur/cpu/tid (us) and args
_events: Deque[dict] = deque(maxlen=RING_SPANS)   # guarded-by: _lock
_enabled = False
_trace_dir: Optional[str] = None
# Wall-clock anchor for the monotonic counter: timestamps are epoch-based
# microseconds so profiles from different processes merge on a common
# timeline (tools/timeline.py multi-trainer merge needs comparable ts).
_EPOCH_NS = time.time_ns() - time.perf_counter_ns()


def now_us() -> float:
    """Epoch-anchored monotonic microseconds — the shared timestamp
    base for host spans AND the request tracer (obs/tracing.py), so
    their Chrome traces merge on one timeline."""
    return (_EPOCH_NS + time.perf_counter_ns()) / 1e3


# The thread's CPU clock is a system call (0.24 us here, 6 us on the
# machine with the chip, where it also ticks only every 10 ms: PERF.md,
# PR 35), and a span's opening usually follows the closing of the one
# before within a few microseconds: a reading under CPU_REUSE_US old is
# given again, so a step's thirteen spans read the clock some dozen
# times, not twenty-six, and a span's `cpu` is off by that much at most
CPU_REUSE_US = 20.0
_cpu_read = threading.local()   # .last: (now_us, ns) of the thread's reading


def _thread_cpu_ns(at_us: float) -> int:
    last = getattr(_cpu_read, "last", None)
    if last is not None and at_us - last[0] < CPU_REUSE_US:
        return last[1]
    ns = time.thread_time_ns()
    _cpu_read.last = (at_us, ns)
    return ns


def record(name: str, ts: float, dur: float, cpu: Optional[float] = None,
           **args) -> None:
    """Append one finished span (or record: `obs/tracing.py` files a
    `request` here when it finishes) to the ring, stamps on `now_us`,
    under the calling thread's id. `cpu` is the microseconds of CPU
    time the span's thread got between the two stamps (None for a
    record that is no stretch of one thread): `dur - cpu` is how long
    the thread was off the CPU, blocked in a call that waits or
    waiting for the interpreter."""
    ev = {"name": name, "ts": ts, "dur": dur, "cpu": cpu,
          "tid": threading.get_native_id(), "args": args}
    with _lock:
        _events.append(ev)


# a collection stops every thread, whichever one ran it: its
# microseconds go to ONE sum, which only grows (collections never
# overlap, so the callback is its one writer) and of which each engine
# takes what is new as its step closes (`gc_us`)
GC_RECORD_US = 200.0
_gc_start_ns = 0
_gc_total_us = 0.0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_start_ns, _gc_total_us
    if phase == "start":
        _gc_start_ns = time.perf_counter_ns()
        return
    dur = (time.perf_counter_ns() - _gc_start_ns) / 1e3
    _gc_total_us += dur
    if dur >= GC_RECORD_US:
        record("runtime.gc", (_EPOCH_NS + _gc_start_ns) / 1e3, dur,
               generation=info["generation"], collected=info["collected"])


def watch_gc() -> None:
    """Put the collector's stamps on the ring (`ServeEngine` calls this
    when it is built; once a process, however often it is called)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_total_us() -> float:
    """Microseconds of every collection since `watch_gc`."""
    return _gc_total_us


class RecordEvent:
    """RAII host-side span (≈ platform/profiler.h:72 RecordEvent).

    Usable as a context manager. Spans are recorded only while the
    profiler is enabled (between start_profiler and stop_profiler) —
    matching the reference's g_state gate.
    """

    def __init__(self, name: str):
        self.name = name
        self._start = 0.0

    def __enter__(self):
        self._start = now_us()
        return self

    def __exit__(self, *exc):
        if _enabled:
            record(self.name, self._start, now_us() - self._start)
        return False


record_event = RecordEvent


def record_function(name: Optional[str] = None):
    """Decorator wrapping a function body in a RecordEvent span."""

    def deco(fn):
        ev_name = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with RecordEvent(ev_name):
                return fn(*args, **kwargs)

        return wrapped

    return deco


class annotate:
    """A span at a layer boundary, written twice. To the profiler's own
    trace (`jax.profiler.TraceAnnotation(name, **args)`: on the
    timeline of the device's operations whenever a `jax.profiler`
    session is on, a flag test when none is), and ALWAYS to the ring,
    on `now_us`: two readings of the clock and of the thread's CPU
    time (`_thread_cpu_ns`), `ts` when it opens, `dur` and `cpu` when
    it closes, all readable afterwards. `args` are what is known when
    it opens; `set()` adds the counts known only when it closes (those
    reach the ring's entry alone). `discard()` keeps a span that turned
    out to bracket nothing (an idle engine step) out of the ring."""

    __slots__ = ("name", "args", "ts", "dur", "cpu", "_cpu_ns", "_trace",
                 "_keep")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args
        self.ts = self.dur = self.cpu = 0.0
        self._keep = True
        self._trace = jax.profiler.TraceAnnotation(name, **args)

    def set(self, **args) -> None:
        self.args.update(args)

    def discard(self) -> None:
        self._keep = False

    def __enter__(self) -> "annotate":
        self._trace.__enter__()
        self.ts = now_us()
        self._cpu_ns = _thread_cpu_ns(self.ts)
        return self

    def __exit__(self, *exc) -> bool:
        end = now_us()
        self.dur = end - self.ts
        self._trace.__exit__(*exc)
        if self._keep:      # a discarded span's thread clock is not read
            self.cpu = (_thread_cpu_ns(end) - self._cpu_ns) / 1e3
            record(self.name, self.ts, self.dur, self.cpu, **self.args)
        return False


def start_profiler(trace_dir: Optional[str] = None) -> None:
    """Enable `RecordEvent` recording, from an empty ring; if trace_dir
    is given, also start a jax.profiler device trace into it
    (≈ EnableProfiler(kAll))."""
    global _enabled, _trace_dir
    with _lock:
        _events.clear()
    _enabled = True
    _trace_dir = trace_dir
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
    vlog(1, f"profiler started (trace_dir={trace_dir})")


def stop_profiler(sorted_key: str = "total",
                  profile_path: Optional[str] = None,
                  print_table: bool = True) -> List[dict]:
    """Stop profiling; print the aggregated op-time table and optionally
    dump the raw events as a Chrome-trace json (≈ DisableProfiler's
    sorted table + profiler.proto dump, profiler.h:117-126).

    sorted_key in {"total", "calls", "max", "min", "ave"}.
    Returns the aggregated rows.
    """
    global _enabled, _trace_dir
    _enabled = False
    if _trace_dir:
        jax.profiler.stop_trace()
        _trace_dir = None
    rows = profile_table(sorted_key)
    if print_table and rows:
        print(format_table(rows, sorted_key))
    if profile_path:
        save_profile(profile_path)
    return rows


def reset_profiler() -> None:
    with _lock:
        _events.clear()


def get_events() -> List[dict]:
    with _lock:
        return list(_events)


@contextlib.contextmanager
def profiler(sorted_key: str = "total", profile_path: Optional[str] = None,
             trace_dir: Optional[str] = None):
    """Context manager form (≈ fluid.profiler.profiler)."""
    start_profiler(trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key=sorted_key, profile_path=profile_path)


def profile_table(sorted_key: str = "total") -> List[dict]:
    """Aggregate recorded spans into per-name stats rows."""
    agg: Dict[str, dict] = {}
    for ev in get_events():
        row = agg.setdefault(ev["name"], {
            "name": ev["name"], "calls": 0, "total": 0.0,
            "min": float("inf"), "max": 0.0,
        })
        row["calls"] += 1
        row["total"] += ev["dur"]
        row["min"] = min(row["min"], ev["dur"])
        row["max"] = max(row["max"], ev["dur"])
    rows = []
    grand_total = sum(r["total"] for r in agg.values()) or 1.0
    for row in agg.values():
        row["ave"] = row["total"] / row["calls"]
        row["ratio"] = row["total"] / grand_total
        rows.append(row)
    key = {"total": "total", "calls": "calls", "max": "max", "min": "min",
           "ave": "ave"}.get(sorted_key, "total")
    rows.sort(key=lambda r: r[key], reverse=True)
    return rows


def format_table(rows: List[dict], sorted_key: str = "total") -> str:
    lines = [
        f"------------------->  Profiling Report (sorted by {sorted_key})"
        "  <-------------------",
        f"{'Event':<40}{'Calls':>8}{'Total(us)':>14}{'Min(us)':>12}"
        f"{'Max(us)':>12}{'Ave(us)':>12}{'Ratio':>8}",
    ]
    for r in rows:
        lines.append(
            f"{r['name'][:39]:<40}{r['calls']:>8}{r['total']:>14.1f}"
            f"{r['min']:>12.1f}{r['max']:>12.1f}{r['ave']:>12.1f}"
            f"{r['ratio']:>8.3f}")
    return "\n".join(lines)


def events_to_chrome_trace(events: Optional[List[dict]] = None,
                           pid: int = 0) -> dict:
    """Render host spans as Chrome trace format (chrome://tracing /
    perfetto), ≈ tools/timeline.py:36 _ChromeTraceFormatter."""
    events = get_events() if events is None else events
    trace = [{
        "name": ev["name"], "ph": "X", "cat": "host",
        "ts": ev["ts"], "dur": ev["dur"], "pid": pid, "tid": ev["tid"],
        "args": dict(ev.get("args") or {}),
    } for ev in events]
    meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"process {pid}"}}]
    return {"traceEvents": meta + trace, "displayTimeUnit": "ms"}


def save_profile(path: str, pid: int = 0) -> None:
    """Dump recorded host events as a Chrome-trace json file."""
    with open(path, "w") as f:
        json.dump(events_to_chrome_trace(pid=pid), f)
    vlog(1, f"profile written to {path}")
