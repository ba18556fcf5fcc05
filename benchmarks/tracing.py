"""The traced slice of a `--trace 1` run: the profiler is on for the last
few seconds of the window only (traces are large and tracing slows the
host), and what it wrote is reduced once the window has closed.

Busy time and the window it is a share of are both read from the trace,
on the device's clock: the window is the extent from the first device
operation to the last. The host's stamps around `start_trace` and
`stop_trace` are not the denominator (operations go on while
`stop_trace` collects); they only bracket the extent, and a trace whose
extent does not fit between them is refused, not clamped."""

from __future__ import annotations

import shutil
import time

from benchmarks import trace_reduce
from benchmarks.common import log


def slice_seconds(window_s: float) -> float:
    return min(4.0, window_s / 3.0)


def idle_pct(obs):
    """Share of the traced slice in which no operation ran on the device:
    what the `device_idle_pct.*` readers return."""
    if not obs.get("trace") or not obs.get("trace_window_s"):
        return None
    return 100.0 * (1.0 - obs["busy_s"] / obs["trace_window_s"])


def bracketed(extent_s: float, inner_s: float, outer_s: float) -> bool:
    """Whether the trace's extent fits the host's stamps: no longer than
    from the call of `start_trace` to the return of `stop_trace` (2% for
    the two clocks' rates), and no shorter than half of what lay between
    the two calls. Another unit or epoch on the device's side fails."""
    return 0.5 * inner_s <= extent_s <= 1.02 * outer_s


class TraceSlice:
    def __init__(self, trace_dir: str, device_prefix: str,
                 keep: bool = False):
        self.dir, self.prefix, self.keep = trace_dir, device_prefix, keep
        self.asked = self.started = self.stopped = self.returned = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        # the device's lines are all that is read: the Python tracer
        # would slow the host loop it is meant to watch
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        self.asked = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()
        self.returned = time.perf_counter()

    def reduce(self) -> dict:
        """What the readers and the result's `device` are given. The
        trace's files are deleted: a run leaves little on disk."""
        path = trace_reduce.find_xplane(self.dir)
        events = trace_reduce.load_xplane(path, self.prefix)
        if not events:
            for row in trace_reduce.describe_xplane(path):
                log("trace:", row)
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        inner = self.stopped - self.started
        outer = self.returned - self.asked
        if not events:
            if self.prefix.startswith("/device:"):
                raise SystemExit("benchmarks/tracing.py: the trace holds no "
                                 f"operation of a {self.prefix}* plane")
            # the CPU of a toy run: nothing to be a share of
            return {"trace": events, "trace_window_s": inner, "busy_s": 0.0}
        extent = trace_reduce.extent_seconds(events)
        busy = trace_reduce.busy_seconds(events)
        log(f"traced slice: device operations span {extent} s and keep the "
            f"device busy for {busy} s; on the host's clock the profiler "
            f"was on for {inner} s, {outer} s with its start and stop")
        if not bracketed(extent, inner, outer):
            raise SystemExit(
                f"benchmarks/tracing.py: the trace's device operations span "
                f"{extent} s, which does not fit the {inner} to {outer} s "
                f"the profiler was on by the host's clock")
        log("device ops one by one:",
            trace_reduce.top_ops(events, merge=str))
        return {
            "trace": events,
            "trace_window_s": extent,
            "busy_s": busy,
            "breakdown": {"device_ops": trace_reduce.top_ops(events),
                          "idle_gaps": trace_reduce.idle_gaps(events)},
        }
