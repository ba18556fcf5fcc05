"""Tracing / profiling (≈ reference platform/profiler + tools/timeline.py).

Two tiers, mirroring the reference's design split:

1. Host-side event profiler — `RecordEvent` / `record_event` RAII spans
   aggregated into sorted op-time tables (≈ RecordEvent wrap of every op
   run, /root/reference/paddle/fluid/platform/profiler.h:72,117-126 and
   EnableProfiler/DisableProfiler print tables). Under jit, XLA fuses ops,
   so host spans cover the runtime tier (trace, compile, step dispatch,
   data feed); device-op granularity comes from tier 2.
2. Device tracer — `start_profiler`/`stop_profiler`/`profiler` wrap
   `jax.profiler.start_trace/stop_trace` (≈ CUPTI device_tracer.h:39);
   `annotate` / `TraceAnnotation` name regions inside the device timeline;
   `annotate` spans also land, always, in the one bounded span ring that
   `get_events` reads (the serving loop's host spans, OBSERVABILITY.md).

`timeline.py` converts recorded host events to Chrome trace format and can
merge multiple processes' profiles (≈ tools/timeline.py:25-36).
"""

from paddle_tpu.profiler.profiler import (
    RecordEvent,
    annotate,
    events_to_chrome_trace,
    get_events,
    profile_table,
    profiler,
    record_event,
    record_function,
    reset_profiler,
    save_profile,
    start_profiler,
    stop_profiler,
)
from paddle_tpu.profiler.timeline import Timeline, merge_profiles
from paddle_tpu.profiler.device_trace import (
    OpRow, device_trace, format_table, op_table)

__all__ = [
    "RecordEvent", "annotate", "events_to_chrome_trace", "get_events",
    "profile_table", "profiler", "record_event", "record_function",
    "reset_profiler", "save_profile", "start_profiler", "stop_profiler",
    "Timeline", "merge_profiles",
    "OpRow", "device_trace", "format_table", "op_table",
]
