"""The 95th percentile of the time to first token over every request
sent in the window, as `ttft_p95_ms` takes it, in a cell where it
swings too widely from run to run to be held to a bound (PERF.md 2): a
cache miss's two chunk steps, and misses that meet, make that tail. The
traced run's profiler is on for the window's last seconds only."""


def read(obs):
    return (obs.get("end_to_end") or {}).get("ttft_p95_ms")
