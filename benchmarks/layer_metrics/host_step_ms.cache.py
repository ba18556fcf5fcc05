"""`engine.flush` (copy-on-write copies, tier loads, compress, promote
replayed before the step), per engine step, over the window."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "host_step_ms.cache")
