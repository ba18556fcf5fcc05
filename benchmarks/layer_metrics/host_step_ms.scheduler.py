"""`engine.plan` (the scheduler's `next_batch`, admissions and the cold
sweep), per engine step, over the window."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "host_step_ms.scheduler")
