"""`engine.pack` + `.sample` + `.publish` + `engine.step`'s self time, per
engine step, over the window: the step's own host work."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "host_step_ms.step")
