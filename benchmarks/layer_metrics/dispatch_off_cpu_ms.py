"""A step's `engine.dispatch` `dur - cpu`, over the window: the wait for the
interpreter while the handlers write the last step's frames, plus whatever
the launch itself blocks on. On standard error beside it the mean `dur` of
the steps whose dispatch no `frontdoor.deliver` and no `runtime.gc` of
another thread overlapped, with their number: the launch alone."""

from benchmarks import handoff_reduce


def read(obs):
    return handoff_reduce.metric(obs, "dispatch_off_cpu_ms")
