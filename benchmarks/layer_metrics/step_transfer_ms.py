"""Mean of `engine.dispatch`'s start to `engine.fetch`'s end over the steps
of the traced slice, less the mean device time of the step program's
whole executions in the slice: uploads, launch and the logits' download
around the device's step."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "step_transfer_ms")
