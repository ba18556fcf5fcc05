"""Device idle time per whole cycle of the step program in the traced slice
(`span_reduce.device_cycles`) less the four `host_step_ms.*` and
`step_transfer_ms` taken over the slice: what no span explains. In
milliseconds, not a share, so it cannot pass 100%."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "idle_unnamed_ms")
