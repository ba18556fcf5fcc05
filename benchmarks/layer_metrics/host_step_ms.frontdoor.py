"""The front door's spans on the engine loop's thread (`frontdoor.control`,
`.finish`, `.snapshot`, `.wait`), per engine step, over the window. Time
of the loop's cycle that no span covers is not added: it shows in
`idle_unnamed_ms`."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "host_step_ms.frontdoor")
