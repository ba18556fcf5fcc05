"""95th percentile (nearest rank) over the window's hand-overs of the end of
`frontdoor.deliver` (the woken handlers have written their frames) less the
opening stamp of `engine.sample` of the engine step that caused it: how
long a picked token takes to be written."""

from benchmarks import handoff_reduce


def read(obs):
    return handoff_reduce.metric(obs, "deliver_p95_ms")
