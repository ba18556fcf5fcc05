"""A step's `engine.fetch` less its `engine.wait` (`block_until_ready` on
the step's picks), over the window: the download, and the counts, once the
device is known to be done."""

from benchmarks import handoff_reduce


def read(obs):
    return handoff_reduce.metric(obs, "fetch_after_ready_ms")
