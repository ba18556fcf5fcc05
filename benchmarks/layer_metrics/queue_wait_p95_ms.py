"""95th percentile (nearest rank) of arrival at the front door to first
admission by the scheduler, over the requests that arrived in the
window: the wait in the front door's submit queue and the scheduler's."""

from benchmarks import span_reduce


def read(obs):
    return span_reduce.metric(obs, "queue_wait_p95_ms")
