"""A step's sum of `dur - cpu` over the engine loop's spans that wait on
nothing of their own (`engine.plan`, `.flush`, `.pack`, `.sample`,
`.publish`, `engine.step`'s self time, `frontdoor.control`, `.finish`,
`.snapshot`), over the window: the loop's wait for the interpreter outside
the launch (`engine.dispatch`, `engine.fetch` and `frontdoor.wait` block
on something of their own and are left out)."""

from benchmarks import handoff_reduce


def read(obs):
    return handoff_reduce.metric(obs, "loop_off_cpu_ms")
