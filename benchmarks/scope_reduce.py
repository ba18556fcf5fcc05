"""Device time by the program's named scopes, and the traced slice's
counts from the program's spans.

A trace names an operation as the compiler does (`fusion.12`,
`ragged-dot-none.3`): enough for a Pallas kernel, whose call carries its
own name, and not for the XLA operations of a layer. The compiled
program's text holds, for every instruction, the name the program gave
it: the `jax.named_scope` path it came from, as `metadata={op_name=
"jit(_step_fn)/.../moe_experts/..."}` (a fusion carries its root's). The
runner hands that text over, and this module sums the trace's
operations whose instruction lies under a scope. A program without the
scope gives no match and the reader leaves its metric out.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmarks import span_reduce, trace_reduce
from benchmarks.common import log

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*\bop_name="([^"]*)"')


# What the compiler builds itself out of a scope's instructions, and
# names without their path: its grouped matrix product (`jax.lax.
# ragged_dot` becomes a kernel of the compiler's own, `op_name=
# "ragged-dot-none"`), which in a serving step is the expert layer's.
COMPILER_MADE = {"moe_experts": ("ragged-dot",)}


def scopes_of(program_text: str, scopes) -> dict:
    """{instruction name: scope} for the instructions whose `op_name`
    lies under one of `scopes`, or is what the compiler made of the
    scope's instructions (`COMPILER_MADE`)."""
    out = {}
    for line in program_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            for s in scopes:
                if (f"/{s}/" in m.group(2) + "/"
                        or m.group(2).startswith(COMPILER_MADE.get(s, ()))):
                    out[m.group(1)] = s
    return out


def scope_seconds(events, program_text, scopes):
    """{scope: {"total_s", "count", "names"}} over the trace's device
    operations whose instruction the compiled program puts under the
    scope; None without a trace or a program text."""
    if not events or not program_text:
        return None
    where = scopes_of(program_text, scopes)
    total = {s: defaultdict(float) for s in scopes}
    count = defaultdict(int)
    for ev in events:
        if ev.line != trace_reduce.OPS_LINE:
            continue
        s = where.get(ev.name.split(" ", 1)[0])
        if s is not None:
            total[s][trace_reduce.family(ev.name)] += ev.dur_ns / 1e9
            count[s] += 1
    out = {}
    for s in scopes:
        ranked = sorted(total[s].items(), key=lambda kv: -kv[1])
        out[s] = {"total_s": sum(total[s].values()), "count": count[s],
                  "names": ranked[:8]}
        log(f"scope {s}: {sum(1 for v in where.values() if v == s)} "
            f"instructions in the program, {count[s]} operations in the "
            f"trace, {out[s]['total_s']} s; by family: {ranked[:8]}")
    return out


def slice_counts(obs: dict, fields) -> dict | None:
    """Sums of `engine.step`'s span fields over the steps that started
    in the traced slice, with their number: {"steps": n, field: sum}.
    None where the ring has no such steps or a step lacks a field (a
    program that does not count it)."""
    if not obs.get("trace_window_s"):
        return None
    events = span_reduce.ring()
    window, why = span_reduce.find_window(events, obs["window_s"])
    if window is None:
        log(f"scope_reduce: {why}")
        return None
    t1 = window[1]
    steps = span_reduce.Steps(span_reduce.loop_spans(events),
                              t1 - obs["trace_window_s"] * 1e6, t1).steps
    if not steps or any(f not in s["args"] for s in steps for f in fields):
        return None
    out = {"steps": len(steps)}
    for f in fields:
        out[f] = float(sum(s["args"][f] for s in steps))
    return out


def device_steps(obs: dict) -> int:
    """Executions of the step program that the traced slice shows."""
    found = trace_reduce.matching(
        trace_reduce.program_sums(obs.get("trace") or ()), "step_fn")
    return max((v["count"] for v in found.values()), default=0)
