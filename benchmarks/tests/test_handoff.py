"""The hand-off readers on rings and traces built by hand.

A cycle of the hand-built loop is 20 ms: `frontdoor.control` 0.5,
`engine.step` 18 (plan 2 of which 0.5 off the CPU, flush 0.5, pack 1,
dispatch, fetch 8 with `engine.wait` 7 inside, sample 1.5 of which 0.3
off, publish 0.5, 0.5 of its own of which 0.1 off), `frontdoor.finish`
1 of which 0.2 off: 1.1 ms a step off the CPU outside the launch. A
hand-over is delivered on the handler's thread from 0.9 ms after the
step's end; after an even step the delivery lasts 8 ms and reaches into
the next step's `engine.dispatch`, which then takes 4 ms with 3 off the
CPU; after an odd step it lasts 3 ms and the next dispatch is alone: 2
ms with 1 off (so an even step is 16 ms long and an odd one 18)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import handoff, handoff_reduce as hr  # noqa: E402
from benchmarks import span_reduce as sr  # noqa: E402
from benchmarks.common import load_json, load_module  # noqa: E402
from benchmarks.host_spans import Span  # noqa: E402
from benchmarks.trace_reduce import MODULES_LINE, OPS_LINE, Event  # noqa: E402

MS = 1e3
LOOP, HANDLER, OTHER = 7, 9, 11
CYCLE, STEPS = 20.0, 40
T0 = 5e9
CELLS = ["gpt2m-chat", "gpt2l-docs", "glm47f-docs8k", "phi4mf-reason",
         "sala-docs32k"]


def span(name, at_ms, dur_ms, cpu_ms=None, tid=LOOP, **args):
    return {"name": name, "ts": T0 + at_ms * MS, "dur": dur_ms * MS,
            "cpu": None if cpu_ms is None else cpu_ms * MS, "tid": tid,
            "args": args}


def one_step(i, at):
    """The loop's spans of the cycle that starts `at` ms into the
    window, and the delivery of its hand-over."""
    s = at + 0.5
    crowded = i % 2 == 1          # the step before was even
    launch = 4.0 if crowded else 2.0
    fetch_at = s + 3.5 + launch
    step_ms = 18.0 - (4.0 - launch)
    kids_cpu = 1.5 + 0.5 + 1.0 + 1.0 + 0.5 + 1.2 + 0.5
    deliver_ms = 8.0 if i % 2 == 0 else 3.0
    return [
        span("frontdoor.control", at, 0.5, 0.5, submitted=1, cancelled=0),
        span("engine.step", s, step_ms, kids_cpu + 0.4, step=i, gc_us=100.0),
        span("engine.plan", s, 2.0, 1.5, step=i),
        span("engine.flush", s + 2.0, 0.5, 0.5, step=i, cow=0),
        span("engine.pack", s + 2.5, 1.0, 1.0, step=i),
        span("engine.dispatch", s + 3.5, launch, 1.0, step=i),
        span("engine.fetch", fetch_at, 8.0, 0.5, step=i, bytes=384),
        span("engine.wait", fetch_at + 0.01, 7.0, 0.1, step=i),
        span("engine.sample", fetch_at + 8.0, 1.5, 1.2, step=i, emitted=4),
        span("engine.publish", fetch_at + 9.5, 0.5, 0.5, step=i),
        span("frontdoor.finish", s + step_ms, 1.0, 0.8, closed=0, woken=4),
        span("frontdoor.deliver", s + step_ms + 0.9, deliver_ms, 1.0,
             tid=HANDLER, step=i, streams=4, frames=4, wake_us=200.0),
    ]


def ring(steps=STEPS):
    window_ms = steps * CYCLE
    events = [span("obs.scrape", -2.0, 2.0, tid=HANDLER, bytes=100),
              span("obs.scrape", window_ms + 1.0, 2.0, tid=HANDLER,
                   bytes=100),
              span("obs.scrape", window_ms + 500.0, 2.0, tid=HANDLER,
                   bytes=100)]
    for i in range(-3, steps + 3):
        events += one_step(i, i * CYCLE)
    return events


def parents(events):
    """The same run on the parent's program: no `cpu`, no `engine.wait`,
    no delivery, no `gc_us`."""
    out = []
    for e in events:
        if e["name"] in ("engine.wait", "frontdoor.deliver", "runtime.gc"):
            continue
        e = {k: v for k, v in e.items() if k != "cpu"}
        e["args"] = {k: v for k, v in e["args"].items() if k != "gc_us"}
        out.append(e)
    return out


OBS = {"window_s": STEPS * CYCLE / 1e3}


def test_the_four_numbers_of_the_hand_built_loop():
    out = hr.reduce(ring(), dict(OBS))
    assert out["why"] == {}
    assert out["loop_off_cpu_ms"] == pytest.approx(1.1)
    # half the steps crowded (3 ms off the CPU), half alone (1)
    assert out["dispatch_off_cpu_ms"] == pytest.approx(2.0)
    assert out["fetch_after_ready_ms"] == pytest.approx(1.0)
    # the delivery ends 0.9 + 8 ms after the step; `engine.sample`
    # opened 2.5 ms before that: the long deliveries are half of all
    assert out["deliver_p95_ms"] == pytest.approx(2.5 + 0.9 + 8.0)


def test_readers_return_the_reduction_once_a_run(monkeypatch):
    calls = []
    monkeypatch.setattr(sr, "ring", lambda: calls.append(1) or ring())
    obs = dict(OBS)
    got = {name: load_module("layer_metrics", name).read(obs)
           for name in hr.METRICS}
    assert len(calls) == 1 and "_handoff_reduce" in obs
    assert got == {"loop_off_cpu_ms": pytest.approx(1.1),
                   "dispatch_off_cpu_ms": pytest.approx(2.0),
                   "fetch_after_ready_ms": pytest.approx(1.0),
                   "deliver_p95_ms": pytest.approx(11.4)}


def test_a_ring_without_the_fields_gives_none_and_says_why(monkeypatch,
                                                           capsys):
    monkeypatch.setattr(sr, "ring", lambda: parents(ring()))
    obs = dict(OBS)
    for name in hr.METRICS:
        assert load_module("layer_metrics", name).read(obs) is None
    said = capsys.readouterr().err
    assert said.count("carry no `cpu`") == 2
    assert "no engine.wait span" in said
    assert "no frontdoor.deliver record" in said
    # and one without the window's scrapes, each reader in its own words
    monkeypatch.setattr(sr, "ring", lambda: ring()[3:])
    obs = dict(OBS)
    for name in hr.METRICS:
        assert load_module("layer_metrics", name).read(obs) is None
    assert capsys.readouterr().err.count("obs.scrape spans") == 4


def test_span_reduce_reads_the_old_numbers_with_and_without_the_wait():
    """`engine.wait` is none of `span_reduce.CHILDREN`: a step's self
    time, the four host shares and dispatch-to-fetch are the same on
    the parent's ring and on this one."""
    assert "engine.wait" not in sr.CHILDREN
    new, old = (sr.reduce(r, dict(OBS, trace=None))
                for r in (ring(), parents(ring())))
    for key in ("host_step_ms.frontdoor", "host_step_ms.scheduler",
                "host_step_ms.cache", "host_step_ms.step"):
        assert new[key] == pytest.approx(old[key])
    assert new["host_step_ms.step"] == pytest.approx(1.0 + 1.5 + 0.5 + 0.5)
    t0, t1 = sr.find_window(ring(), OBS["window_s"])[0]
    around = [sr.Steps(sr.loop_spans(r), t0, t1).dispatch_to_fetch_ms()
              for r in (ring(), parents(ring()))]
    assert around[0] == around[1] == pytest.approx(11.0)


def test_a_launch_is_alone_when_no_other_thread_overlapped_it(capsys):
    events = ring()
    hr.reduce(events, dict(OBS))
    said = capsys.readouterr().err
    assert f"engine.dispatch alone: n {STEPS // 2} mean dur 2.0 ms" in said
    assert f"engine.dispatch all: n {STEPS} mean dur 3.0 ms" in said
    # a collection on another thread over step 4's launch takes it out;
    # one on the loop's own thread over step 6's does not
    at = 4 * CYCLE + 0.5 + 3.5
    events.append(span("runtime.gc", at + 0.5, 0.4, tid=OTHER,
                       generation=2, collected=10))
    events.append(span("runtime.gc", at + 2 * CYCLE + 0.5, 0.4, tid=LOOP,
                       generation=1, collected=0))
    hr.reduce(events, dict(OBS))
    said = capsys.readouterr().err
    assert f"engine.dispatch alone: n {STEPS // 2 - 1} mean" in said
    assert said.count("runtime.gc ") == 2
    assert "generation 2, collected 10, on another's thread" in said
    assert "generation 1, collected 0, on the loop's thread" in said


def test_intervals_overlap_by_the_latest_end_so_far():
    iv = hr.Intervals([span("a", 0.0, 10.0), span("b", 1.0, 1.0),
                       span("c", 30.0, 1.0)])
    assert iv.overlap(span("x", 5.0, 1.0))        # inside the long one
    assert not iv.overlap(span("x", 10.0, 20.0))  # touches both ends only
    assert iv.overlap(span("x", 29.0, 1.5))
    assert not iv.overlap(span("x", 40.0, 1.0))
    assert not hr.Intervals([]).overlap(span("x", 0.0, 1.0))


def test_a_stalled_step_lists_the_other_threads_records(capsys):
    events = [e for e in ring()]
    # step 10 waits 100 ms for its device: every later span moves on
    late = T0 + (10 * CYCLE + 10.0) * MS
    for e in events:
        if e["ts"] >= late and e["name"] != "obs.scrape":
            e["ts"] += 100.0 * MS
    events.append(span("runtime.gc", 10 * CYCLE + 12.0, 90.0, tid=OTHER,
                       generation=2, collected=5))
    hr.reduce(events, dict(OBS))
    said = capsys.readouterr().err
    (line,) = [ln for ln in said.splitlines() if "stalled step 10" in ln]
    assert "runtime.gc 11.5 90.0" in line
    assert "frontdoor.deliver" in line      # step 9's, written in step 10


def test_a_hand_over_no_step_caused_is_in_no_percentile():
    events = ring()
    events.append(span("frontdoor.deliver", 3 * CYCLE + 1.0, 500.0, 1.0,
                       tid=HANDLER, step=None, streams=1, frames=1,
                       wake_us=100.0))
    out = hr.reduce(events, dict(OBS))
    assert out["deliver_p95_ms"] == pytest.approx(11.4)


def test_the_new_entries_are_the_issue_s_table():
    bench = load_json("BENCHMARK.json")
    new = bench["per_layer"][-4:]
    assert [m["name"] for m in new] == list(hr.METRICS)
    assert [m["layer"] for m in new] == ["front door", "step", "step",
                                         "front door"]
    for m in new:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("ms", "lower", "program_span", "itl_p95_ms")
        assert m["workloads"] == CELLS
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(load_module("layer_metrics", m["name"]).read)


# -- handoff.py: a kept trace, made up ------------------------------------

DEVICE = "/device:TPU:0"
NS = 1e6                        # a trace counts nanoseconds


def trace(steps=12, launch_ms=3.0, notice_ms=1.5, after_ms=0.5,
          device_ms=12.0, shift_ms=0.0):
    """`steps` cycles of 25 ms on one clock: `engine.dispatch` opens,
    the program starts `launch_ms` later and runs `device_ms`,
    `engine.wait` returns `notice_ms` after it ends and `engine.fetch`
    `after_ms` after that. `shift_ms` moves the device's clock."""
    spans, events = [], []
    for i in range(steps):
        at = i * 25.0
        ready = at + launch_ms + device_ms + notice_ms
        spans += [
            Span("loop", "engine.step", at * NS - 4e6, 24.0 * NS,
                 {"step": i}),
            Span("loop", "engine.dispatch", at * NS, 4.0 * NS, {"step": i}),
            Span("loop", "engine.fetch", (at + 4.0) * NS,
                 (ready + after_ms - at - 4.0) * NS, {"step": i}),
            Span("loop", "engine.wait", (at + 4.01) * NS,
                 (ready - at - 4.01) * NS, {"step": i}),
            Span("handler", "obs.scrape", at * NS, 1.0 * NS, {}),
        ]
        start = (at + launch_ms + shift_ms) * NS
        events += [Event(DEVICE, MODULES_LINE, "jit__step_fn(123)", start,
                         device_ms * NS),
                   Event(DEVICE, OPS_LINE, "fusion.1", start,
                         device_ms * NS)]
    return events, spans


def test_the_gaps_of_a_made_up_trace_add_up_to_the_transfer():
    out = handoff.reduce(*trace())
    # the first and the last execution may be cut: ten whole ones
    assert (out["executions"], out["unmatched"],
            out["outside_their_brackets"]) == (10, 0, 0)
    assert out["launch_to_start_ms"]["mean"] == pytest.approx(3.0)
    assert out["end_to_ready_ms"]["mean"] == pytest.approx(1.5)
    assert out["fetch_after_ready_ms"]["mean"] == pytest.approx(0.5)
    assert out["step_device_ms"] == pytest.approx(12.0)
    assert out["step_transfer_ms"] == pytest.approx(5.0)
    assert out["sum_of_gaps_ms"] == pytest.approx(out["step_transfer_ms"])
    assert out["launch_to_start_ms"]["p95"] == pytest.approx(3.0)


def test_a_device_clock_of_its_own_shows_outside_the_brackets():
    """A device plane 4 ms late ends its executions after their
    `engine.wait`; one 4 ms early starts them before their own
    `engine.dispatch`, so each is set against the step before."""
    late = handoff.reduce(*trace(shift_ms=4.0))
    assert late["outside_their_brackets"] == late["executions"] == 10
    early = handoff.reduce(*trace(shift_ms=-4.0))
    assert early["outside_their_brackets"] == early["executions"] == 10


def test_a_trace_without_the_wait_matches_nothing():
    events, spans = trace()
    out = handoff.reduce(events, [s for s in spans
                                  if s.name != "engine.wait"])
    assert out == {"executions": 0, "unmatched": 10,
                   "outside_their_brackets": 0}
    assert handoff.reduce([], spans)["executions"] == 0
