"""The reduction from the program's span ring to the host's share of a
step, on a ring and a device trace built by hand to agree, and the
attribution of a kept trace's idle gaps to host spans.

A cycle of the hand-built loop is 81 ms: `frontdoor.control` 1,
`engine.step` 79 (plan 2, flush 1, pack 3, dispatch to fetch 66 with
the device busy for 60 of them, sample 4, publish 2, 1 of its own),
`frontdoor.finish` 1. So the device idles 21 ms a step: front door 2,
scheduler 2, cache 1, step 10, transfers 6, and nothing unnamed."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import host_spans, span_reduce as sr  # noqa: E402
from benchmarks.common import load_json, load_module  # noqa: E402
from benchmarks.trace_reduce import (MODULES_LINE, OPS_LINE,  # noqa: E402
                                     Event)

MS = 1e3                       # the ring counts microseconds
LOOP, HANDLER = 7, 9           # thread ids
CYCLE, DEVICE, STEPS, SLICE = 81.0, 60.0, 40, 10
T0 = 5e9                       # the window's start on the ring's clock
NEW = ["host_step_ms.frontdoor", "host_step_ms.scheduler",
       "host_step_ms.cache", "host_step_ms.step", "step_transfer_ms",
       "idle_unnamed_ms", "queue_wait_p95_ms"]


def span(name, at_ms, dur_ms, tid=LOOP, **args):
    return {"name": name, "ts": T0 + at_ms * MS, "dur": dur_ms * MS,
            "tid": tid, "args": args}


def one_step(i, at):
    """The loop's spans of the cycle that starts `at` ms into the
    window; the device runs from 4 ms into `engine.dispatch`."""
    s = at + 1.0
    return [
        span("frontdoor.control", at, 1.0, submitted=1, cancelled=0),
        span("engine.step", s, 79.0, step=i),
        span("engine.plan", s, 2.0, step=i),
        span("engine.flush", s + 2.0, 1.0, step=i, cow=0),
        span("engine.pack", s + 3.5, 3.0, step=i),          # 0.5 its own
        span("engine.dispatch", s + 6.5, 5.0, step=i),
        span("engine.fetch", s + 11.5, 61.0, step=i, bytes=4),
        span("engine.sample", s + 72.5, 4.0, step=i, emitted=1),
        span("engine.publish", s + 76.5, 2.0, step=i),      # 0.5 its own
        span("frontdoor.finish", s + 79.0, 1.0, closed=0),
    ]


def request(i, arrival, wait):
    at = T0 + arrival * MS
    args = {"req": i, "prompt": 8, "cached": 0, "arrival": at,
            "enqueued": at + 0.25 * wait * MS, "admitted": at + wait * MS,
            "first_token": at + (wait + 80) * MS,
            "first_write": at + (wait + 81) * MS,
            "finished": at + (wait + 400) * MS, "admit_step": i,
            "first_token_step": i, "chunk_steps": 1, "preemptions": 0,
            "reason": "length"}
    return {"name": "request", "ts": at, "dur": (wait + 400) * MS,
            "tid": LOOP, "args": args}


def ring(steps=STEPS):
    window_ms = steps * CYCLE
    events = [span("obs.scrape", -2.0, 2.0, tid=HANDLER, bytes=100),
              span("obs.scrape", window_ms + 1.0, 2.0, tid=HANDLER,
                   bytes=100),
              span("obs.scrape", window_ms + 500.0, 2.0, tid=HANDLER,
                   bytes=100)]
    for i in range(-3, steps + 3):          # the loop ran before and after
        events += one_step(i, i * CYCLE)
    events += [request(i, i * CYCLE, wait=float(i)) for i in range(steps)]
    return events


def device_trace(steps=SLICE):
    """`steps` whole cycles on the device's own clock, between an
    execution the profiler caught the end of and one it caught the
    start of."""
    d = "/device:TPU:0"
    out = []
    for i in range(steps + 2):
        at = i * CYCLE * 1e6
        dur = DEVICE * 1e6 * (0.02 if i == 0 else 0.5 if i == steps + 1
                              else 1.0)
        out += [Event(d, MODULES_LINE, "jit__step_fn(1)", at, dur),
                Event(d, OPS_LINE, "fusion.1", at, dur / 2),
                Event(d, OPS_LINE, "copy.2", at + dur / 4, 3 * dur / 4)]
    return out


def observed(steps=STEPS, traced=True):
    obs = {"window_s": steps * CYCLE / 1e3, "trace": None,
           "trace_window_s": None, "busy_s": None}
    if traced:      # the harness's own extent and busy time are not read
        obs.update(trace=device_trace(), trace_window_s=SLICE * CYCLE / 1e3,
                   busy_s=0.0)
    return obs


def test_the_window_is_found_from_three_scrapes():
    (t0, t1), why = sr.find_window(ring(), STEPS * CYCLE / 1e3)
    assert why is None
    assert t0 == T0 and t1 == pytest.approx(T0 + STEPS * CYCLE * MS)
    # an older scrape (a probe during the warm-up) changes nothing
    older = ring() + [span("obs.scrape", -900.0, 2.0, tid=HANDLER)]
    assert sr.find_window(older, STEPS * CYCLE / 1e3)[0] == (t0, t1)
    # two scrapes, or a second one inside the window, are not the runner's
    assert sr.find_window(ring()[1:], 1.0)[0] is None
    window, why = sr.find_window(ring(), 2 * STEPS * CYCLE / 1e3)
    assert window is None and "second scrape" in why


def test_a_step_s_self_time_subtracts_its_children():
    spans = sr.loop_spans(ring())
    assert all(e["tid"] == LOOP for e in spans)
    steps = sr.Steps(spans, T0, T0 + STEPS * CYCLE * MS)
    assert len(steps) == STEPS
    durs = steps.durs
    assert durs[sr.SELF] == pytest.approx([1.0] * STEPS)
    assert steps.cycle_ms() == pytest.approx(CYCLE)
    assert steps.dispatch_to_fetch_ms() == pytest.approx(66.0)
    table = {row[0]: row for row in sr.span_table(steps)}
    assert table["engine.fetch"][1:4] == pytest.approx((STEPS, 61.0, 61.0))
    assert table["engine.step"][4] == pytest.approx(
        100 * 79.0 * STEPS / (CYCLE * (STEPS - 1)))


def test_the_host_s_share_of_a_step_by_layer():
    got = sr.reduce(ring(), observed())
    assert got["host_step_ms.frontdoor"] == pytest.approx(2.0)
    assert got["host_step_ms.scheduler"] == pytest.approx(2.0)
    assert got["host_step_ms.cache"] == pytest.approx(1.0)
    assert got["host_step_ms.step"] == pytest.approx(10.0)
    assert got["step_transfer_ms"] == pytest.approx(6.0)
    # request i waited i ms for its admission: nearest rank of 40
    assert got["queue_wait_p95_ms"] == pytest.approx(37.0)


def test_nothing_is_unnamed_where_ring_and_trace_agree():
    assert sr.reduce(ring(), observed())["idle_unnamed_ms"] == \
        pytest.approx(0.0, abs=1e-6)


def test_a_span_taken_out_shows_as_the_planted_gap():
    cut = [e for e in ring() if e["name"] != "frontdoor.finish"]
    got = sr.reduce(cut, observed())
    assert got["idle_unnamed_ms"] == pytest.approx(1.0)
    assert got["host_step_ms.frontdoor"] == pytest.approx(1.0)
    # a child taken out is still the step's own time: it stays named
    cut = [e for e in ring() if e["name"] != "engine.pack"]
    got = sr.reduce(cut, observed())
    assert got["idle_unnamed_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["host_step_ms.step"] == pytest.approx(10.0)


def test_a_stalled_step_is_listed_with_its_spans():
    events = ring()
    for e in events:            # everything after step 12 is 300 ms late
        if e["tid"] == LOOP and e["ts"] > T0 + (12 * CYCLE + 80.5) * MS:
            e["ts"] += 300 * MS
    spans = sr.loop_spans(events)
    steps = sr.Steps(spans, T0, T0 + STEPS * CYCLE * MS)
    (stall,) = sr.stalled(spans, steps)
    assert stall["step"] == 12
    assert stall["cycle_ms"] == pytest.approx(CYCLE + 300)
    names = [s[0] for s in stall["spans"]]
    assert names[0] == "engine.step" and names[-1] == "frontdoor.control"
    assert len(names) == 10


def test_only_whole_cycles_of_the_device_count():
    idle, device, n = sr.device_cycles(device_trace())
    assert (idle, device, n) == pytest.approx((CYCLE - DEVICE, DEVICE, SLICE))
    # a second chip's plane and another program change nothing
    more = device_trace() + [
        Event("/device:TPU:1", OPS_LINE, "fusion.1", 0.0, 9e9),
        Event("/device:TPU:0", MODULES_LINE, "jit__copy_blocks(2)", 5.0, 1.0)]
    assert sr.device_cycles(more) == pytest.approx((CYCLE - DEVICE, DEVICE,
                                                    SLICE))
    assert sr.device_cycles(device_trace(steps=1)) is None
    assert sr.device_cycles([]) is None and sr.device_cycles(None) is None


def test_without_the_trace_the_span_metrics_still_read():
    got = sr.reduce(ring(), observed(traced=False))
    assert got["host_step_ms.step"] == pytest.approx(10.0)
    assert "step_transfer_ms" not in got and "idle_unnamed_ms" not in got


def test_too_few_steps_give_nothing():
    got = sr.reduce(ring(steps=10), observed(steps=10))
    assert set(got) == {"why"} and "under 20" in got["why"]


@pytest.mark.parametrize("name", NEW)
def test_every_reader_returns_none_on_an_empty_ring(name, monkeypatch):
    monkeypatch.setattr(sr, "ring", lambda: [])
    assert load_module("layer_metrics", name).read(observed()) is None


@pytest.mark.parametrize("name", NEW)
def test_every_reader_reads_the_hand_built_ring(name, monkeypatch):
    monkeypatch.setattr(sr, "ring", ring)
    assert load_module("layer_metrics", name).read(observed()) is not None


def test_the_new_entries_are_the_issue_s_table():
    entries = {m["name"]: m for m in load_json("BENCHMARK.json")["per_layer"]}
    both = ["gpt2m-chat", "gpt2l-docs"]
    want = {
        "host_step_ms.frontdoor": ("program_span", "front door", both),
        "host_step_ms.scheduler": ("program_span", "scheduler", both),
        "host_step_ms.cache": ("program_span", "cache", both),
        "host_step_ms.step": ("program_span", "step", both),
        "step_transfer_ms": ("device_trace", "step", both),
        "idle_unnamed_ms": ("device_trace", "device", both),
        "queue_wait_p95_ms": ("program_span", "front door", ["gpt2m-chat"]),
    }
    assert list(entries)[-7:] == NEW
    for name, (source, layer, cells) in want.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["workloads"]) == ("ms", "lower", source, layer, cells)
        assert m["moves"] == ("ttft_p95_ms" if name.startswith("queue")
                              else "itl_p95_ms")


# -- benchmarks/host_spans.py: span against gap, in one kept trace ---------

def test_a_gap_is_split_among_the_spans_that_cover_it():
    line = "python"
    S = host_spans.Span
    spans = [S(line, "frontdoor.control", 0, 5, {}),
             S(line, "engine.step", 10, 90, {"step": 1}),
             S(line, "engine.plan", 12, 8, {"step": 1}),
             S(line, "engine.dispatch", 25, 10, {"step": 1}),
             S(line, "engine.fetch", 35, 60, {"step": 1}),
             S(line, "frontdoor.finish", 101, 4, {}),
             S(line, "engine.step", 110, 90, {"step": 2}),
             S(line, "engine.dispatch", 125, 10, {"step": 2}),
             # a handler's thread: explains nothing of the loop's gaps
             S("handler", "obs.scrape", 96, 30, {})]
    d = "/device:TPU:0"
    ops = [Event(d, OPS_LINE, "fusion.1", 30, 50),
           Event(d, OPS_LINE, "fusion.2", 40, 10),          # inside
           Event(d, OPS_LINE, "fusion.1", 130, 40),
           Event("/device:TPU:1", OPS_LINE, "fusion.1", 0, 500)]
    got = host_spans.attribute(ops, spans)
    ns = {k: round(v * 1e9, 6) for k, v in got["idle_gaps"].items()}
    # the one gap, 80 to 130: fetch to 95, the step's own to 100, nothing
    # to 101, finish to 105, nothing to 110, the next step's own to 125,
    # its dispatch to 130
    assert ns == {"engine.step (self)": 20, "engine.fetch": 15,
                  "unnamed": 6, "engine.dispatch": 5, "frontdoor.finish": 4}
    before = {k: round(v * 1e9, 6)
              for k, v in got["before_first_op"].items()}
    assert before == {"engine.plan": 8, "engine.step (self)": 7,
                      "frontdoor.control": 5, "engine.dispatch": 5,
                      "unnamed": 5}
    assert {k: round(v * 1e9, 6) for k, v in got["after_last_op"].items()} \
        == {"engine.step (self)": 30}
    assert host_spans.attribute(ops, []) == {}
    assert host_spans.attribute([], spans) == {}
