"""Host spans of the serving loop (OBSERVABILITY.md "Host spans"): the
`annotate` primitive writes every layer boundary of `ServeEngine.step`
and of the front door's loop to the profiler's trace and to one bounded
ring that outlives both; the request tracer files one `request` record
a finished request there. On the CPU toy engine, so counts and order
are checked, never a time.
"""

import gc
import glob
import importlib
import inspect
import json
import statistics
import threading
import time
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.models.transformer import CausalLM
from paddle_tpu.obs.metrics import MetricsRegistry
from paddle_tpu.serve.frontend import ServeFrontend
from paddle_tpu.serve.sse import collect_stream, http_get

import paddle_tpu.engine.engine as engine_mod

# the package re-exports a function named `profiler` over the submodule
prof = importlib.import_module("paddle_tpu.profiler.profiler")

pytestmark = pytest.mark.serve

VOCAB = 61
CHILDREN = ("engine.plan", "engine.flush", "engine.pack", "engine.dispatch",
            "engine.fetch", "engine.sample", "engine.publish")
PROMPTS = [[5, 9, 2, 7, 1, 3], [4, 4, 8], [11, 12, 13, 14, 15, 16, 17, 18]]


@pytest.fixture(scope="module")
def model_and_vars():
    model = CausalLM(vocab=VOCAB, model_dim=16, num_heads=4, num_layers=2,
                     ffn_dim=32, dropout=0.0, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("registry", MetricsRegistry())
    return ServeEngine(model, variables, **kw)


def _drain(model_and_vars, held=False):
    """A fresh engine that served PROMPTS (a prefill budget of 4 tokens:
    several chunk steps a prompt), over an emptied ring; `held`
    synchronous, or with a second step in flight as it ships."""
    eng = _engine(*model_and_vars, max_prefill_tokens=4)
    if held:
        eng._runs_ahead = lambda flight: False
    eng.generate([[1, 2]], max_new_tokens=2)      # the one compilation
    eng.reset_stats()
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    return eng, prof.get_events()


@pytest.fixture
def drained(model_and_vars):
    return _drain(model_and_vars)


def _spans(events, name):
    return [e for e in events if e["name"] == name]


def _end(ev):
    return ev["ts"] + ev["dur"]


def _inside(events, outer, names=CHILDREN):
    """The spans named `names` that lie inside `outer`, by start: how
    `benchmarks/span_reduce.py` finds a step's children. A child's own
    `step` is the step it works for, which is the next one's for the
    launch half of a call that keeps a second step in flight."""
    return sorted((e for e in events if e["name"] in names
                   and e["tid"] == outer["tid"]
                   and outer["ts"] <= e["ts"] and _end(e) <= _end(outer)),
                  key=lambda e: e["ts"])


LAUNCH, COLLECT = CHILDREN[:4], CHILDREN[4:]


@pytest.mark.parametrize("held", [False, True], ids=["ahead", "held"])
def test_children_nest_in_their_step_and_cover_it(model_and_vars, held):
    """Every child lies inside one `engine.step`. A call's children, in
    time: whole launches (plan, flush, pack, dispatch) of the steps it
    sent out, then one collect (fetch, sample, publish) of the step
    whose number the `engine.step` span carries. Held synchronous, a
    call is its own step's seven and nothing else."""
    _, events = _drain(model_and_vars, held)
    steps = _spans(events, "engine.step")
    assert len(steps) >= 6
    homed = 0
    for st in steps:
        kids = _inside(events, st)
        homed += len(kids)
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["ts"], (a["name"], b["name"])
        names = [k["name"] for k in kids]
        assert tuple(names[-3:]) == COLLECT
        assert {k["args"]["step"] for k in kids[-3:]} == {st["args"]["step"]}
        launches = kids[:-3]
        assert len(launches) % 4 == 0 and len(launches) <= 8
        if held:
            assert [k["args"]["step"] for k in launches] == \
                [st["args"]["step"]] * 4
        for i in range(0, len(launches), 4):
            group = launches[i:i + 4]
            assert tuple(k["name"] for k in group) == LAUNCH
            assert len({k["args"]["step"] for k in group}) == 1
            assert group[0]["args"]["step"] >= st["args"]["step"]
    assert homed == sum(e["name"] in CHILDREN for e in events)
    # (with a second step in flight a call has a launch's and a
    # collect's lines between its children, on a toy step of a
    # millisecond: the synchronous call keeps the bound it had. The
    # median call, since one that lost its core between two children,
    # beside five busy test workers, read as low as 0.07)
    covered = statistics.median(
        sum(k["dur"] for k in _inside(events, st)) / st["dur"]
        for st in steps)
    assert covered >= (0.95 if held else 0.9)
    # by its own number every step still has the seven, in their order
    for st in steps:
        mine = sorted((e for e in events if e["name"] in CHILDREN
                       and e["args"]["step"] == st["args"]["step"]),
                      key=lambda e: e["ts"])
        assert [k["name"] for k in mine] == list(CHILDREN)


def test_spans_carry_the_step_and_agree_with_the_counters(drained):
    eng, events = drained
    steps = _spans(events, "engine.step")
    n = int(eng.obs.get("ptpu_engine_steps_total").value)
    assert [st["args"]["step"] for st in steps] == list(range(1, n + 1))
    for name in CHILDREN:
        assert [e["args"]["step"] for e in _spans(events, name)] == \
            list(range(1, n + 1)), name
    tokens = eng.obs.get("ptpu_serve_tokens_total")
    samples = _spans(events, "engine.sample")
    assert sum(e["args"]["emitted"] for e in samples) == \
        tokens.labels(kind="generated").value == 6 * len(PROMPTS)
    assert sum(e["args"]["finished"] for e in samples) == len(PROMPTS)
    assert sum(st["args"]["chunk_tokens"] for st in steps) == \
        tokens.labels(kind="prefill").value
    assert all(e["args"]["bytes"] > 0
               for e in _spans(events, "engine.fetch"))
    assert {"cow", "loads", "compress", "promote"} <= \
        set(_spans(events, "engine.flush")[0]["args"])
    assert {"decode_rows", "chunk_rows", "queue_depth", "used_blocks",
            "overlapped", "discarded_rows"} <= set(steps[0]["args"])
    # a step launched while the one before it was uncollected says so,
    # and greedy traffic that ends by its count throws no row away
    assert sum(st["args"]["overlapped"] for st in steps) == \
        eng.obs.get("ptpu_engine_steps_overlapped_total").value > 0.5 * n
    assert sum(st["args"]["discarded_rows"] for st in steps) == \
        eng.obs.get("ptpu_engine_rows_discarded_total").value == 0
    # the histogram is fed from the span: one observation a step, and
    # the idle call that ends `run()` left neither
    assert sum(c.count for c in eng.obs.get(
        "ptpu_serve_step_ms").children().values()) == n


def test_overlapped_and_discarded_rows_are_their_counters_deltas(
        model_and_vars):
    """`overlapped` (the step was launched while the one before it was
    uncollected) and `discarded_rows` (rows whose request had ended
    since the launch) on `engine.step` sum to the deltas of
    `ptpu_engine_steps_overlapped_total` and
    `ptpu_engine_rows_discarded_total`, with a request that ends on an
    end-of-sequence token, one cancelled with a row out, and one at a
    temperature (its steps wait for the host) in the traffic."""
    eng = _engine(*model_and_vars)
    free = eng.generate([PROMPTS[0]], max_new_tokens=8)[0]
    names = ("ptpu_engine_steps_total", "ptpu_engine_steps_overlapped_total",
             "ptpu_engine_rows_discarded_total")
    before = [eng.obs.get(n).value for n in names]
    prof.reset_profiler()
    assert free.index(free[5]) == 5     # the sixth token ends it
    eng.add_request(PROMPTS[0], max_new_tokens=8, eos_id=free[5])
    drop = eng.add_request(PROMPTS[1], max_new_tokens=12)
    eng.add_request(PROMPTS[2], max_new_tokens=1, temperature=0.7, seed=3)
    for _ in range(6):
        assert eng.step()
    assert eng.cancel(drop)
    eng.run()
    events = prof.get_events()
    steps = _spans(events, "engine.step")
    n, ahead, dropped = (eng.obs.get(name).value - was
                         for name, was in zip(names, before))
    assert len(steps) == n
    assert sum(st["args"]["overlapped"] for st in steps) == ahead
    assert sum(st["args"]["discarded_rows"] for st in steps) == dropped
    assert 0 < ahead < n and dropped == 2
    assert all(st["args"]["overlapped"] in (0, 1) for st in steps)
    # the emitted step's number on the span, children inside it
    assert [st["args"]["step"] for st in steps] == \
        list(range(steps[0]["args"]["step"], steps[0]["args"]["step"] + int(n)))
    for st in steps:
        kids = _inside(events, st)
        assert {k["args"]["step"] for k in kids if k["name"] in COLLECT} == \
            {st["args"]["step"]}
        assert all(k["args"]["step"] == st["args"]["step"] + 1
                   for k in kids if k["name"] in LAUNCH) \
            or not st["args"]["overlapped"]
    eng.cache.assert_quiesced()


def test_ring_is_bounded_and_outlives_engine_and_front_end(model_and_vars):
    prof.reset_profiler()
    fe = ServeFrontend(_engine(*model_and_vars)).start()
    out = collect_stream(fe.url, {"prompt": PROMPTS[0],
                                  "max_new_tokens": 5})
    assert out["done"]
    fe.stop()
    del fe
    gc.collect()
    names = {e["name"] for e in prof.get_events()}
    assert {"engine.step", "frontdoor.control", "frontdoor.finish",
            "request"} <= names
    for _ in range(prof.RING_SPANS + 10):
        prof.record("filler", 0.0, 0.0)
    events = prof.get_events()
    assert len(events) == prof.RING_SPANS
    # (a collection that ran meanwhile may have filed its own record)
    assert {"filler"} <= {e["name"] for e in events} <= \
        {"filler", "runtime.gc"}
    prof.reset_profiler()


def test_profiler_trace_holds_fetch_with_its_step(drained, tmp_path):
    """The harness's options (benchmarks/tracing.py): Python tracer off,
    host tracer at 1. The annotation lies on the host plane, on the
    line of the thread that opened it, with `step` as a stat."""
    eng, _ = drained
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    for p in PROMPTS:
        eng.add_request(p, max_new_tokens=3)
    first = eng.steps + 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            assert eng.step()
    finally:
        jax.profiler.stop_trace()
    eng.run()
    from jax.profiler import ProfileData
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found += [dict(ev.stats)["step"] for ev in line.events
                      if ev.name == "engine.fetch"]
    assert sorted(found) == [first, first + 1, first + 2]


@pytest.fixture(scope="module")
def served(model_and_vars):
    """One request over HTTP through a started front end; the ring as
    it was when the stream had ended, and the front end still up."""
    prof.reset_profiler()
    fe = ServeFrontend(_engine(*model_and_vars)).start()
    out = collect_stream(fe.url, {"prompt": PROMPTS[2],
                                  "max_new_tokens": 16})
    assert out["done"] and len(out["tokens"]) == 16
    # the client has its last frame as soon as the hand-over wakes the
    # handler; the engine loop's thread closes the spans of that very
    # step a moment later (it may wait for the interpreter meanwhile):
    # the ring is read once it has stopped growing
    events = prof.get_events()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        time.sleep(0.1)
        later = prof.get_events()
        if len(later) == len(events):
            break
        events = later
    yield fe, out, events
    fe.stop()


def test_request_record_is_monotone_and_names_its_steps(served):
    _, out, events = served
    (rec,) = [e["args"] for e in _spans(events, "request")
              if e["args"]["req"] == out["final"]["req_id"]]
    order = ["arrival", "enqueued", "admitted", "first_token",
             "first_write", "finished"]
    stamps = [rec[k] for k in order]
    assert None not in stamps
    assert stamps == sorted(stamps), dict(zip(order, stamps))
    assert rec["prompt"] == len(PROMPTS[2]) and rec["reason"] == "length"
    assert rec["chunk_steps"] >= 1 and rec["preemptions"] == 0
    in_ring = {e["args"]["step"]: e for e in _spans(events, "engine.step")}
    for step_key, stamp in (("admit_step", "admitted"),
                            ("first_token_step", "first_token")):
        st = in_ring[rec[step_key]]
        assert st["ts"] <= rec[stamp] <= _end(st)
    # the front door drained it inside a `frontdoor.control` span
    assert any(c["ts"] <= rec["enqueued"] <= _end(c)
               and c["args"]["submitted"] >= 1
               for c in _spans(events, "frontdoor.control"))


def test_finish_span_says_how_many_streams_it_woke(served):
    """`frontdoor.finish` closes a step's streams and hands its frames
    to the event loop in one wake-up: `woken` counts the streams of
    that hand-over. One stream was served, so every step that emitted
    a token woke one stream, and the wake-ups are the counter's."""
    fe, out, events = served
    finishes = _spans(events, "frontdoor.finish")
    assert all({"closed", "woken"} <= set(e["args"]) for e in finishes)
    emitting = [e for e in _spans(events, "engine.sample")
                if e["args"]["emitted"]]
    woke = [e for e in finishes if e["args"]["woken"]]
    # the warm-up's two tokens went to a stream with no loop to wake
    assert len(woke) == len(out["tokens"]) == len(emitting) - 2
    assert sum(e["args"]["woken"] for e in finishes) == len(woke)
    assert fe.obs.get("ptpu_frontdoor_wakeups_total").value == len(woke)
    # the done frame left with its last token's hand-over
    assert [e["args"] for e in finishes if e["args"]["closed"]][-1] == \
        {"closed": 1, "woken": 1}


def test_queued_starts_at_the_arrival(served):
    fe, out, events = served
    (rec,) = [e["args"] for e in _spans(events, "request")
              if e["args"]["req"] == out["final"]["req_id"]]
    frag = fe.engine.tracer.trace_fragment(out["final"]["trace_id"])
    queued = [e for e in frag["traceEvents"] if e["name"] == "queued"]
    assert queued[0]["ts"] == rec["arrival"] < rec["enqueued"]
    assert queued[0]["ts"] + queued[0]["dur"] == rec["admitted"]
    marks = {e["name"]: e for e in frag["traceEvents"] if e["ph"] == "i"}
    assert marks["first_token"]["args"]["step"] == rec["first_token_step"]
    assert marks["first_write"]["ts"] == rec["first_write"]


def test_trace_route_still_serves_its_fragment(served):
    fe, out, _ = served
    status, body = http_get(fe.url + "/trace/" + out["final"]["trace_id"])
    assert status == 200
    frag = json.loads(body)
    assert frag["req_id"] == out["final"]["req_id"]
    assert {"queued", "prefill", "decode"} <= {
        e["name"] for e in frag["traceEvents"] if e["ph"] == "X"}
    assert http_get(fe.url + "/trace/unknown")[0] == 404


def test_scrape_is_a_span_on_the_handlers_thread(served):
    fe, _, _ = served
    status, body = http_get(fe.url + "/metrics")
    assert status == 200
    scrape = _spans(prof.get_events(), "obs.scrape")[-1]
    assert scrape["args"]["bytes"] == len(body.encode())
    step = _spans(prof.get_events(), "engine.step")[-1]
    assert scrape["tid"] != step["tid"]


def test_step_reads_the_clock_at_most_twice_a_span(drained):
    """Every boundary of a step is read once: the request stamps take
    the readings of the spans they fall in."""
    eng, _ = drained
    for p in PROMPTS:
        eng.add_request(p, max_new_tokens=4)
    prof.reset_profiler()
    reads = []

    def counted():
        reads.append(threading.get_ident())
        return real()
    real = prof.now_us
    with mock.patch.object(prof, "now_us", counted), \
            mock.patch.object(engine_mod, "now_us", counted), \
            mock.patch("paddle_tpu.obs.tracing.now_us", counted):
        steps = 0
        while eng.step():
            steps += 1
    spans = [e for e in prof.get_events() if e["name"].startswith("engine.")]
    # `engine.step`, its seven children and `engine.wait`
    assert steps >= 4 and len(spans) == 9 * steps
    assert _spans(prof.get_events(), "request")
    # the idle call that ended the loop opened two spans it discarded;
    # another test's front end may be waiting on its own thread
    mine = reads.count(threading.get_ident())
    # (a call that found nothing to launch behind its step discarded a
    # plan span too: one reading each)
    assert 0 < mine <= 2 * len(spans) + 4 + steps
    # and the engine has no other clock
    source = inspect.getsource(engine_mod)
    assert "perf_counter" not in source and "time.monotonic" not in source


def test_every_span_says_how_long_its_thread_ran(drained):
    """`cpu` is the opening thread's CPU time between the span's two
    stamps: never more than `dur` (but for the two clocks' grain);
    a record that is no stretch of one thread has None."""
    _, events = drained
    assert all({"name", "ts", "dur", "cpu", "tid", "args"} == set(e)
               for e in events)
    spans = [e for e in events if e["name"] not in ("request", "runtime.gc")]
    assert len(spans) >= 9 * 6
    # the thread's clock may tick far more coarsely than `now_us` (every
    # 10 ms on one machine, PERF.md section 6): a reading is then up to a
    # tick behind, so one span's `cpu` may pass its `dur` by a tick, and
    # the steps' sum the stretch they lie in by no more. The grain is
    # measured here, not assumed
    grain = _thread_clock_grain_us()
    for e in spans:
        assert 0.0 <= e["cpu"] <= e["dur"] + grain + 50.0, e
    steps = _spans(events, "engine.step")
    assert sum(st["cpu"] for st in steps) <= \
        _end(steps[-1]) - steps[0]["ts"] + grain + 50.0
    assert [e["cpu"] for e in _spans(events, "request")] == \
        [None] * len(PROMPTS)
    # a parent ran at least as long as the children inside it did: their
    # readings lie between its two, whatever the grain
    for st in steps:
        assert sum(k["cpu"] for k in _inside(events, st)) <= st["cpu"] + 50.0


def _thread_clock_grain_us() -> float:
    """The smallest step `time.thread_time_ns` shows while this thread
    spins, in microseconds: the thread clock's grain on this machine."""
    seen, deadline = set(), time.perf_counter() + 0.05
    while time.perf_counter() < deadline or len(seen) < 2:
        seen.add(time.thread_time_ns())
    ticks = sorted(seen)
    return min(b - a for a, b in zip(ticks, ticks[1:])) / 1e3


def test_a_span_that_sleeps_was_off_the_cpu():
    prof.reset_profiler()
    with prof.annotate("test.sleeps") as span:
        time.sleep(0.05)
    (ev,) = _spans(prof.get_events(), "test.sleeps")
    assert ev["dur"] >= 50e3 and ev["cpu"] == span.cpu < 0.1 * ev["dur"]
    # and one that computes was on it
    with prof.annotate("test.computes"):
        deadline = time.thread_time() + 0.02
        while time.thread_time() < deadline:
            pass
    (ev,) = _spans(prof.get_events(), "test.computes")
    assert 20e3 <= ev["cpu"] <= ev["dur"] + _thread_clock_grain_us() + 50.0


def test_a_reading_of_the_thread_clock_serves_the_boundaries_beside_it():
    """The thread's CPU clock is a system call (6 us on the machine with
    the chip): a reading under `CPU_REUSE_US` old, by `now_us`, is given
    again, so a parent's and its first child's openings share one, and
    the last child's and the parent's closings."""
    real, reads = time.thread_time_ns, []

    def nested(stamps):
        clock = iter(stamps)
        del reads[:]
        prof._cpu_read.__dict__.clear()
        with mock.patch.object(prof, "now_us", lambda: next(clock)), \
                mock.patch.object(prof.time, "thread_time_ns",
                                  lambda: reads.append(1) or real()):
            with prof.annotate("test.outer"):
                with prof.annotate("test.inner"):
                    pass
        return len(reads)
    near = prof.CPU_REUSE_US / 4
    assert nested([0.0, near, 1000.0, 1000.0 + near]) == 2
    far = prof.CPU_REUSE_US * 2
    assert nested([0.0, far, 1000.0, 1000.0 + far]) == 4
    # a reading is another thread's business never
    seen = []
    t = threading.Thread(target=lambda: seen.append(
        prof._thread_cpu_ns(prof.now_us())))
    mine = prof._thread_cpu_ns(prof.now_us())
    t.start()
    t.join(10)
    assert seen and seen[0] != mine


def test_wait_lies_inside_its_fetch_and_leaves_the_old_children_alone(
        drained):
    """`engine.wait` brackets `block_until_ready` on the step's picks,
    first thing inside `engine.fetch`, under the same `step`. It is no
    child of `engine.step`: the seven still tile the step (the test
    above all), so `benchmarks/span_reduce.py`, which knows the seven,
    reads the step's self time and dispatch-to-fetch as before."""
    _, events = drained
    steps = _spans(events, "engine.step")
    waits = {e["args"]["step"]: e for e in _spans(events, "engine.wait")}
    fetches = {e["args"]["step"]: e for e in _spans(events, "engine.fetch")}
    assert sorted(waits) == sorted(fetches) == \
        [st["args"]["step"] for st in steps]
    for step, wait in waits.items():
        fetch = fetches[step]
        assert wait["tid"] == fetch["tid"] and set(wait["args"]) == {"step"}
        assert fetch["ts"] <= wait["ts"] and _end(wait) <= _end(fetch)
    assert "engine.wait" not in CHILDREN
    for st in steps:
        mine = [e["name"] for e in sorted(events, key=lambda e: e["ts"])
                if e["name"].startswith("engine.") and e["name"] != "engine.step"
                and e["args"]["step"] == st["args"]["step"]]
        assert mine == list(CHILDREN[:5]) + ["engine.wait"] + \
            list(CHILDREN[5:])


def test_one_deliver_a_hand_over_on_the_event_loops_thread(served):
    """Every hand-over that woke a stream leaves one `frontdoor.deliver`,
    filed by the event loop's thread, under the engine step whose
    frames it carried."""
    _, out, events = served
    woke = [e for e in _spans(events, "frontdoor.finish")
            if e["args"]["woken"]]
    delivers = _spans(events, "frontdoor.deliver")
    assert len(delivers) == len(woke) == len(out["tokens"])
    loop_tid = _spans(events, "engine.step")[-1]["tid"]
    assert {e["tid"] for e in delivers} != {loop_tid}
    assert len({e["tid"] for e in delivers}) == 1
    assert all(set(e["args"]) == {"step", "streams", "frames", "writes",
                                  "wake_us"} for e in delivers)
    assert all(e["args"]["streams"] == 1 for e in delivers)
    grain = _thread_clock_grain_us()
    # its step is a step of the ring, the one that had just sampled
    samples = {e["args"]["step"]: e for e in _spans(events, "engine.sample")
               if e["ts"] > woke[0]["ts"] - 1e6}
    for d, f in zip(delivers, woke):
        sample = samples[d["args"]["step"]]
        assert sample["args"]["emitted"] == 1
        assert _end(sample) <= f["ts"] <= _end(f)
        assert sample["ts"] < d["ts"] + d["dur"]
        assert 0.0 <= d["cpu"] <= d["dur"] + grain + 50.0


def test_delivers_count_the_frames_the_client_received(served):
    """`frames` is what the handlers wrote between a hand-over's
    `_set_events` and its closer: over a drained run every token frame
    and the done frame."""
    _, out, events = served
    delivers = _spans(events, "frontdoor.deliver")
    assert sum(e["args"]["frames"] for e in delivers) == \
        len(out["tokens"]) + 1
    # the done frame left with the last token's hand-over, in its write
    assert [e["args"]["frames"] for e in delivers] == \
        [1] * (len(delivers) - 1) + [2]
    assert all(e["args"]["writes"] == 1 for e in delivers)


def test_a_wake_up_takes_no_negative_time(served):
    """`wake_us`: from the engine loop's `flush` to `_set_events` on the
    event loop's thread, both on `now_us`; the flush lies inside its
    `frontdoor.finish`."""
    _, _, events = served
    finishes = [e for e in _spans(events, "frontdoor.finish")
                if e["args"]["woken"]]
    for d, f in zip(_spans(events, "frontdoor.deliver"), finishes):
        assert d["args"]["wake_us"] >= 0.0
        flushed = d["ts"] - d["args"]["wake_us"]
        assert f["ts"] <= flushed <= _end(f)


def test_a_collection_between_two_steps_shows_in_the_next_step(
        model_and_vars):
    """The collector's stamps: every collection's microseconds go to one
    sum of which a closing `engine.step` takes what is new (`gc_us`),
    and one of 0.2 ms or more is a `runtime.gc` record on the ring."""
    eng = _engine(*model_and_vars)
    eng.add_request(PROMPTS[0], max_new_tokens=6)
    prof.reset_profiler()
    assert eng.step() and eng.step()
    assert gc.collect() >= 0        # a full collection: milliseconds
    assert eng.step()
    eng.run()
    steps = _spans(prof.get_events(), "engine.step")
    forced = [e for e in _spans(prof.get_events(), "runtime.gc")
              if e["args"]["generation"] == 2
              and _end(steps[1]) <= e["ts"] <= steps[2]["ts"]]
    assert len(forced) == 1
    (rec,) = forced
    assert rec["dur"] >= prof.GC_RECORD_US and rec["cpu"] is None
    assert set(rec["args"]) == {"generation", "collected"}
    assert rec["tid"] == steps[2]["tid"]
    assert all("gc_us" in st["args"] for st in steps)
    # (a difference of two readings of the sum: to the microsecond)
    assert steps[2]["args"]["gc_us"] >= rec["dur"] - 1.0
    # the sum only grows, and the steps took all of it
    assert sum(st["args"]["gc_us"] for st in steps) <= prof.gc_total_us()


def test_a_second_engine_installs_no_second_callback(model_and_vars):
    _engine(*model_and_vars)
    _engine(*model_and_vars)
    assert gc.callbacks.count(prof._on_gc) == 1
    prof.watch_gc()
    assert gc.callbacks.count(prof._on_gc) == 1


def test_ring_reaches_back_130_s_at_chats_rate(served):
    """The arithmetic of `RING_SPANS`'s comment: a step that streams
    leaves twelve entries (`engine.step`, its seven children,
    `engine.wait`, `frontdoor.control` when it drained something,
    `frontdoor.finish`, `frontdoor.deliver`), two steps in three a
    `request` record; `gpt2m-chat` steps 45 times a second (a 22.2 ms
    cycle, PERF.md section 5)."""
    _, _, events = served
    per_step = {"engine.step", *CHILDREN, "engine.wait", "frontdoor.control",
                "frontdoor.finish", "frontdoor.deliver"}
    assert len(per_step) == 12
    steps = _spans(events, "engine.step")
    # nothing else recurs with the steps: the loop's other spans
    # (`frontdoor.snapshot` every 0.25 s, `.wait` when idle) do not
    others = {e["name"] for e in events} - per_step
    assert others <= {"request", "frontdoor.snapshot", "frontdoor.wait",
                      "obs.scrape", "runtime.gc"}
    assert sum(e["name"] in per_step for e in events) <= 12 * len(steps)
    per_second = 45.1 * (12 + 2 / 3)
    assert prof.RING_SPANS / per_second >= 130.0


def test_attention_counts_agree_with_the_counters(drained):
    """`kv_tokens_read` is the sum of the step's rows' contexts and
    `attn_keys` the keys its query tokens attend; the host knows both,
    whatever the model."""
    eng, events = drained
    steps = _spans(events, "engine.step")
    assert sum(st["args"]["kv_tokens_read"] for st in steps) == \
        eng.obs.get("ptpu_attn_kv_tokens_read_total").value > 0
    assert sum(st["args"]["attn_keys"] for st in steps) == \
        eng.obs.get("ptpu_attn_keys_attended_total").value
    # every computed token attends itself and what came before it
    want = sum((len(p) + 5) * (len(p) + 6) // 2 for p in PROMPTS)
    assert eng.obs.get("ptpu_attn_keys_attended_total").value == want
    # a dense model has no expert to count
    assert "moe_assignments" not in steps[0]["args"]
    assert eng.obs.get("ptpu_moe_assignments_total").value == 0


def test_attn_cells_is_the_ragged_grid_s_cells_with_work(model_and_vars,
                                                         monkeypatch):
    """`attn_cells`: over the step's query tiles, the spans of pool
    blocks the kernel walks for the tile (its row's context, cut at the
    tile's last query's causal edge), a pad tile none; and
    `attn_cells_skipped` the rest of the step's tiles x its table's
    spans. Held to their counters, and to a count by hand for a step of
    one decode row, one two-tile chunk and three pad tiles, where a span
    is two 4-token blocks."""
    from paddle_tpu.kernels import paged_attention
    # the span is read off the pool's shape; steered here by a span's keys
    monkeypatch.setattr(paged_attention, "_SPAN_KEYS", 8)
    eng = _engine(*model_and_vars, max_prefill_tokens=16)
    assert (eng.num_tiles, eng.tile_q, eng._cell_keys) == (6, 8, 8)
    assert eng._grid_cells == 6 * -(-eng.max_blocks_per_seq // 2)
    prof.reset_profiler()
    before = {name: eng.obs.get(f"ptpu_{name}_total").value
              for name in ("attn_cells", "attn_cells_skipped")}
    first = eng.add_request(list(range(1, 6)), max_new_tokens=12)
    for _ in range(3):
        eng.step()
    eng.add_request(list(range(30, 58)), max_new_tokens=2)
    while eng.step():
        pass
    steps = _spans(prof.get_events(), "engine.step")
    for name, was in before.items():
        assert sum(st["args"][name] for st in steps) == \
            eng.obs.get(f"ptpu_{name}_total").value - was
    assert all(st["args"]["attn_cells"] + st["args"]["attn_cells_skipped"]
               == eng._grid_cells for st in steps)
    # the prompt alone: one tile reaching 5 keys; the five pad tiles walk
    # nothing
    assert steps[0]["args"]["attn_cells"] == 1
    mixed = [st["args"] for st in steps
             if st["args"]["decode_rows"] == 1 and st["args"]["chunk_rows"]]
    assert [a["chunk_tokens"] for a in mixed] == [16, 12]
    ctx = mixed[1]["kv_tokens_read"] - 28     # the decode row's context
    assert 5 < ctx <= 5 + len(first.generated)
    # the chunk [16, 28): tiles reaching 24 and 28 keys, 3 and 4 spans
    assert mixed[1]["attn_cells"] == -(-ctx // 8) + 3 + 4
    # the chunk [0, 16): its first tile stops at its own causal edge
    assert mixed[0]["attn_cells"] == -(-(ctx - 1) // 8) + 1 + 2


def test_expert_counts_agree_with_the_counters():
    """A model with expert layers: `moe_assignments` is real (row,
    choice) pairs over the expert layers, `moe_active_experts` the
    (layer, expert) pairs a step touched."""
    from paddle_tpu.models.latent_moe import LatentMoELM
    model = LatentMoELM(vocab=VOCAB, model_dim=16, num_heads=2, num_layers=3,
                        q_rank=8, kv_rank=8, nope_dim=4, rope_dim=4, v_dim=4,
                        dense_dim=32, expert_dim=8, num_experts=8, top_k=2,
                        max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=4)
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    steps = _spans(prof.get_events(), "engine.step")
    computed = sum(len(p) + 5 for p in PROMPTS)
    assert sum(st["args"]["moe_assignments"] for st in steps) == \
        eng.obs.get("ptpu_moe_assignments_total").value == computed * 2 * 2
    active = [st["args"]["moe_active_experts"] for st in steps]
    assert sum(active) == eng.obs.get("ptpu_moe_active_experts_total").value
    assert all(0 < a <= min(2 * 8, st["args"]["moe_assignments"])
               for a, st in zip(active, steps))
    assert sum(st["args"]["kv_tokens_read"] for st in steps) == \
        eng.obs.get("ptpu_attn_kv_tokens_read_total").value
    assert eng.expert_tokens.sum() == computed * 2 * 2


def test_expert_counts_over_a_slotted_layout_agree_with_the_counters():
    """A model whose layers keep a paged pool or a state slot AND whose
    step returns tokens per expert: `moe_assignments` and
    `moe_active_experts` are what they are over a paged-only layout,
    each summing to its counter, beside the slots' own fields."""
    from paddle_tpu.models.conv_moe_lm import ConvMoELM
    model = ConvMoELM(
        vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2, ffn_dim=32,
        expert_dim=8, num_experts=8, top_k=2,
        layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=8)
    assert eng.cache.kinds == ["state", "paged", "state", "rows"]
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    steps = _spans(prof.get_events(), "engine.step")

    def total(field):
        return sum(st["args"][field] for st in steps)
    computed = sum(len(p) + 5 for p in PROMPTS)
    # two expert layers, two choices a token
    assert total("moe_assignments") == eng.obs.get(
        "ptpu_moe_assignments_total").value == computed * 2 * 2 \
        == eng.expert_tokens.sum()
    assert total("moe_active_experts") == eng.obs.get(
        "ptpu_moe_active_experts_total").value
    assert all(0 < st["args"]["moe_active_experts"]
               <= min(2 * 8, st["args"]["moe_assignments"]) for st in steps)
    assert total("ssm_tokens") == computed == eng.obs.get(
        "ptpu_ssm_tokens_scanned_total").value
    assert total("state_slots") == sum(
        st["args"]["decode_rows"] + st["args"]["chunk_rows"] for st in steps)
    assert eng._step_fn._cache_size() == 1


def test_a_share_of_an_expert_layer_counts_the_pairs_it_sends_away():
    """A model whose expert layers hold 4 of 16 experts (rank 2 of 4) in
    window and full attention layers: `moe_assignments` and
    `moe_active_experts` count the held experts alone, and
    `moe_assignments_away` the pairs routed to experts held elsewhere;
    held and away add up to every pair, and each sums to its counter
    (`ptpu_moe_pairs_total{where}`), beside the window layout's rows
    and keys of each kind."""
    from paddle_tpu.models.window_moe_lm import WindowMoELM
    model = WindowMoELM(
        vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2, head_dim=8,
        ffn_dim=32, expert_dim=8, num_experts=4, top_k=3,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"], window=4,
        expert_shards=4, expert_rank=2, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=8)
    assert eng.cache.kinds == ["window", "paged", "window", "rows"]
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    steps = _spans(prof.get_events(), "engine.step")

    def total(field):
        return sum(st["args"][field] for st in steps)
    computed = sum(len(p) + 5 for p in PROMPTS)
    pairs = eng.obs.get("ptpu_moe_pairs_total")
    # two expert layers, three choices a token, a quarter held here
    assert total("moe_assignments") + total("moe_assignments_away") \
        == computed * 2 * 3
    assert total("moe_assignments") == pairs.labels(where="held").value \
        == eng.obs.get("ptpu_moe_assignments_total").value \
        == eng.expert_tokens.sum() > 0
    assert total("moe_assignments_away") == pairs.labels(
        where="away").value > total("moe_assignments")
    assert total("moe_active_experts") == eng.obs.get(
        "ptpu_moe_active_experts_total").value
    assert eng.expert_tokens.shape == (2, 4)
    assert all(st["args"]["moe_active_experts"] <= 2 * 4 for st in steps)
    for kind in ("full", "window"):
        assert total("kv_rows_" + kind) == eng.obs.get(
            "ptpu_attn_kv_rows_total").labels(kind=kind).value
    assert total("attn_keys_window") == sum(
        min(p + 1, 4) for prompt in PROMPTS for p in range(len(prompt) + 5))
    assert eng._step_fn._cache_size() == 1

@pytest.mark.parametrize("layout", ["paged", "slots"])
def test_product_rows_agree_with_the_counters(model_and_vars, layout):
    """`product_rows` is the compact width every product of the step ran
    on (the chunk budget rounded to whole tiles, and a window a batch
    row: 8 + 4 of the flat packing's 8 + 4 x 8 here); the counter's
    `real` kind sums the steps' tokens, its `pad` kind the rest of each
    step's width. Over a paged layout and over one with state slots."""
    if layout == "paged":
        model, variables = model_and_vars
    else:
        from paddle_tpu.models.conv_moe_lm import ConvMoELM
        model = ConvMoELM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            ffn_dim=32, expert_dim=8, num_experts=8, top_k=2,
            layer_types=["conv", "full_attention", "conv"], max_len=64)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=4)
    assert (eng.product_rows, eng.flat_tokens) == (8 + 4, 8 + 4 * 8)
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    steps = _spans(prof.get_events(), "engine.step")
    assert all(st["args"]["product_rows"] == eng.product_rows
               for st in steps)
    real = sum(st["args"]["chunk_tokens"] + st["args"]["decode_rows"]
               for st in steps)
    assert real == sum(len(p) + 5 for p in PROMPTS)
    rows = eng.obs.get("ptpu_engine_product_rows_total")
    assert rows.labels(kind="real").value == real
    assert rows.labels(kind="pad").value == sum(
        st["args"]["product_rows"] for st in steps) - real > 0
    assert eng._step_fn._cache_size() == 1


def test_slot_counts_agree_with_the_counters():
    """A model whose layers keep three kinds of state: `ssm_tokens` is
    the real tokens through the scan a state-space layer, `state_slots`
    the rows' slots, `kv_rows_*` and `attn_keys_*` the cached rows read
    and the keys attended a layer of each kind of pool (the window's
    clipped to it), `window_blocks_released` the ring blocks given back;
    each span field sums to the counter of its name."""
    from paddle_tpu.models.hybrid_lm import HybridLM
    model = HybridLM(
        vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2, ffn_dim=32,
        layer_kinds=["mamba", "window", "mamba", "full", "gmu", "cross"],
        window=8, d_inner=32, d_state=4, d_conv=4, dt_rank=2, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=8)
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    steps = _spans(prof.get_events(), "engine.step")

    def total(field):
        return sum(st["args"][field] for st in steps)
    computed = sum(len(p) + 5 for p in PROMPTS)
    assert total("ssm_tokens") == computed == eng.obs.get(
        "ptpu_ssm_tokens_scanned_total").value
    assert total("state_slots") == sum(
        st["args"]["decode_rows"] + st["args"]["chunk_rows"] for st in steps)
    for kind in ("full", "window"):
        assert total("kv_rows_" + kind) == eng.obs.get(
            "ptpu_attn_kv_rows_total").labels(kind=kind).value
        assert total("attn_keys_" + kind) == eng.obs.get(
            "ptpu_attn_keys_total").labels(kind=kind).value
    # every query token at position p: p + 1 keys, min(p + 1, 8) in the
    # window
    assert total("attn_keys_full") == total("attn_keys") == sum(
        p + 1 for prompt in PROMPTS for p in range(len(prompt) + 5))
    assert total("attn_keys_window") == sum(
        min(p + 1, 8) for prompt in PROMPTS for p in range(len(prompt) + 5))
    assert total("kv_rows_full") == total("kv_tokens_read")
    assert 0 < total("kv_rows_window") < total("kv_rows_full")
    assert total("window_blocks_released") == eng.obs.get(
        "ptpu_kv_window_blocks_released_total").value \
        == eng.cache.window_blocks_released > 0
    # the gauge reads the slots held as a step ends
    assert eng.obs.get("ptpu_state_slots_in_use").value == 0
    eng.add_request(PROMPTS[0], max_new_tokens=4)
    eng.step()
    assert eng.obs.get("ptpu_state_slots_in_use").value == 1
    eng.run()
    assert eng._step_fn._cache_size() == 1


def test_a_layer_of_two_kinds_counts_its_scan_and_its_rows():
    """A model whose every layer keeps a paged pool AND a state slot
    (attention and a Mamba-2 mixer side by side): `ssm_tokens` is the
    real tokens through one layer's scan, `state_slots` the rows'
    slots, `kv_rows_full` and `attn_keys_full` one layer's cached rows
    read and keys attended; each span field sums to its counter."""
    from paddle_tpu.models.parallel_hybrid_lm import ParallelHybridLM
    model = ParallelHybridLM(
        vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2, head_dim=8,
        ffn_dim=32, num_layers=2, ssm_heads=4, ssm_head_dim=4, ssm_state=8,
        ssm_groups=2, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=8)
    prof.reset_profiler()
    eng.generate(PROMPTS, max_new_tokens=6)
    steps = _spans(prof.get_events(), "engine.step")

    def total(field):
        return sum(st["args"][field] for st in steps)
    computed = sum(len(p) + 5 for p in PROMPTS)
    assert total("ssm_tokens") == computed == eng.obs.get(
        "ptpu_ssm_tokens_scanned_total").value
    assert total("state_slots") == sum(
        st["args"]["decode_rows"] + st["args"]["chunk_rows"] for st in steps)
    assert total("kv_rows_full") == total("kv_tokens_read") == eng.obs.get(
        "ptpu_attn_kv_rows_total").labels(kind="full").value
    assert total("attn_keys_full") == total("attn_keys") == sum(
        p + 1 for prompt in PROMPTS for p in range(len(prompt) + 5))
    assert total("kv_rows_window") == total("window_blocks_released") == 0
    assert eng._step_fn._cache_size() == 1


def test_sparse_and_snapshot_counts_agree_with_the_counters(monkeypatch):
    """A model of block-sparse and lightning layers, served with state
    snapshots: `sparse_rows_read`, `sparse_keys`, `index_rows_read` and
    `blocks_selected` are what ONE sparse layer and kv head reads after
    selection, `la_tokens` the real tokens through a lightning layer,
    `snapshots_taken` / `snapshots_restored` / `snapshot_tokens_skipped`
    the cache's snapshot traffic of the step; each span field sums to
    the counter that goes with it. A decode row's `attn_cells` are the
    spans of its compacted table."""
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.sparse_linear_lm import SparseLinearLM
    # a span of one 4-token block
    monkeypatch.setattr(paged_attention, "_SPAN_KEYS", 4)
    sel = dict(dense_len=16, kernel=4, stride=2, block=4, init_blocks=1,
               local=8, topk=1)
    model = SparseLinearLM(
        vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2, head_dim=4,
        ffn_dim=32, mixer_types=["lightning-attn", "minicpm4"], la_heads=2,
        la_head_dim=8, sparse=sel, max_len=64, snapshot_tokens=8,
        snapshot_slots=4)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, max_prefill_tokens=8)
    assert eng.cache.snapshot_every == 8
    shared = list(range(1, 27))
    prof.reset_profiler()
    eng.generate([shared + [30, 31]], max_new_tokens=6)
    eng.generate([shared + [40]], max_new_tokens=6)     # hits 24 deep
    steps = _spans(prof.get_events(), "engine.step")

    def total(field):
        return sum(st["args"][field] for st in steps)
    rows = [(28, 6), (27, 6)]
    computed = (28 + 5) + (27 - 24 + 5)
    assert total("la_tokens") == computed == eng.obs.get(
        "ptpu_la_tokens_total").value
    assert total("state_slots") == sum(
        st["args"]["decode_rows"] + st["args"]["chunk_rows"] for st in steps)
    assert total("sparse_rows_read") == eng.obs.get(
        "ptpu_attn_kv_rows_total").labels(kind="sparse").value
    assert total("sparse_keys") == eng.obs.get(
        "ptpu_attn_keys_total").labels(kind="sparse").value
    assert total("index_rows_read") == eng.obs.get(
        "ptpu_attn_index_rows_total").value > 0
    assert total("blocks_selected") == eng.obs.get(
        "ptpu_attn_blocks_selected_total").value > 0
    # a query past dense_len keeps the first block, the best one and the
    # blocks of its 8 newest positions: fewer keys than its context
    dense = sum(p + 1 for n, new in rows for p in range(n + new - 1)) \
        - sum(p + 1 for p in range(24))
    assert 0 < total("sparse_keys") < dense == total("attn_keys")
    # a decode row past dense_len walks the spans of its kept keys,
    # fewer than its context's
    assert eng._cell_keys == 4
    alone = [st["args"] for st in steps
             if st["args"]["decode_rows"] == 1 and not st["args"]["chunk_rows"]]
    assert alone and all(a["attn_cells"] == -(-a["sparse_keys"] // 4)
                         for a in alone)
    assert all(a["attn_cells"] < -(-a["kv_tokens_read"] // 4) for a in alone)
    snaps = eng.obs.get("ptpu_state_snapshots_total")
    assert total("snapshots_restored") == 1 \
        == snaps.labels(event="restored").value
    assert total("snapshot_tokens_skipped") == 24 \
        == eng.cache.snapshot_tokens_skipped
    # taken at 8, 16 and 24 of the first prompt; a take is counted by
    # the step after it, so the last drain's spans may lack none here
    assert snaps.labels(event="taken").value == 3 \
        == eng.cache.snapshots_taken
    assert total("snapshots_taken") == 3
    assert eng.obs.get("ptpu_state_snapshots_held").value == 3
    flushes = _spans(prof.get_events(), "engine.flush")
    assert sum(f["args"]["restores"] for f in flushes) == 1
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
