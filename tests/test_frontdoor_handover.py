"""The front door's one wake-up a step (serve/frontend.py `_HandOver`).

`_Stream.push` on the engine loop's thread enqueues a frame at once and
only notes the stream; the loop wakes the consumers of all noted
streams with one `call_soon_threadsafe` an iteration, inside
`frontdoor.finish`, so a step of 31 rows costs one write to the event
loop's self-pipe and not 31. What must hold besides: every stream gets
its tokens in order and its done frame after its last token, and no
frame pushed outside a step (a refused submission's error, the aborts
of a shutdown and of the drain deadline) leaves a consumer parked.

The consumers here are the front door's own `_a_next_items` loop, or
its whole handler over a socket pair, on an event loop of the test's;
where a test counts a step at a time, the test's thread plays the
engine loop. A woken handler writes every frame it was handed in one
write, arms one timer a response (its deadline) and waits for the
drain only on a paused transport.
"""

import asyncio
import importlib
import json
import socket
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.models.transformer import CausalLM
from paddle_tpu.obs.metrics import MetricsRegistry
from paddle_tpu.serve.aio import AioConnection, AioRequest
from paddle_tpu.serve.frontend import ServeFrontend, _HandOver, _Stream, \
    _TOKEN_FRAME
from paddle_tpu.serve.sse import DONE_SENTINEL, sse_event, stream_completion

prof = importlib.import_module("paddle_tpu.profiler.profiler")

pytestmark = pytest.mark.serve

VOCAB = 61
PROMPTS = [[5, 9, 2, 7, 1, 3], [4, 4, 8], [11, 12, 13, 14, 15, 16, 17, 18],
           [21, 3], [9, 8, 7, 6, 5]]
WANTS = [6, 9, 7, 12, 5]
ABORTED = ("done", "cancelled", [], None)


@pytest.fixture(scope="module")
def model_and_vars():
    model = CausalLM(vocab=VOCAB, model_dim=16, num_heads=4, num_layers=2,
                     ffn_dim=32, dropout=0.0, max_len=512)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 256)
    kw.setdefault("registry", MetricsRegistry())
    return ServeEngine(model, variables, **kw)


def _params(prompt, want, **kw):
    """What `_parse_completion` hands `_a_post` for this body."""
    return dict({"prompt": prompt, "max_new_tokens": want,
                 "temperature": 0.0, "top_k": 0, "seed": 0, "eos_id": None,
                 "deadline_ms": None, "n": 1, "best_of": 1}, **kw)


class _ParkingEvent(asyncio.Event):
    """A stream's event that says when its consumer has come to wait."""

    def __init__(self):
        super().__init__()
        self.parked = threading.Event()

    async def wait(self):
        self.parked.set()
        return await super().wait()


async def _drain(stream, timeout=60.0):
    """A handler's loop without the socket: every item up to the one
    that ends the stream (None at its deadline)."""
    loop = asyncio.get_running_loop()
    deadline = loop.call_at(loop.time() + timeout, stream.expire)
    items = []
    try:
        while True:
            items.extend(await ServeFrontend._a_next_items(stream) or [None])
            if items[-1] is None or items[-1][0] in ("done", "error",
                                                     "gone"):
                return items
    finally:
        deadline.cancel()


class Consumers:
    """An event loop on a thread of its own, as the acceptor's is."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()

    def open(self, handover, params, cls=_Stream):
        """An attached stream whose consumer is already draining it."""
        stream = cls(params)
        stream.attach(self.loop, _ParkingEvent(), handover)
        return stream, asyncio.run_coroutine_threadsafe(_drain(stream),
                                                        self.loop)

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
        assert not self.thread.is_alive()
        self.loop.close()


@pytest.fixture
def consumers():
    c = Consumers()
    yield c
    c.close()


def _submit(fe, stream):
    """What `_a_post` does once the stream is attached."""
    fe._submit.append(stream)
    fe._work.set()


def _wait_until(pred, timeout=60.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _counter(eng, name, **labels):
    fam = eng.obs.get(name)
    return fam.labels(**labels) if labels else fam


def test_a_step_is_one_wakeup_whatever_its_rows(model_and_vars, consumers):
    """Five streams over a batch of four, the test's thread as the
    engine loop: a step that emitted tokens raises the wake-ups by
    exactly one, a step that emitted none by none; a done frame rides
    its last token's wake-up; every stream reads an undisturbed
    engine's tokens, in order, then its done frame."""
    fe = ServeFrontend(_engine(*model_and_vars), warmup=False)
    eng = fe.engine
    fe._handover.tid = threading.get_ident()
    opened = [consumers.open(fe._handover, _params(p, w))
              for p, w in zip(PROMPTS, WANTS)]
    for stream, _ in opened:
        fe._submit.append(stream)
    fe._drain_control_queues()
    generated = _counter(eng, "ptpu_serve_tokens_total", kind="generated")
    wakeups = _counter(eng, "ptpu_frontdoor_wakeups_total")
    prof.reset_profiler()
    rows = []
    while eng.scheduler.has_work():
        g0, w0 = generated.value, wakeups.value
        assert fe._step_once()
        fe._flush_finished()
        rows.append(int(generated.value - g0))
        assert wakeups.value - w0 == (1 if rows[-1] else 0), rows
        assert not fe._handover.pending
    assert max(rows) >= 4 and sum(rows) == sum(WANTS)
    assert wakeups.value == sum(1 for r in rows if r) < sum(WANTS)

    ref = _engine(*model_and_vars)
    reqs = [ref.add_request(p, max_new_tokens=w)
            for p, w in zip(PROMPTS, WANTS)]
    ref.run()
    for (_, fut), req, want in zip(opened, reqs, WANTS):
        items = fut.result(timeout=60)
        assert [i[0] for i in items] == ["token"] * want + ["done"]
        tokens = [i[1] for i in items[:-1]]
        assert tokens == ServeEngine._generated_of(req)
        assert items[-1] == ("done", "length", tokens, None)

    # the span says how many streams each hand-over woke: a stream a
    # step it got a token in, so as many as tokens here (no row drafts)
    finishes = [e["args"] for e in prof.get_events()
                if e["name"] == "frontdoor.finish"]
    assert [a["woken"] for a in finishes] == rows
    assert sum(a["closed"] for a in finishes) == len(PROMPTS)


def test_push_wakes_at_once_only_off_the_engine_loops_thread(consumers):
    """From the engine loop's thread a push is enqueued and noted, and
    its consumer sleeps until the flush; from any other thread it wakes
    on the spot, as before."""
    handover = _HandOver()
    handover.tid = threading.get_ident()
    noted, fut_noted = consumers.open(handover, {})
    direct, fut_direct = consumers.open(handover, {})
    assert noted.ev.parked.wait(10) and direct.ev.parked.wait(10)
    done = ("done", "length", [], None)

    noted.push(("token", 7, 0))
    noted.push(done)                      # one stream, noted once
    assert handover.pending == {noted} and noted.q.qsize() == 2
    other = threading.Thread(target=direct.push, args=(done,))
    other.start()
    other.join(10)
    assert fut_direct.result(timeout=10) == [done]
    assert handover.pending == {noted}
    assert not fut_noted.done()           # enqueued, and nobody told it

    assert handover.flush() == 1 and not handover.pending
    assert fut_noted.result(timeout=10) == [("token", 7, 0), done]
    assert handover.flush() == 0


def test_unattached_stream_is_a_plain_queue():
    """The warm-up's stream has no event loop: a push is a `put`, the
    drain a blocking `get`, and nothing is noted for a hand-over."""
    stream = _Stream({})
    stream.push(("token", 3, 0))
    assert stream.q.get(timeout=1) == ("token", 3, 0)
    assert stream.handover is None and stream.ev is None


class _LoggedStream(_Stream):
    log = None

    def push(self, item):
        self.log.append("push")
        super().push(item)


@pytest.fixture
def loop_log(monkeypatch):
    """The engine loop's iterations ("control" opens one), pushes and
    hand-overs in the order its thread made them."""
    log = []
    control, flush = ServeFrontend._drain_control_queues, _HandOver.flush

    def logged_control(self):
        log.append("control")
        control(self)

    def logged_flush(self, step=None):
        if self.pending:
            log.append(("flush", len(self.pending)))
        return flush(self, step)
    monkeypatch.setattr(ServeFrontend, "_drain_control_queues",
                        logged_control)
    monkeypatch.setattr(_HandOver, "flush", logged_flush)
    monkeypatch.setattr(_LoggedStream, "log", log)
    return log


def test_refused_submission_wakes_its_parked_consumer_in_the_same_iteration(
        model_and_vars, consumers, loop_log):
    """The warm-up rides the loop with no event loop attached; then an
    idle engine refuses a submission: the error is pushed outside any
    step, and handed over before the loop's next look at its queues."""
    fe = ServeFrontend(_engine(*model_and_vars)).start()
    try:
        assert fe._warm and not fe._handover.pending
        assert fe.engine._m_compiles.value == 1
        stream, fut = consumers.open(fe._handover, _params([], 4),
                                     cls=_LoggedStream)
        assert stream.ev.parked.wait(10)
        mark = len(loop_log)
        _submit(fe, stream)
        assert fut.result(timeout=10) == [("error", "empty prompt")]
        tail = loop_log[mark:]
        pushed = tail.index("push")
        assert tail[pushed + 1] == ("flush", 1), tail[:pushed + 3]
        assert _counter(fe.engine, "ptpu_frontdoor_wakeups_total").value == 1
        assert _counter(fe.engine, "ptpu_engine_steps_total").value == 0
    finally:
        fe.stop()


@pytest.mark.parametrize("how", ["shutdown", "drain_deadline"])
def test_abort_reaches_streaming_and_parked_consumers(model_and_vars,
                                                      consumers, how):
    """Two long requests fill the batch and a third waits in the
    scheduler's queue, its consumer parked without a frame. Both ways
    out of the loop abort all three, and the loop's last act hands the
    aborts over: no consumer is left waiting on a thread that ended."""
    fe = ServeFrontend(
        _engine(*model_and_vars, max_batch_size=2),
        drain_deadline_s=0.0 if how == "drain_deadline" else 30.0).start()
    opened = [consumers.open(fe._handover, _params(p, 400))
              for p in PROMPTS[:3]]
    for stream, _ in opened:
        _submit(fe, stream)
    assert _wait_until(lambda: len(fe._active) == 3
                       and fe.engine.scheduler.queue_depth == 1)
    waiting = [s for s, _ in opened if s.req in fe.engine.scheduler.waiting]
    assert len(waiting) == 1 and waiting[0].ev.parked.wait(10)
    if how == "shutdown":
        fe.stop()
    else:
        fe.begin_drain()
        assert fe.wait(timeout=30) == 75
        fe._teardown()
        assert _counter(fe.engine,
                        "ptpu_serve_drain_cancelled_total").value == 3
    assert fe._stopped.is_set() and not fe._handover.pending
    for stream, fut in opened:
        items = fut.result(timeout=10)
        assert items[-1] == ABORTED
        assert all(i[0] == "token" for i in items[:-1])
        if stream is waiting[0]:
            assert items == [ABORTED]


def test_parallel_candidates_keep_their_order_beside_other_streams(
        model_and_vars):
    """A best-of group pushes several candidates' tokens on ONE stream
    in a step: each candidate's frames keep their order (`pos` counts
    from 0 without a gap and the streamed tokens are the done frame's),
    while three plain streams decode beside it; no step made more than
    one wake-up."""
    fe = ServeFrontend(_engine(*model_and_vars, max_batch_size=8)).start()
    try:
        plain = {}

        def _plain(i):
            s = stream_completion(fe.url, {"prompt": PROMPTS[i],
                                           "max_new_tokens": 24})
            plain[i] = ([ev["token"] for ev in s.events() if "token" in ev],
                        s.done)
        threads = [threading.Thread(target=_plain, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        s = stream_completion(fe.url, {
            "prompt": [2, 3, 4, 5], "max_new_tokens": 20,
            "temperature": 0.7, "seed": 3, "n": 3, "best_of": 4})
        streamed, final = {}, None
        for ev in s.events():
            if "token" in ev:
                assert final is None          # done comes last
                got = streamed.setdefault(ev["index"], [])
                assert ev["pos"] == len(got)
                got.append(ev["token"])
            elif ev.get("done"):
                final = ev
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        assert s.done and sorted(streamed) == [0, 1, 2]
        assert {c["index"]: c["tokens"] for c in final["candidates"]} == \
            streamed
        ref = _engine(*model_and_vars)
        for i, (tokens, done) in plain.items():
            assert done and tokens == ref.generate(
                [PROMPTS[i]], max_new_tokens=24)[0]
        eng = fe.engine
        wakeups = _counter(eng, "ptpu_frontdoor_wakeups_total").value
        tokens = _counter(eng, "ptpu_serve_tokens_total",
                          kind="generated").value
        assert tokens == 3 * 24 + 4 * 20
        assert 0 < wakeups <= _counter(eng, "ptpu_engine_steps_total").value
        assert wakeups < tokens / 2
    finally:
        fe.stop()



class _Wire(AioConnection):
    """The front door's connection over a socket pair, its writes
    logged; the test's thread reads the client's half."""

    def __init__(self, reader, writer):
        super().__init__(reader, writer)
        self.writes = []

    async def write(self, data):
        self.writes.append(data)
        await super().write(data)


def _respond(fe, consumers, **body):
    """A POST of `body` to the front door's own handler, on the
    consumers' loop over a socket pair; the test's thread, as the engine
    loop, admits the stream it submitted. Returns the wire, the client's
    socket, the handler's future and the stream, once the handler has
    written the response's head."""
    server, client = socket.socketpair()
    client.settimeout(30)

    async def _open():
        return _Wire(*await asyncio.open_connection(sock=server))
    wire = asyncio.run_coroutine_threadsafe(_open(), consumers.loop).result(10)
    request = AioRequest("POST", "/v1/completions", {},
                         json.dumps(dict({"prompt": [1, 2, 3],
                                          "max_new_tokens": 8},
                                         **body)).encode())
    fut = asyncio.run_coroutine_threadsafe(fe._a_dispatch(request, wire),
                                           consumers.loop)
    assert _wait_until(lambda: fe._submit)
    stream = fe._submit[0]
    fe._drain_control_queues()
    assert stream.req is not None
    if body.get("stream", True):
        assert _wait_until(lambda: wire.writes)
    return wire, client, fut, stream


def _hand_over(fe):
    """The engine loop's hand-over, then the wait for its frames."""
    woken = fe._hand_over()
    assert fe._handover.delivered.wait(10)
    return woken


def _received(wire, client, consumers):
    """Everything the client got once the handler closed the wire."""
    asyncio.run_coroutine_threadsafe(wire.close(), consumers.loop).result(10)
    data = b""
    while chunk := client.recv(65536):
        data += chunk
    client.close()
    return data


def _frames(turn, pos=None):
    """`sse_event`'s bytes for a turn of (token, candidate) pairs."""
    pos = {} if pos is None else pos
    out = []
    for tok, cand in turn:
        out.append(sse_event({"token": tok, "index": cand,
                              "pos": pos.get(cand, 0)}))
        pos[cand] = pos.get(cand, 0) + 1
    return b"".join(out)


# one hand-over's tokens: a list of (token, candidate) a stream
TURNS = {
    "k1": [[(5, 0)]],
    "k3": [[(5, 0), (9, 0), (2, 0)]],
    "k8": [[(t, 0) for t in (5, 9, 2, 7, 1, 3, 60, 0)]],
    "n2_beside_another": [[(5, 0), (9, 1), (2, 0), (7, 1), (4, 1)],
                          [(3, 0), (4, 0)]],
}
DONE = ("done", "length", [], None)


@pytest.mark.parametrize("case", sorted(TURNS))
def test_a_hand_over_reaches_each_socket_in_one_write(model_and_vars,
                                                      consumers, case):
    """The frames a hand-over gives a stream leave in one write, in
    order; with n = 2 its candidates' frames keep their order (`pos`
    counts a candidate's own) beside another stream's. The hand-over's
    `frontdoor.deliver` counts the frames and the writes that carried
    them; the done frame leaves in a write of its own hand-over."""
    fe = ServeFrontend(_engine(*model_and_vars), warmup=False)
    fe._handover.tid = threading.get_ident()
    turns = TURNS[case]
    opened = [_respond(fe, consumers,
                       **({"n": 2} if any(c for _, c in turn) else {}))
              for turn in turns]
    prof.reset_profiler()
    for (_, _, _, stream), turn in zip(opened, turns):
        for tok, cand in turn:
            stream.push(("token", tok, cand))
    assert _hand_over(fe) == len(turns)
    for (wire, _, _, stream), turn in zip(opened, turns):
        assert wire.writes[1:] == [_frames(turn)]
        assert stream.streamed == len(turn)
    delivers = [e["args"] for e in prof.get_events()
                if e["name"] == "frontdoor.deliver"]
    assert len(delivers) == 1
    assert (delivers[0]["streams"], delivers[0]["frames"],
            delivers[0]["writes"]) == (len(turns), sum(map(len, turns)),
                                       len(turns))

    for _, _, _, stream in opened:
        stream.push(DONE)
    assert _hand_over(fe) == len(turns)
    for (wire, client, fut, stream), turn in zip(opened, turns):
        fut.result(timeout=10)
        done = sse_event({"done": True, "reason": "length", "tokens": [],
                          "req_id": stream.req.req_id,
                          "trace_id": stream.params["trace_id"]})
        assert wire.writes[2:] == [done + sse_event(DONE_SENTINEL)]
        assert _received(wire, client, consumers) == \
            b"".join(wire.writes)
    assert fe._handover.written == sum(map(len, turns)) + len(turns)
    assert fe._handover.writes == 2 * len(turns)


def test_a_stream_arms_one_timer_and_waits_for_no_drain(
        model_and_vars, consumers, monkeypatch):
    """A stream of 64 tokens, a hand-over each, arms one timer on its
    event loop, its deadline, and cancels it at its end: no write waits
    under `asyncio.wait_for` while the transport is not paused."""
    fe = ServeFrontend(_engine(*model_and_vars), warmup=False)
    fe._handover.tid = threading.get_ident()
    loop = consumers.loop
    timers, waits = [], []
    call_at, wait_for = loop.call_at, asyncio.wait_for

    def counted_call_at(*args, **kw):
        timers.append(call_at(*args, **kw))
        return timers[-1]

    def counted_wait_for(aw, timeout):
        waits.append(timeout)
        return wait_for(aw, timeout)
    monkeypatch.setattr(loop, "call_at", counted_call_at)
    monkeypatch.setattr(asyncio, "wait_for", counted_wait_for)
    wire, client, fut, stream = _respond(fe, consumers, max_new_tokens=64)
    tokens = [(t % VOCAB, 0) for t in range(64)]
    for tok, cand in tokens:
        stream.push(("token", tok, cand))
        assert _hand_over(fe) == 1
    stream.push(DONE)
    _hand_over(fe)
    fut.result(timeout=10)
    assert len(timers) == 1 and timers[0].cancelled()
    assert waits == []
    assert len(wire.writes) == 1 + 64 + 1
    assert b"".join(wire.writes[1:-1]) == _frames(tokens)
    assert _counter(fe.engine, "ptpu_frontdoor_drain_waits_total").value == 0
    monkeypatch.undo()
    assert _received(wire, client, consumers) == b"".join(wire.writes)


@pytest.mark.parametrize("streaming", [True, False],
                         ids=["stream", "aggregate"])
def test_an_engine_that_never_answers_times_out_and_cancels(
        model_and_vars, consumers, monkeypatch, streaming):
    """A response whose engine never answers ends at its deadline, and
    its request is cancelled: a stream after its head, an aggregate
    response with a 504."""
    fe = ServeFrontend(_engine(*model_and_vars), warmup=False)
    fe._handover.tid = threading.get_ident()
    monkeypatch.setattr(fe, "_stream_timeout", lambda params: 0.3)
    t0 = time.monotonic()
    wire, client, fut, stream = _respond(fe, consumers, stream=streaming)
    fut.result(timeout=10)
    assert time.monotonic() - t0 >= 0.3
    assert stream.expired and list(fe._cancel) == [stream]
    fe._drain_control_queues()
    assert not fe._active and not fe.engine.scheduler.has_work()
    got = _received(wire, client, consumers)
    if streaming:
        assert got == wire.writes[0] and b"text/event-stream" in got
    else:
        assert got.startswith(b"HTTP/1.0 504 ")
        assert got.endswith(b'{"error": "timed out"}\n')


@pytest.mark.parametrize("tok,cand,pos", [
    (0, 0, 0), (7, 0, 0), (60, 1, 0), (50256, 0, 17), (151935, 3, 0),
    (2 ** 31 - 1, 7, 4095)])
def test_token_frame_template_is_sse_events_bytes(tok, cand, pos):
    """The wire format: a token frame from the template is byte for byte
    what `sse_event` makes of the frame's dict."""
    assert _TOKEN_FRAME % (tok, cand, pos) == \
        sse_event({"token": tok, "index": cand, "pos": pos})
