"""Closed-loop serving: a fixed number of callers, each sending its next
request when its last stream has ended.

The replica is built as `python -m paddle_tpu.serve.replica --model-dir`
builds it (`build_parser` -> `build_frontend` -> `start()`), in this
process, and driven over HTTP at POST /v1/completions by
`sse.stream_completion`; every token frame is stamped on the client's
clock. One thing differs from the command line: the weights are made on
the device by `benchmarks/weights.py` and handed to the engine in
memory, where `--model-dir` would read a 1.4 to 3 GB export back from
disk (a run writes little to disk, and the reference may use nothing the
program made).
"""

from __future__ import annotations

import gc
import threading
import time
from unittest import mock

import numpy as np

from benchmarks import reference, tracing, weights
from benchmarks.common import (build_model, check, check_tree, held_checks,
                               log)
from benchmarks.traffic import RequestSource

COUNTERS = {
    "generated": 'ptpu_serve_tokens_total{kind="generated"}',
    "prefill": 'ptpu_serve_tokens_total{kind="prefill"}',
    "cached": 'ptpu_serve_tokens_total{kind="cached"}',
    "steps": "ptpu_engine_steps_total",
    "kv_prompt": "ptpu_kv_prompt_tokens_total",
    "kv_hit": "ptpu_kv_hit_tokens_total",
    "preemptions": "ptpu_sched_preemptions_total",
}

# the numbers this runner compares with the reference, each needing an
# entry in the cell's limits file, and where `token_gaps` has them
HELD = {"token_gap_max": "gap_max", "token_gap_mean": "gap_mean"}


class Record:
    """One request as its caller saw it."""

    def __init__(self, index: int, prompt: list, want: int):
        self.index, self.prompt, self.want = index, prompt, want
        self.sent = None
        self.times, self.tokens = [], []
        self.final, self.done, self.error = None, False, None

    def complete(self) -> bool:
        return (self.error is None and self.done
                and (self.final or {}).get("reason") == "length"
                and len(self.tokens) == self.want)


def _caller(url: str, source: RequestSource, records: list) -> None:
    from paddle_tpu.serve.sse import stream_completion
    while True:
        i = source.take()
        if i is None:
            return
        prompt, want = source.get(i)
        rec = Record(i, prompt, want)
        rec.sent = time.perf_counter()
        try:
            s = stream_completion(
                url, {"prompt": prompt, "max_new_tokens": want}, timeout=180)
            if s.status != 200:
                rec.error = f"status {s.status}"
                s.close()
            else:
                for ev in s.events():
                    now = time.perf_counter()
                    if "token" in ev:
                        rec.tokens.append(ev["token"])
                        rec.times.append(now)
                    if ev.get("done"):
                        rec.final = ev
                rec.done = s.done
        except Exception as e:   # thread boundary: recorded, counted failed
            rec.error = repr(e)
        records.append(rec)


def build_frontend(config: dict, params):
    from paddle_tpu.engine.engine import ServeEngine
    from paddle_tpu.serve import replica
    model = build_model(config)
    check_tree(model, params)
    s = config["serve"]

    def from_memory(cls, model_dir, **kw):
        kw.setdefault("max_seq_len", config["n_positions"])
        return cls(model, {"params": params}, **kw)

    args = replica.build_parser().parse_args([
        "--model-dir", "in-memory", "--port", "0",
        "--block-size", str(s["block_size"]),
        "--num-blocks", str(s["num_blocks"]),
        "--max-batch-size", str(s["max_batch_size"]),
        "--max-prefill-tokens", str(s["max_prefill_tokens"]),
        "--tile-q", str(s["tile_q"]),
        # closed loops have no admission queue to shed from
        "--slo-ttft-ms", "1e9", "--slo-tpot-ms", "1e9",
        "--slo-queue-wait-ms", "1e9"])
    with mock.patch.object(ServeEngine, "from_saved_model",
                           classmethod(from_memory)):
        frontend = replica.build_frontend(args)
    frontend.start()     # warms the one compiled step, then listens
    return frontend


def scrape(url: str) -> dict:
    from paddle_tpu.serve.sse import http_get, parse_prometheus_values
    status, body = http_get(url + "/metrics")
    if status != 200:
        raise RuntimeError(f"/metrics gave {status}")
    return parse_prometheus_values(body)


def lower_step(eng, spec, pool_spec=None):
    """The engine's one step lowered for operands of the engine's own
    shapes (chip_smoke.py's reading). `spec` turns an array into its
    ShapeDtypeStruct; `pool_spec` may give the pools another size."""
    import jax
    import jax.numpy as jnp
    t, nt, b = eng.flat_tokens, eng.num_tiles, eng.max_batch_size

    def i32(*shape):
        return spec(jax.ShapeDtypeStruct(shape, jnp.int32))
    return eng._step_fn.lower(
        jax.tree.map(spec, eng.variables), i32(t), i32(t),
        jax.tree.map(pool_spec or spec, eng.cache.pools),
        jax.tree.map(spec, eng.cache.qpools),
        jax.tree.map(spec, eng.cache.qscales),
        i32(b + 1, eng.max_blocks_per_seq), i32(b + 1), i32(b + 1),
        i32(nt), i32(nt), i32(t), i32(b, eng.spec_len))


def step_has_kernel(eng) -> bool:
    """A Pallas TPU kernel shows in the step's program as a
    `tpu_custom_call`; the XLA tiers do not."""
    import jax
    return "tpu_custom_call" in lower_step(
        eng, lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)).as_text()


def percentile(values, q: float) -> float:
    """The q-th percentile, nearest rank: a value that was measured."""
    ordered = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(ordered))))
    return ordered[rank - 1]


def window_metrics(records: list, t0: float, t1: float) -> dict:
    """The end-to-end metrics of the window [t0, t1], over everything:
    all requests sent in it, all gaps and all tokens received in it."""
    seconds = t1 - t0
    sent = [r for r in records if t0 <= r.sent < t1]
    failed = [r for r in sent if not r.complete()]
    worst = seconds * 1e3
    ttft = [(r.times[0] - r.sent) * 1e3 if r.times and r.complete()
            else worst for r in sent]
    gaps, received = [], 0
    for r in records:
        for a, b in zip(r.times, r.times[1:]):
            if t0 <= b <= t1:
                gaps.append((b - a) * 1e3)
        received += sum(1 for t in r.times if t0 <= t <= t1)
    return {
        "attempted": len(sent), "failed": len(failed),
        "failures": [f"request {r.index}: {r.error} done={r.done} "
                     f"tokens={len(r.tokens)}/{r.want}" for r in failed[:5]],
        "end_to_end": {
            "ttft_p95_ms": percentile(ttft, 95) if ttft else worst,
            "itl_p95_ms": percentile(gaps, 95) if gaps else worst,
            "serve_tok_s": received / seconds,
        },
        "ttft_ms": ttft, "gaps_ms": gaps,
    }


def contexts(records: list, t0: float, t1: float, cached_share: float):
    """For `serve_mfu_pct`: the sums of keys attended (position + 1) over
    the tokens generated in the window, exactly, and over the prompt
    tokens computed for requests sent in it, taking the uncached share
    of each prompt to be its tail."""
    generated = prefill = 0.0
    for r in records:
        n = len(r.prompt)
        generated += sum(n + k + 1 for k, t in enumerate(r.times)
                         if t0 <= t <= t1)
        if t0 <= r.sent < t1:
            first = int(round(n * cached_share))
            prefill += (n * (n + 1) - first * (first + 1)) / 2.0
    return {"generated_context_sum": generated,
            "prefill_context_sum": prefill}


def token_gaps(config: dict, mix: dict, seed: int, sample: list,
               control) -> dict:
    """Run the reference once over each sampled prompt with its served
    tokens. Returns the widest and the mean gap by which a served
    token's logit lies below the reference's best and, with `control`,
    the same of the token that the lower precision puts first."""
    import jax.numpy as jnp
    n_pos, n_head = config["n_positions"], config["n_head"]
    shared = (mix.get("shared") or {}).get("tokens", 0)
    rows_n = mix["answer"]["max"]
    width = min(n_pos, -(-(shared + mix["prompt"]["max"] + rows_n) // 128)
                * 128)
    g = mix["check_requests"]
    tokens = np.zeros((g, width), np.int32)
    rows = np.zeros((g, rows_n), np.int32)
    served = np.zeros((g, rows_n), np.int32)
    real = np.zeros((g, rows_n), bool)
    for i, r in enumerate(sample):
        seq = (r.prompt + r.tokens)[:width]
        tokens[i, : len(seq)] = seq
        n = len(r.tokens)
        rows[i, :n] = len(r.prompt) - 1 + np.arange(n)
        served[i, :n] = r.tokens
        real[i, :n] = True
    params = weights.make_params(config, seed)
    stacked, rest = reference.stack_layers(params, config["n_layer"])
    del params
    got, low = reference.served_gaps(
        stacked, rest, jnp.asarray(tokens), jnp.asarray(rows),
        jnp.asarray(served), n_head, n_pos, control)
    got, low = np.asarray(got)[real], np.asarray(low)[real]
    out = {"compared_tokens": int(real.sum()),
           "served_gap_max": float(got.max()),
           "served_gap_mean": float(got.mean()),
           "served_tokens_below_best": int((got > 0).sum())}
    if control:
        out.update(control_gap_max=float(low.max()),
                   control_gap_mean=float(low.mean()),
                   control_tokens_below_best=int((low > 0).sum()))
    return out


def pick_sample(records: list, t0: float, t1: float, k: int, seed: int):
    """k finished requests of the window drawn from the seed, the
    longest among them."""
    done = sorted((r for r in records if t0 <= r.sent < t1 and r.complete()
                   and r.times[-1] <= t1), key=lambda r: r.index)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    others = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    picked = rng.permutation(len(others))[: max(0, k - 1)]
    return [longest] + [others[i] for i in sorted(picked)]


def run(ctx) -> dict:
    import jax

    from paddle_tpu.resilience.errors import PREEMPT_EXIT_CODE
    config, mix = ctx.config, ctx.traffic
    ctx.phase("jax up")
    params = weights.make_params(config, ctx.seed)
    frontend = build_frontend(config, params)
    del params
    ctx.phase("replica warm and listening")
    eng, url = frontend.engine, frontend.url
    source = RequestSource(mix, config["vocab_size"], ctx.seed)
    records = []
    if ctx.fault == "token_altered":   # tests: a token altered at its source
        from paddle_tpu.engine import engine as engine_mod
        sample = engine_mod._sample
        vocab = config["vocab_size"]
        mock.patch.object(
            engine_mod, "_sample",
            lambda logits, req, pos: (sample(logits, req, pos) + 1) % vocab
        ).start()
    callers = [threading.Thread(target=_caller, daemon=True,
                                args=(url, source, records))
               for _ in range(mix["callers"])]
    for c in callers:
        c.start()
    time.sleep(mix["warm_s"])     # the loop reaches its steady state

    before = scrape(url)
    ctx.phase("closed loop warm")
    t0 = ctx.open_window()
    tracer = None
    if ctx.trace:
        lead = ctx.seconds - tracing.slice_seconds(ctx.seconds)
        time.sleep(max(0.0, t0 + lead - time.perf_counter()))
        tracer = ctx.tracer()
        tracer.start()
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    t1 = time.perf_counter()
    if tracer:
        tracer.stop()
    after = scrape(url)
    source.close()
    deadline = time.monotonic() + 120
    for c in callers:
        c.join(max(0.0, deadline - time.monotonic()))
    stuck = sum(c.is_alive() for c in callers)

    peak = ctx.memory_peak_bytes()
    counters = {k: after.get(s, 0.0) - before.get(s, 0.0)
                for k, s in COUNTERS.items()}
    compiles = scrape(url).get("ptpu_engine_compiles")
    # how much of the reserved pool live sequences hold (cached-free
    # blocks are free): gauges the engine keeps anyway, read at no cost
    # to the window
    occupancy = {"at_start": before.get("ptpu_kv_occupancy"),
                 "at_end": after.get("ptpu_kv_occupancy"),
                 "peak_since_start": eng.peak_occupancy}
    has_kernel = ctx.toy or step_has_kernel(eng)
    frontend.begin_drain()
    exit_code = frontend.wait(timeout=120)
    frontend._teardown()
    mock.patch.stopall()
    del eng, frontend
    gc.collect()

    m = window_metrics(records, t0, t1)
    for f in m["failures"]:
        log("failed:", f)
    log(f"window {t1 - t0:.3f}s: {m['attempted']} requests sent, "
        f"{len(m['gaps_ms'])} gaps; counters {counters}")
    for name in ("ttft_ms", "gaps_ms"):
        if m[name]:
            log(f"{name}: " + ", ".join(
                f"p{q} {percentile(m[name], q):.1f}"
                for q in (10, 50, 75, 90, 95, 99, 100)))
    log(f"share of the pool's blocks held by live sequences: {occupancy}")
    observed = {"counters": counters, "window_s": t1 - t0,
                "kv_occupancy": occupancy,
                "busy_s": None, "trace_window_s": None, "trace": None}
    cached_share = (counters["kv_hit"] / counters["kv_prompt"]
                    if counters["kv_prompt"] else 0.0)
    observed.update(contexts(records, t0, t1, cached_share))
    if tracer:
        observed.update(tracer.reduce())

    sample = pick_sample(records, t0, t1, mix["check_requests"], ctx.seed)
    t_ref = time.perf_counter()
    gaps = (token_gaps(config, mix, ctx.seed, sample, ctx.control) if sample
            else {"compared_tokens": 0})
    log(f"compared {gaps['compared_tokens']} served tokens of "
        f"{len(sample)} requests in {time.perf_counter() - t_ref:.1f} s: "
        f"{gaps}")
    # with --control the lower precision's tokens stand in the served
    # tokens' place, before the same limits
    side = "control" if ctx.control else "served"
    checks = held_checks(ctx.limits, {name: gaps.get(f"{side}_{key}")
                                      for name, key in HELD.items()})
    checks += [
        check("requests_failed", m["failed"] + stuck, 0),
        check("engine_compiles", compiles, 1, ok=compiles == 1),
        check("kernel_in_step", int(has_kernel), 1, ok=has_kernel),
        check("drain_exit_code", exit_code, PREEMPT_EXIT_CODE,
              ok=exit_code == PREEMPT_EXIT_CODE),
    ]
    return {"attempted": m["attempted"], "failed": m["failed"] + stuck,
            "end_to_end": m["end_to_end"], "observed": observed,
            "memory_peak_bytes": peak, "checks": checks}
