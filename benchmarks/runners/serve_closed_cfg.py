"""Closed-loop serving of a configuration that names its own modules.

`serve_closed.py` with one thing taken from the configuration's file
where that runner has it built in: which module makes the weights
(`weights`), which is the plain reference (`reference`: it is given the
configuration and the seed and makes its own weights, a layer at a time)
and how long a sequence the replica serves (`serve.max_seq_len`). The
caller, the window, the sample and the scrape are `serve_closed`'s own,
by import; the replica is built the same way (`build_parser` ->
`build_frontend` -> `start()`, the weights handed over in memory) and
driven over HTTP.

It also reads what the newer program counts: the attention's and the
experts' counters, the load of each expert (standard error), and, in a
traced run, the device time of the operations that the compiled step's
text puts under the program's named scopes (`scope_reduce.py`). A program without them reads as zeros and
None, and the readers leave their metrics out.
"""

from __future__ import annotations

import gc
import importlib
import threading
import time
from unittest import mock

import numpy as np

from benchmarks import scope_reduce, tracing
from benchmarks.common import (build_model, check, check_tree, held_checks,
                               load_module, log)
from benchmarks.traffic import RequestSource

closed = load_module("runners", "serve_closed")

COUNTERS = dict(
    closed.COUNTERS,
    kv_tokens_read="ptpu_attn_kv_tokens_read_total",
    attn_keys="ptpu_attn_keys_attended_total",
    moe_assignments="ptpu_moe_assignments_total",
    moe_active_experts="ptpu_moe_active_experts_total")
HELD = closed.HELD
SCOPES = ("moe_experts", "mla_attention")


def build_frontend(config: dict, model, params):
    from paddle_tpu.engine.engine import ServeEngine
    from paddle_tpu.serve import replica
    check_tree(model, params)
    s = config["serve"]

    def from_memory(cls, model_dir, **kw):
        kw.setdefault("max_seq_len", s["max_seq_len"])
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


def token_gaps(config: dict, mix: dict, seed: int, sample: list,
               control) -> dict:
    """`serve_closed.token_gaps` through the configuration's own
    reference: each sampled prompt with its served tokens, once."""
    import jax.numpy as jnp
    reference = importlib.import_module(config["reference"])
    shared = (mix.get("shared") or {}).get("tokens", 0)
    rows_n = mix["answer"]["max"]
    width = min(config["serve"]["max_seq_len"],
                -(-(shared + mix["prompt"]["max"] + rows_n) // 128) * 128)
    g = len(sample)
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
    got, low = reference.served_gaps(
        config, seed, jnp.asarray(tokens), jnp.asarray(rows),
        jnp.asarray(served), control)
    got, low = np.asarray(got)[real], np.asarray(low)[real]
    out = {"compared_tokens": int(real.sum()),
           "served_gap_max": float(got.max()),
           "served_gap_mean": float(got.mean()),
           "served_tokens_below_best": int((got > 0).sum()),
           "served_gaps_largest": np.sort(got)[-5:][::-1].tolist()}
    if control:
        out.update(control_gap_max=float(low.max()),
                   control_gap_mean=float(low.mean()),
                   control_tokens_below_best=int((low > 0).sum()))
    return out


def expert_balance(per_expert) -> None:
    """The largest expert's load over the mean, a layer, on standard
    error."""
    per_expert = np.asarray(per_expert, np.float64)
    if not per_expert.size or not per_expert.sum():
        return
    mean = per_expert.mean(axis=1)
    log("expert load since the replica's start, largest over mean, by "
        "expert layer: " + ", ".join(
            f"{x:.2f}" for x in per_expert.max(axis=1) / mean)
        + f"; experts never touched: {int((per_expert == 0).sum())} of "
        f"{per_expert.size}")


def run(ctx) -> dict:
    from paddle_tpu.resilience.errors import PREEMPT_EXIT_CODE
    config, mix = ctx.config, ctx.traffic
    ctx.phase("jax up")
    # the model first: a program without this block fails here, at once
    model = build_model(config)
    params = importlib.import_module(config["weights"]).make_params(
        config, ctx.seed)
    frontend = build_frontend(config, model, params)
    del model
    del params
    ctx.phase("replica warm and listening")
    eng, url = frontend.engine, frontend.url
    source = RequestSource(mix, config["vocab_size"], ctx.seed)
    records = []
    if ctx.fault == "token_altered":   # tests: a token altered at its source
        from paddle_tpu.engine import engine as engine_mod
        sample = engine_mod._sample
        vocab = config["vocab_size"]

        def altered(logits, req, pos):
            tok, lp = sample(logits, req, pos)
            return (tok + 1) % vocab, lp
        mock.patch.object(engine_mod, "_sample", altered).start()
    callers = [threading.Thread(target=closed._caller, daemon=True,
                                args=(url, source, records))
               for _ in range(mix["callers"])]
    for c in callers:
        c.start()
    time.sleep(mix["warm_s"])     # the loop reaches its steady state

    before = closed.scrape(url)
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
    after = closed.scrape(url)
    source.close()
    deadline = time.monotonic() + 120
    for c in callers:
        c.join(max(0.0, deadline - time.monotonic()))
    stuck = sum(c.is_alive() for c in callers)

    peak = ctx.memory_peak_bytes()
    counters = {k: after.get(s, 0.0) - before.get(s, 0.0)
                for k, s in COUNTERS.items()}
    compiles = closed.scrape(url).get("ptpu_engine_compiles")
    occupancy = {"at_start": before.get("ptpu_kv_occupancy"),
                 "at_end": after.get("ptpu_kv_occupancy"),
                 "peak_since_start": eng.peak_occupancy}
    has_kernel = ctx.toy or closed.step_has_kernel(eng)
    program_text = None
    if tracer and not ctx.toy:
        # the compiled step's own text names each instruction's scope;
        # the compilation is the cache's, not a second one
        import jax
        program_text = closed.lower_step(
            eng, lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        ).compile().as_text()
    expert_balance(getattr(eng, "expert_tokens", ()))
    frontend.begin_drain()
    exit_code = frontend.wait(timeout=120)
    frontend._teardown()
    mock.patch.stopall()
    del eng, frontend
    gc.collect()

    m = closed.window_metrics(records, t0, t1)
    for f in m["failures"]:
        log("failed:", f)
    log(f"window {t1 - t0:.3f}s: {m['attempted']} requests sent, "
        f"{len(m['gaps_ms'])} gaps; counters {counters}")
    for name in ("ttft_ms", "gaps_ms"):
        if m[name]:
            log(f"{name}: " + ", ".join(
                f"p{q} {closed.percentile(m[name], q):.1f}"
                for q in (10, 50, 75, 90, 95, 99, 100)))
    log(f"share of the pool's blocks held by live sequences: {occupancy}")
    observed = {"counters": counters, "window_s": t1 - t0,
                "kv_occupancy": occupancy,
                "busy_s": None, "trace_window_s": None, "trace": None}
    cached_share = (counters["kv_hit"] / counters["kv_prompt"]
                    if counters["kv_prompt"] else 0.0)
    observed.update(closed.contexts(records, t0, t1, cached_share))
    if tracer:
        observed.update(tracer.reduce())
        observed["scope_s"] = scope_reduce.scope_seconds(
            observed["trace"], program_text, SCOPES)

    sample = closed.pick_sample(records, t0, t1, mix["check_requests"],
                                ctx.seed)
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
