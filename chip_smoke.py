"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: train, serve, latent,
                                     # hybrid
    python chip_smoke.py --only latent   # that phase alone: a minute
    python chip_smoke.py --only hybrid   # likewise
    python chip_smoke.py --chips 4   # one four-chip host: tp=4 serving
                                     # against tp=1, and the sharded
                                     # training parity gate — nothing else

One process (a chip belongs to one process), no network, everything
generated from a seed. Both one-chip phases run `CausalLM` at GPT-2
medium's published widths and full depth in bf16:

- train: `MeshTrainer` on a one-device mesh, five Adam steps;
- serve: export the model, build the replica exactly as `python -m
  paddle_tpu.serve.replica --model-dir ...` does, and send it requests
  over HTTP.

- latent: the latent-attention, routed-expert decoder at the published
  widths of `benchmarks/configs/glm-4.7-flash.json` cut to two layers
  (the dense one and one expert layer, all 64 experts, the whole
  vocabulary), bf16 weights from the benchmark's seed, through
  `ServeEngine`'s one step and its latent pool; the logits it sampled
  from are held against the benchmark's plain float32 reference.

- hybrid: the decoder of five layer kinds at the published widths of
  `benchmarks/configs/phi-4-mini-flash.json` cut to six layers (one of
  each kind: state-space, window, the memory layer, full, gated memory
  unit, cross), through `ServeEngine`'s paged pool, window rings and
  state slots; the largest difference of the logits it sampled from
  against the benchmark's plain float32 reference is printed.

Each phase prints one JSON line of what it counted and which of its
gates failed; the last line of stdout is the result object. Everything
else (serve events, warnings) goes to stderr. The exit code is 0 only
if every gate of every phase held, and non-zero — before any phase
runs — when `jax.devices()[0].platform` is not "tpu". There is no flag
that skips a phase or picks the CPU: the phases are functions of the
model widths so that tests/test_chip_smoke.py can drive the same code
at toy widths on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import io
import json
import os
import sys
import tempfile
import threading
import time
from unittest import mock

import numpy as np

# GPT-2 medium (Radford et al. 2019, the 345M row): nothing is cut.
GPT2_MEDIUM = dict(vocab=50257, model_dim=1024, num_heads=16, num_layers=24,
                   ffn_dim=4096, max_len=1024)
# A deployment-sized engine on one v5e. The step takes and returns the KV
# pools without donation, so the program holds two pools: compiled for a
# described v5e at these widths it needs 1.32 GiB of float32 weights +
# 2 x 4.5 GiB of pools + 0.6 GiB of temporaries = 10.9 GiB of the chip's
# 15.75 usable (PERF.md section 5; the chip run peaked at 10.3 GiB).
# 3,072 blocks of 16 tokens hold 49,152 tokens.
ENGINE = dict(block_size=16, max_batch_size=8, max_prefill_tokens=512,
              num_blocks=3072)
# Prompts of 32-700 tokens: the two longest exceed the 512-token chunk
# budget, the two of equal length are checked against model.generate in
# one batched call, and two more share a 256-token prefix. The logit
# check's own prompt takes two prefill chunks through the ragged step.
SERVE = dict(widths=GPT2_MEDIUM, engine=ENGINE,
             prompt_lens=(32, 64, 64, 400, 640, 700),
             shared_prefix=256, pair_suffixes=(40, 90), new_tokens=32,
             logit_prompt_len=600)
# max |ragged-step logit - dense-forward logit| over the vocabulary, as a
# share of max |dense logit|. bf16 keeps 8 bits: the same weights in bf16
# and float32 differ by 1.2% of the largest logit on this model (CPU run
# at these widths), while a paging or masking fault moves logits by their
# own spread, about 25% of the largest.
LOGIT_TOL = 0.05
# The latent phase: the cell's own engine but for the pool (256 blocks of
# 128 rows are 32,768 tokens: two layers' pools take 84 MB). The first
# prompt is prefilled in three chunks of 1,024, the second hits its
# first 2,048 tokens in the prefix index.
LATENT = dict(layers=2, num_blocks=256, prompt_lens=(2500, 2300),
              shared_prefix=2048, new_tokens=8)
# The hybrid phase: one layer of each of the five kinds at the published
# widths (the memory layer doubles as the state-space one), the cell's
# own engine but for 64 paged blocks and four slots. The first prompt is
# prefilled in six chunks of 256 and decoded past 1,400 positions, so
# every ring has turned over; the second takes the slot a finished
# sequence left.
HYBRID = dict(layer_kinds=("mamba", "window", "mamba", "full", "gmu",
                           "cross"),
              num_blocks=64, max_batch_size=4, prompt_lens=(1400, 300),
              new_tokens=8)
TRAIN = dict(batch=8, seq=1024, steps=5)
PEAK_BYTES_LIMIT = 14e9
SEED = 0


def _device_bytes(dev, key: str):
    stats = dev.memory_stats()
    return None if stats is None else int(stats[key])


class _CompileCache:
    """Counts what this process reads from and writes to JAX's
    persistent compilation cache (jax.monitoring events)."""

    def __init__(self, directory: str):
        import jax
        self.directory = directory
        self.read = 0
        self.written = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.read += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def take(self) -> dict:
        """Entries read and written since the last call: one phase's."""
        out = {"dir": self.directory, "entries_read": self.read,
               "entries_written": self.written}
        self.read = self.written = 0
        return out


def _lowered_text(jitted, *args) -> str:
    """The program `jitted` traces for these operands, as text. A Pallas
    TPU kernel shows in it as a `tpu_custom_call`; the interpret-mode
    kernel and the XLA reference tiers do not."""
    import jax

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return jitted.lower(*jax.tree.map(spec, args)).as_text()


def pool_sized_copies(program_text: str, pool_elements: int) -> list:
    """The `copy` instructions of a compiled program whose result has a
    pool's element count (on one chip): a whole-pool relayout. The
    step writes its K/V into the donated pool in place, so there are
    none; a pool whose default device layout is not the one its
    scatter and kernel use brings two per pool per step back
    (tests/test_chip_compile.py holds the same count without a chip)."""
    import math
    import re
    found = []
    for line in program_text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) == pool_elements:
            found.append(line.strip()[:160])
    return found


def _engine_step_compiled(eng):
    """The engine's one step, compiled for operands of its own shapes
    on the devices it runs on."""
    import jax
    import jax.numpy as jnp
    t, nt, b = eng.flat_tokens, eng.num_tiles, eng.max_batch_size

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    return eng._step_fn.lower(
        jax.tree.map(spec, eng.variables), i32(t), i32(t),
        jax.tree.map(spec, eng.cache.pools),
        jax.tree.map(spec, eng.cache.qpools),
        jax.tree.map(spec, eng.cache.qscales),
        i32(b + 1, eng.max_blocks_per_seq), i32(b + 1), i32(b + 1),
        i32(nt), i32(nt), i32(t), i32(b, eng.spec_len)).compile()


def _placement_failures(eng, tp_size: int) -> list:
    """Code that never saw two real devices may put everything on the
    first: check where the tp engine's weights and pools actually are,
    while that engine is the only thing alive."""
    import jax
    failed = []
    devs = jax.devices()[:tp_size]
    qkv = eng.variables["params"]["blocks_0"]["attn"]["q_proj"]["weight"]
    for name, arr in (("q_proj weight", qkv),
                      ("kv pool", eng.cache.pools[0])):
        on = {s.device for s in arr.addressable_shards}
        if on != set(devs):
            failed.append(f"{name} on {len(on)} devices, want {tp_size}")
    whole = sum(pool.nbytes for pool in eng.cache.pools)
    if eng.cache.per_chip_pool_bytes() * tp_size != whole:
        failed.append("per-chip pool bytes are not 1/tp of the pool")
    in_use = [_device_bytes(d, "bytes_in_use") for d in devs]
    if None in in_use:
        failed.append("device reports no memory_stats")
    elif max(in_use) > 1.2 * min(in_use):
        failed.append(f"bytes_in_use uneven across chips: {in_use}")
    return failed


def serve_phase(widths: dict, engine: dict, prompt_lens, shared_prefix: int,
                pair_suffixes, new_tokens: int, logit_prompt_len: int,
                dtype, seed: int, tp_size: int = 1, cache=None):
    """Export -> replica front end -> HTTP traffic -> drain, then the
    ragged step's logits against the dense forward. Returns (line,
    artifacts): the phase's JSON line, whose "failed" lists the gates
    that did not hold, and the tokens and logits a caller may compare
    across tensor-parallel degrees."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.engine import engine as engine_mod
    from paddle_tpu.resilience.errors import PREEMPT_EXIT_CODE
    from paddle_tpu.serve import replica
    from paddle_tpu.serve.sse import (collect_stream, http_get,
                                      parse_prometheus_values)
    from paddle_tpu.testing import export_causal_lm

    failed = []
    dev = jax.devices()[0]
    bytes_before = _device_bytes(dev, "bytes_in_use")
    rs = np.random.RandomState(seed)
    vocab = widths["vocab"]

    def tokens(n):
        return rs.randint(0, vocab, n).tolist()

    prompts = {f"p{i}_{n}": tokens(n) for i, n in enumerate(prompt_lens)}
    prefix = tokens(shared_prefix)
    pair = [prefix + tokens(n) for n in pair_suffixes]
    prompts["pair0"] = pair[0]
    logit_prompt = tokens(logit_prompt_len)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path, model, variables = export_causal_lm(
            os.path.join(tmp, "model"), seed=seed, dtype=dtype, **widths)
        del variables        # the replica loads its own copy from disk
        args = replica.build_parser().parse_args([
            "--model-dir", path, "--port", "0",
            "--block-size", str(engine["block_size"]),
            "--num-blocks", str(engine["num_blocks"]),
            "--max-batch-size", str(engine["max_batch_size"]),
            "--max-prefill-tokens", str(engine["max_prefill_tokens"]),
            "--tp-size", str(tp_size),
            # not a latency test: admission must not shed on a slow step
            "--slo-ttft-ms", "1e9", "--slo-tpot-ms", "1e9",
            "--slo-queue-wait-ms", "1e9"])
        t0 = time.perf_counter()
        frontend = replica.build_frontend(args)
        frontend.start()     # warms the one compiled step, then listens
        setup_s = time.perf_counter() - t0
    eng = frontend.engine
    if tp_size > 1:
        failed += _placement_failures(eng, tp_size)

    url = frontend.url
    deadline = time.monotonic() + 60
    while http_get(url + "/readyz")[0] != 200:
        if time.monotonic() > deadline:
            raise RuntimeError("replica never became ready")
        time.sleep(0.1)

    results = {}

    def drive(name, prompt):
        try:
            results[name] = collect_stream(
                url, {"prompt": prompt, "max_new_tokens": new_tokens},
                timeout=600)
        except Exception as e:   # thread boundary: recorded, gated below
            results[name] = {"status": None, "done": False, "tokens": [],
                             "final": None, "error": repr(e)}

    threads = {name: threading.Thread(target=drive, args=(name, p))
               for name, p in prompts.items()}
    for t in threads.values():
        t.start()
    # blocks are shared only once their first owner has committed them:
    # the pair's second request goes in after the first has finished,
    # while the long prompts are still in flight
    threads["pair0"].join()
    prompts["pair1"] = pair[1]
    threads["pair1"] = threading.Thread(target=drive,
                                        args=("pair1", pair[1]))
    threads["pair1"].start()
    for t in threads.values():
        t.join()

    for name, r in sorted(results.items()):
        final = r["final"] or {}
        if not (r["status"] == 200 and r["done"]
                and final.get("reason") == "length"
                and len(r["tokens"]) == new_tokens):
            failed.append(f"stream {name}: status={r['status']} "
                          f"done={r['done']} reason={final.get('reason')} "
                          f"tokens={len(r['tokens'])} {r.get('error', '')}")
    metrics = parse_prometheus_values(http_get(url + "/metrics")[1])
    compiles = metrics.get("ptpu_engine_compiles")
    mixed_steps = metrics.get('ptpu_serve_step_ms_count{kind="mixed"}', 0)
    if compiles != 1:
        failed.append(f"ptpu_engine_compiles={compiles}, want 1")
    if not mixed_steps:
        failed.append("no step carried prefill and decode rows together")

    frontend.begin_drain()
    exit_code = frontend.wait(timeout=120)
    frontend._teardown()
    if exit_code != PREEMPT_EXIT_CODE:
        failed.append(f"drain ended with {exit_code}, "
                      f"want {PREEMPT_EXIT_CODE}")
    eng.cache.assert_quiesced()
    stats = eng.stats()
    if not stats["hit_tokens"] > 0:
        failed.append("the prefix-sharing pair hit no cached tokens")

    # read off the compiled step: the kernel tier it holds, and that
    # it updates the donated pools in place (no whole-pool relayout,
    # the pools' bytes aliased to its outputs)
    step = _engine_step_compiled(eng)
    step_text = step.as_text()
    pallas_in_step = "tpu_custom_call" in step_text
    if not pallas_in_step:
        failed.append("no Pallas kernel in the engine's step program")
    pool_copies = pool_sized_copies(
        step_text, int(np.prod(eng.cache.pool_shape())))
    if pool_copies:
        failed.append(f"{len(pool_copies)} whole-pool copies in the "
                      f"engine's step program: {pool_copies[0]}")
    aliased = step.memory_analysis().alias_size_in_bytes
    if aliased < eng.cache.per_chip_pool_bytes():
        failed.append(f"the step aliases {aliased} bytes, less than the "
                      f"pools' {eng.cache.per_chip_pool_bytes()}")

    # the ragged step's logits at the prompt's last position, as the
    # engine itself fetched them, against the dense forward
    seen = []
    sample = engine_mod._sample

    def spy(logits, req, pos):
        seen.append(np.array(logits, np.float32))
        return sample(logits, req, pos)

    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        eng.generate([logit_prompt], max_new_tokens=1)
    ragged = seen[-1]
    one_dev = jax.device_put(eng.variables, dev)
    dense = np.asarray(model.apply(
        one_dev, jnp.asarray([logit_prompt], jnp.int32),
        training=False)[0, -1], np.float32)
    logit_err = float(np.max(np.abs(ragged - dense))
                      / np.max(np.abs(dense)))
    if not (np.isfinite(ragged).all() and logit_err <= LOGIT_TOL):
        failed.append(f"ragged-step logits off the dense forward by "
                      f"{logit_err:.4f} of the largest logit")

    # printed, not gated: seeded random weights give near-tied logits
    # and bf16 reduction order may flip an argmax. The prompts of equal
    # length go through model.generate in one batched call.
    twin_len = next(n for n in prompt_lens if prompt_lens.count(n) > 1)
    twins = [n for n, p in prompts.items() if len(p) == twin_len]
    ref = np.asarray(model.generate(
        one_dev, jnp.asarray([prompts[n] for n in twins], jnp.int32),
        num_steps=new_tokens))[:, -new_tokens:]
    agree = sum(int(a == b) for n, row in zip(twins, ref)
                for a, b in zip(results[n]["tokens"], row))

    line = {
        "phase": "serve", "tp_size": tp_size, "dtype": jnp.dtype(dtype).name,
        "widths": widths, "engine": engine,
        "setup_seconds": round(setup_s, 1),
        "requests": len(results), "new_tokens_each": new_tokens,
        "prompt_tokens": sorted(len(p) for p in prompts.values()),
        "streams_complete": sum(r["done"] for r in results.values()),
        "engine_compiles": compiles, "engine_steps": stats["steps"],
        "mixed_steps": int(mixed_steps),
        "hit_tokens": stats["hit_tokens"],
        "max_chunk_tokens": stats["max_chunk_tokens"],
        "cache_quiesced": True, "drain_exit_code": exit_code,
        "pallas_in_step": pallas_in_step,
        "step_pool_sized_copies": len(pool_copies),
        "step_aliased_bytes": aliased,
        "logit_err_share_of_max": round(logit_err, 5),
        "logit_tol": LOGIT_TOL,
        "tokens_equal_to_generate": f"{agree}/{ref.size}",
        "kv_pool_bytes_per_chip": eng.cache.per_chip_pool_bytes(),
        "bytes_in_use_at_start": bytes_before,
        "peak_bytes_in_use": _device_bytes(dev, "peak_bytes_in_use"),
        "failed": failed,
    }
    if cache is not None:
        line["compile_cache"] = cache.take()
    artifacts = {"tokens": {n: r["tokens"] for n, r in results.items()},
                 "logits": ragged}
    return line, artifacts


def train_phase(widths: dict, batch: int, seq: int, steps: int, dtype,
                seed: int, cache=None) -> dict:
    """MeshTrainer on a one-device mesh, Adam, `steps` steps on one fixed
    batch — the loop examples/train_causal_lm.py runs. Returns the
    phase's JSON line; "failed" lists the gates that did not hold."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.transformer import CausalLM
    from paddle_tpu.ops.fused_ce import linear_cross_entropy
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import MeshConfig, MeshTrainer, make_mesh

    failed = []
    dev = jax.devices()[0]
    bytes_before = _device_bytes(dev, "bytes_in_use")
    model = CausalLM(dropout=0.0, dtype=dtype, **widths)

    def loss_fn(module, variables, batch, rng, training):
        inp, tgt = batch
        hid, mut = module.apply(variables, inp, training=training,
                                rngs=rng, mutable=True, return_hidden=True)
        w, b = module.head_weights(variables)
        loss = jnp.mean(linear_cross_entropy(
            hid, w.astype(hid.dtype), tgt,
            None if b is None else b.astype(hid.dtype)))
        return (loss, {}), mut.get("state", {})

    trainer = MeshTrainer(model, Adam(3e-4), loss_fn,
                          make_mesh(MeshConfig(dp=1), devices=[dev]),
                          seed=seed)
    # a learnable stream: next token = (token + 3) mod vocab
    start = np.random.RandomState(seed).randint(0, widths["vocab"],
                                                (batch, 1))
    tok = ((start + 3 * np.arange(seq + 1)[None, :])
           % widths["vocab"]).astype(np.int32)
    ts = trainer.init_state(jnp.asarray(tok[:, :-1]))
    fixed = trainer.put_batch((tok[:, :-1], tok[:, 1:]))
    losses = []
    t0 = time.perf_counter()
    for step in range(steps):
        ts, out = trainer.train_step(ts, fixed, rng=jax.random.key(step))
        losses.append(float(out["loss"]))
        if step == 0:
            first_step_s = time.perf_counter() - t0
    if not np.isfinite(losses).all():
        failed.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        failed.append(f"loss did not fall: {losses}")
    compiles = trainer._train_step._cache_size()
    if compiles != 1:
        failed.append(f"train step compiled {compiles} times, want 1")
    flash_in_step = "tpu_custom_call" in _lowered_text(
        trainer._train_step, ts, fixed, jax.random.key(0))
    if not flash_in_step:
        failed.append("no flash kernel in the trainer's step program")
    peak = _device_bytes(dev, "peak_bytes_in_use")
    if peak is not None and peak >= PEAK_BYTES_LIMIT:
        failed.append(f"peak_bytes_in_use {peak} >= {PEAK_BYTES_LIMIT:.0f}")

    line = {
        "phase": "train", "dtype": jnp.dtype(dtype).name, "widths": widths,
        "batch": batch, "seq": seq, "optimizer": "adam",
        "params": sum(int(x.size) for x in jax.tree.leaves(ts.params)),
        "first_step_seconds": round(first_step_s, 1),
        "steps": steps, "losses": [round(x, 4) for x in losses],
        "train_compiles": compiles, "flash_in_step": flash_in_step,
        "bytes_in_use_at_start": bytes_before,
        "peak_bytes_in_use": peak, "failed": failed,
    }
    if cache is not None:
        line["compile_cache"] = cache.take()
    return line


def _spied_generate(eng, prompts, new_tokens: int):
    """Serve `prompts` one at a time; returns (generated, every logits
    row the engine sampled from, in order)."""
    from paddle_tpu.engine import engine as engine_mod
    seen = []
    sample = engine_mod._sample

    def spy(logits, req, pos):
        seen.append(np.array(logits, np.float32))
        return sample(logits, req, pos)

    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        generated = [eng.generate([prompt], max_new_tokens=new_tokens)[0]
                     for prompt in prompts]
    return generated, seen


def _teacher_forced(prompts, generated, new_tokens: int):
    """(tokens, rows) of the reference's pass over each prompt with its
    served tokens: the positions the engine sampled from."""
    width = -(-(max(map(len, prompts)) + new_tokens) // 128) * 128
    tokens = np.zeros((len(prompts), width), np.int32)
    rows = np.zeros((len(prompts), new_tokens), np.int32)
    for i, (p, g) in enumerate(zip(prompts, generated)):
        tokens[i, :len(p) + len(g)] = p + g
        rows[i] = len(p) - 1 + np.arange(new_tokens)
    return tokens, rows


def _against_reference(ref, seen, generated, failed: list) -> dict:
    """The sampled logits against the reference's [rows, vocab]: the
    line's fields, and the gate on the largest difference."""
    got = np.stack(seen)
    logit_err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    if not (np.isfinite(got).all() and logit_err <= LOGIT_TOL):
        failed.append(f"the step's logits off the reference by "
                      f"{logit_err:.4f} of the largest logit")
    served = np.concatenate(generated)
    gaps = ref.max(axis=-1) - ref[np.arange(len(served)), served]
    return {
        "logit_err_share_of_max": round(logit_err, 5),
        "logit_tol": LOGIT_TOL,
        "largest_logit_difference": float(np.max(np.abs(got - ref))),
        "token_gap_max": float(gaps.max()),
        "tokens_equal_to_reference": f"{int((gaps == 0).sum())}/{gaps.size}",
    }


def latent_phase(config: dict, layers: int, num_blocks: int, prompt_lens,
                 shared_prefix: int, new_tokens: int, seed: int,
                 cache=None) -> dict:
    """Serve the configuration's block, cut to `layers`, in process and
    hold every logits row the engine sampled from against the
    benchmark's reference, teacher-forced. Returns the phase's JSON
    line."""
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build_model
    from paddle_tpu.engine.engine import ServeEngine

    t_start = time.monotonic()
    config = dict(config, num_hidden_layers=layers)
    dev = jax.devices()[0]
    model = build_model(config)
    params = importlib.import_module(config["weights"]).make_params(
        config, seed)
    reference = importlib.import_module(config["reference"])
    serve = dict(config["serve"], num_blocks=num_blocks)
    eng = ServeEngine(model, {"params": params}, **serve)
    del params
    rng = np.random.default_rng(seed)
    vocab = config["vocab_size"]
    shared = rng.integers(0, vocab, shared_prefix).tolist()
    prompts = [shared + rng.integers(0, vocab, n - shared_prefix).tolist()
               for n in prompt_lens]

    # one at a time: the second hits
    generated, seen = _spied_generate(eng, prompts, new_tokens)
    setup_s = time.monotonic() - t_start
    stats = eng.stats()
    failed = []
    compiles = eng._step_fn._cache_size()
    if compiles != 1:
        failed.append(f"the step compiled {compiles} times")
    if stats["hit_tokens"] < shared_prefix // serve["block_size"] \
            * serve["block_size"]:
        failed.append(f"prefix hit of {stats['hit_tokens']} tokens, under "
                      f"the shared {shared_prefix}")
    step = _engine_step_compiled(eng)
    step_text = step.as_text()
    pallas_in_step = "tpu_custom_call" in step_text
    if not pallas_in_step:
        failed.append("no Pallas kernel in the engine's step program")
    pool_copies = pool_sized_copies(
        step_text, int(np.prod(eng.cache.pool_shape())))
    if pool_copies:
        failed.append(f"{len(pool_copies)} whole-pool copies in the "
                      f"engine's step program: {pool_copies[0]}")
    per_expert = eng.expert_tokens.copy()
    computed = stats["prefill_tokens_computed"] + sum(
        len(g) - 1 for g in generated)
    want = computed * config["num_experts_per_tok"] * per_expert.shape[0]
    if per_expert.sum() != want:
        failed.append(f"{per_expert.sum()} (token, expert) pairs counted "
                      f"for {computed} computed tokens: want {want}")
    eng.cache.assert_quiesced()
    peak = _device_bytes(dev, "peak_bytes_in_use")
    del eng
    gc.collect()

    tokens, rows = _teacher_forced(prompts, generated, new_tokens)
    ref, _ = reference.logits_at(config, seed, jnp.asarray(tokens),
                                 jnp.asarray(rows))
    compared = _against_reference(
        np.asarray(ref, np.float32).reshape(-1, vocab), seen, generated,
        failed)
    line = {
        "phase": "latent", "config": config["name"], "layers": layers,
        "dtype": config["compute_dtype"], "engine": serve,
        "setup_and_serve_seconds": round(setup_s, 1),
        "prompt_tokens": [len(p) for p in prompts],
        "new_tokens_each": new_tokens, "engine_compiles": compiles,
        "engine_steps": stats["steps"], "hit_tokens": stats["hit_tokens"],
        "max_chunk_tokens": stats["max_chunk_tokens"],
        "pallas_in_step": pallas_in_step,
        "step_pool_sized_copies": len(pool_copies),
        "expert_pairs": int(per_expert.sum()),
        "experts_touched": int((per_expert > 0).sum()), **compared,
        "peak_bytes_in_use": peak, "failed": failed,
    }
    if cache is not None:
        line["compile_cache"] = cache.take()
    return line


def hybrid_phase(config: dict, layer_kinds, num_blocks: int,
                 max_batch_size: int, prompt_lens, new_tokens: int,
                 seed: int, cache=None) -> dict:
    """Serve the configuration's block cut to `layer_kinds` in process,
    through the paged pool, the window rings and the state slots, and
    hold every logits row the engine sampled from against the
    benchmark's reference, teacher-forced. Returns the phase's JSON
    line."""
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build_model
    from paddle_tpu.engine.engine import ServeEngine

    t_start = time.monotonic()
    config = dict(config, layer_kinds=list(layer_kinds),
                  num_hidden_layers=len(layer_kinds))
    dev = jax.devices()[0]
    model = build_model(config)
    params = importlib.import_module(config["weights"]).make_params(
        config, seed)
    reference = importlib.import_module(config["reference"])
    serve = dict(config["serve"], num_blocks=num_blocks,
                 max_batch_size=max_batch_size)
    eng = ServeEngine(model, {"params": params}, **serve)
    del params
    rng = np.random.default_rng(seed)
    vocab = config["vocab_size"]
    prompts = [rng.integers(0, vocab, n).tolist() for n in prompt_lens]

    # one at a time: the slot is handed on
    generated, seen = _spied_generate(eng, prompts, new_tokens)
    setup_s = time.monotonic() - t_start
    stats = eng.stats()
    failed = []
    compiles = eng._step_fn._cache_size()
    if compiles != 1:
        failed.append(f"the step compiled {compiles} times")
    step_text = _engine_step_compiled(eng).as_text()
    pallas_in_step = "tpu_custom_call" in step_text
    if not pallas_in_step:
        failed.append("no Pallas kernel in the engine's step program")
    # the gate is of the chip's program: off it the scan's XLA tier
    # carries the state through a loop, and the compiler copies it
    copies = [c for size in sorted({int(p.size)
                                    for p in eng.cache.pools[:-1]})
              for c in pool_sized_copies(step_text, size)
              ] if pallas_in_step else []
    if copies:
        failed.append(f"{len(copies)} copies of a pool's or a state's size "
                      f"in the engine's step program: {copies[0]}")
    released = eng.cache.window_blocks_released
    if max(prompt_lens) > config["sliding_window"] and not released:
        failed.append("no ring block was given back")
    eng.cache.assert_quiesced()
    peak = _device_bytes(dev, "peak_bytes_in_use")
    del eng
    gc.collect()

    tokens, rows = _teacher_forced(prompts, generated, new_tokens)
    ref = reference.logits_at(config, seed, jnp.asarray(tokens),
                              jnp.asarray(rows))
    compared = _against_reference(
        np.asarray(ref, np.float32).reshape(-1, vocab), seen, generated,
        failed)
    line = {
        "phase": "hybrid", "config": config["name"],
        "layer_kinds": list(layer_kinds),
        "dtype": config["compute_dtype"], "engine": serve,
        "setup_and_serve_seconds": round(setup_s, 1),
        "prompt_tokens": [len(p) for p in prompts],
        "new_tokens_each": new_tokens, "engine_compiles": compiles,
        "engine_steps": stats["steps"],
        "max_chunk_tokens": stats["max_chunk_tokens"],
        "pallas_in_step": pallas_in_step,
        "step_pool_or_state_sized_copies": len(copies),
        "window_blocks_released": released, **compared,
        "peak_bytes_in_use": peak, "failed": failed,
    }
    if cache is not None:
        line["compile_cache"] = cache.take()
    return line


def _hybrid_config() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        return json.load(f)


def _latent_config() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


def _quiet(fn, *args, **kwargs):
    """Run a phase with everything its callees print (serve events, the
    dry run's summary) sent to stderr: stdout carries the phase lines
    and the result object, nothing else."""
    with contextlib.redirect_stdout(sys.stderr):
        return fn(*args, **kwargs)


def _emit(line: dict) -> bool:
    print(json.dumps(line), flush=True)
    return not line["failed"]


def four_chip_phases(dtype, cache=None) -> bool:
    """What exists only across chips: the serve phase at tp=4 against
    the same engine at tp=1 (same export seed, same prompts, the exact
    fp all-reduce), then every sharded training path against its
    one-device run (__graft_entry__._dryrun_impl)."""
    import __graft_entry__

    serve = dict(SERVE, dtype=dtype, seed=SEED, cache=cache)
    # the tp=4 engine goes first, alone on the chips; its all-reduce
    # mode is read once, host-side, when the engine is constructed
    with mock.patch.dict(os.environ, {"PTPU_SERVE_ALLREDUCE": "fp"}):
        line4, tp4 = _quiet(serve_phase, tp_size=4, **serve)
    ok = _emit(line4)
    gc.collect()
    line1, tp1 = _quiet(serve_phase, tp_size=1, **serve)
    ok = _emit(line1) and ok
    gc.collect()

    failed = []
    lengths_equal = all(len(tp4["tokens"][n]) == len(tp1["tokens"][n])
                        for n in tp1["tokens"])
    if not lengths_equal:
        failed.append("tp=4 and tp=1 streams differ in length")
    agree = sum(int(a == b) for n in tp1["tokens"]
                for a, b in zip(tp4["tokens"][n], tp1["tokens"][n]))
    total = sum(len(t) for t in tp1["tokens"].values())
    logit_err = float(np.max(np.abs(tp4["logits"] - tp1["logits"]))
                      / np.max(np.abs(tp1["logits"])))
    if not logit_err <= LOGIT_TOL:
        failed.append(f"tp=4 logits off tp=1 by {logit_err:.4f} of the "
                      "largest logit")
    ok = _emit({"phase": "tp4_vs_tp1", "lengths_equal": lengths_equal,
                "tokens_equal": f"{agree}/{total}",
                "logit_err_share_of_max": round(logit_err, 5),
                "logit_tol": LOGIT_TOL, "failed": failed}) and ok

    summary = io.StringIO()
    with contextlib.redirect_stdout(summary):
        __graft_entry__._dryrun_impl(4)         # raises where parity fails
    return _emit({"phase": "dryrun_multichip", "devices": 4,
                  "summary": summary.getvalue().strip().splitlines()[-1],
                  "failed": []}) and ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--only", choices=("latent", "hybrid"), default=None,
                    help="run that one-chip phase alone")
    args = ap.parse_args(argv)
    chips = args.chips

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} but JAX found {len(devs)} "
              "device(s)", file=sys.stderr)
        return 2

    cache = _CompileCache(cache_dir)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _emit({"phase": "start", "jax": jax.__version__,
           "jaxlib": jaxlib.__version__,
           "libtpu": importlib.metadata.version("libtpu"),
           "device": device, "chips": chips,
           "compile_cache_dir": cache_dir,
           "compile_cache_entries_at_start":
               len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
           "failed": []})

    if chips == 4:
        ok = four_chip_phases(jnp.bfloat16, cache)
    elif args.only == "latent":
        ok = _emit(_quiet(latent_phase, _latent_config(), seed=SEED,
                          cache=cache, **LATENT))
    elif args.only == "hybrid":
        ok = _emit(_quiet(hybrid_phase, _hybrid_config(), seed=SEED,
                          cache=cache, **HYBRID))
    else:
        # train first: peak_bytes_in_use is the process's high-water
        # mark and cannot be reset, and the serve phase's is the higher
        # one, so in this order each line shows its own phase's peak
        ok = _emit(_quiet(train_phase, GPT2_MEDIUM, dtype=jnp.bfloat16,
                          seed=SEED, cache=cache, **TRAIN))
        gc.collect()         # the trainer's state leaves before serving
        serve, _ = _quiet(serve_phase, dtype=jnp.bfloat16, seed=SEED,
                          cache=cache, **SERVE)
        ok = _emit(serve) and ok
        gc.collect()
        ok = _emit(_quiet(latent_phase, _latent_config(), seed=SEED,
                          cache=cache, **LATENT)) and ok
        gc.collect()
        ok = _emit(_quiet(hybrid_phase, _hybrid_config(), seed=SEED,
                          cache=cache, **HYBRID)) and ok
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
