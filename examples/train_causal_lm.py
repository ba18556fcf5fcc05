"""Long-context causal-LM training on one chip.

Trains `CausalLM` (decoder-only, GPT-style) with the two pieces that
keep memory linear in sequence length — block-causal Pallas flash
attention (O(T) score memory; kernels/flash.py) and the chunked fused
cross-entropy (no [T, V] logits tensor; ops/fused_ce.py) — then
generates a continuation with the KV-cache decode path. On a v5e this
recipe trains full steps at 16k+ tokens (PERF_NOTES.md: 107k tok/s at
seq 16384); the defaults here are sized to finish in seconds anywhere:

    python examples/train_causal_lm.py                 # TPU or CPU
    python examples/train_causal_lm.py --seq 16384     # the long-context point (TPU)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.executor import Trainer
from paddle_tpu.models.transformer import CausalLM
from paddle_tpu.ops.fused_ce import linear_cross_entropy
from paddle_tpu.optim.optimizer import Adam
from paddle_tpu.utils.compile_cache import enable_compile_cache


def sequence_batch(rs, batch, seq, vocab):
    """Learnable stream: next token = (token + 3) mod vocab."""
    start = rs.randint(0, vocab, (batch, 1))
    ramp = np.arange(seq + 1)[None, :] * 3
    return ((start + ramp) % vocab).astype(np.int32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable the resilient loop: checkpoint here, "
                         "resume from the newest intact checkpoint, "
                         "preemption-safe (SIGTERM => emergency save + "
                         "reschedulable exit)")
    ap.add_argument("--save-every", type=int, default=20,
                    help="checkpoint cadence in steps (with --ckpt-dir)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve training telemetry (step phases, goodput, "
                         "MFU, device memory) at :PORT/metrics while the "
                         "resilient loop runs (needs --ckpt-dir)")
    ap.add_argument("--flightrec-dir", default=None,
                    help="dump a postmortem bundle here when the watchdog "
                         "flags a hung step or the loop crashes "
                         "(needs --ckpt-dir)")
    args = ap.parse_args()
    if (args.metrics_port or args.flightrec_dir) and not args.ckpt_dir:
        ap.error("--metrics-port/--flightrec-dir ride on the resilient "
                 "loop: pass --ckpt-dir too")

    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    model = CausalLM(args.vocab, model_dim=args.dim, num_heads=4,
                     num_layers=args.layers, ffn_dim=4 * args.dim,
                     dropout=0.0, max_len=args.seq + 8, dtype=dtype)

    def loss_fn(module, variables, batch, rng, training):
        inp, tgt = batch
        hid, mut = module.apply(variables, inp, training=training,
                                rngs=rng, mutable=True, return_hidden=True)
        w, b = module.head_weights(variables)
        loss = jnp.mean(linear_cross_entropy(
            hid, w.astype(hid.dtype), tgt,
            None if b is None else b.astype(hid.dtype)))
        return (loss, {}), mut.get("state", {})

    trainer = Trainer(model, Adam(3e-3), loss_fn)
    rs = np.random.RandomState(0)
    tok = sequence_batch(rs, args.batch, args.seq, args.vocab)
    ts = trainer.init_state(jnp.asarray(tok[:, :-1]))
    batch = (jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:]))
    print(f"device={jax.devices()[0].device_kind} seq={args.seq} "
          f"params={sum(x.size for x in jax.tree.leaves(ts.params)):,}")
    if args.ckpt_dir:
        # Resilient loop (resilience/supervisor.py): deterministic
        # batch_for + resume-from-latest means a preempted run relaunched
        # with the same command continues the same loss curve.
        from paddle_tpu.io.checkpoint import CheckpointManager
        from paddle_tpu.resilience.supervisor import train_resilient

        manager = CheckpointManager(args.ckpt_dir, max_to_keep=3)
        restored, rstep = manager.restore_latest(ts)
        start = 0
        if restored is not None:
            ts, start = restored, rstep
            print(f"resumed from {args.ckpt_dir} at step {start}")

        def on_step(step, out):
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d}  loss {float(out['loss']):.4f}")

        # Training telemetry (OBSERVABILITY.md "Training telemetry"):
        # one registry feeds the scrape server, the goodput ledger, the
        # MFU gauge (absent where the platform peak is unknown), the
        # per-device memory gauges and the flight recorder's snapshot.
        import contextlib

        from paddle_tpu.obs import (
            DeviceMemoryMonitor, FlightRecorder, GoodputLedger,
            MetricsServer, default_registry)
        from paddle_tpu.obs.goodput import causal_lm_step_flops, param_count

        telemetry = {}
        srv = contextlib.nullcontext()
        if args.metrics_port or args.flightrec_dir:
            reg = default_registry()
            flops = causal_lm_step_flops(
                batch_size=args.batch, seq_len=args.seq, d_model=args.dim,
                n_layers=args.layers, n_params=param_count(ts.params))
            telemetry = dict(registry=reg,
                             goodput=GoodputLedger(registry=reg),
                             flops_per_step=flops,
                             memory_monitor=DeviceMemoryMonitor(registry=reg))
            if args.flightrec_dir:
                telemetry["flight_recorder"] = FlightRecorder(
                    streams=("resilience", "obs"),
                    snapshot_fn=lambda: {"metrics": reg.snapshot()},
                    out_dir=args.flightrec_dir, registry=reg)
            if args.metrics_port:
                srv = MetricsServer(reg, port=args.metrics_port)

        with srv:
            ts = train_resilient(trainer, ts, lambda step: batch, args.steps,
                                 manager, start_step=start,
                                 save_every=args.save_every,
                                 rng_for_step=jax.random.key,
                                 on_step=on_step, **telemetry)
        if telemetry:
            gl = telemetry["goodput"]
            lost = ", ".join(f"{c}={s:.3f}s" for c, s in
                             sorted(gl.lost_seconds().items())) or "none"
            print(f"goodput {gl.goodput():.4f}  "
                  f"productive {gl.productive_seconds():.3f}s  lost: {lost}")
    else:
        for step in range(args.steps):
            ts, out = trainer.train_step(ts, batch, rng=jax.random.key(step))
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d}  loss {float(out['loss']):.4f}")

    # KV-cache generation: the (t+3)%V stream is learnable, so the
    # continuation should keep stepping by 3
    p0 = min(8, args.seq)              # stay inside max_len for tiny --seq
    prompt = jnp.asarray(tok[:2, :p0])
    cont = model.generate(ts.variables, prompt, num_steps=8)
    print("prompt     :", np.asarray(prompt[0]))
    print("continued  :", np.asarray(cont[0, p0:]))
    want = (np.asarray(prompt[0, -1]) + 3 * np.arange(1, 9)) % args.vocab
    print("ideal      :", want)


if __name__ == "__main__":
    main()
