"""BERT-style MLM pretraining on a device mesh (dp x fsdp).

The BASELINE "BERT-base pretraining, pod-scale allreduce" flow as a
runnable script: synthetic corpus, MeshTrainer with ZeRO (REDUCE)
sharding, gradient accumulation, async checkpointing. Runs unchanged on
one chip, a TPU slice, or the 8-device virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/pretrain_bert.py --dp 4 --fsdp 2 --tiny

Multi-host: wrap with `python -m paddle_tpu.parallel.launch --nproc N`
(or generate cluster manifests with `python -m paddle_tpu.parallel.kube`).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.io import CheckpointManager
from paddle_tpu.models.transformer import BertEncoder
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import Adam
from paddle_tpu.parallel import (DistStrategy, MeshConfig, MeshTrainer,
                                 ReduceStrategy, make_mesh)
from paddle_tpu.parallel.distributed import init_distributed
from paddle_tpu.parallel.sharding import fsdp_rules
from paddle_tpu.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=0, help="0 = all devices")
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--per-chip-batch", type=int, default=8)
    ap.add_argument("--tiny", action="store_true",
                    help="small config for CPU-mesh runs")
    ap.add_argument("--ckpt", default="/tmp/ptpu_bert/ckpt")
    args = ap.parse_args()

    init_distributed()   # no-op single-process; PTPU_* env multi-host
    ndev = jax.device_count()
    dp = args.dp or max(1, ndev // args.fsdp)
    mesh = make_mesh(MeshConfig(dp=dp, fsdp=args.fsdp))

    if args.tiny:
        vocab, dim, layers, heads, ffn = 1024, 64, 2, 4, 128
    else:
        vocab, dim, layers, heads, ffn = 30522, 768, 12, 12, 3072
    seq, k = args.seq_len, max(1, args.seq_len * 15 // 100)
    dtype = (jnp.bfloat16 if jax.devices()[0].platform == "tpu"
             else jnp.float32)
    model = BertEncoder(vocab=vocab, model_dim=dim, num_heads=heads,
                        num_layers=layers, ffn_dim=ffn, max_len=seq,
                        dropout=0.0, dtype=dtype)

    def loss_fn(module, variables, batch, rng, training):
        tokens, positions, labels = batch
        logits, mut = module.apply(variables, tokens, positions,
                                   training=training, rngs=rng,
                                   mutable=True)
        loss = jnp.mean(F.softmax_with_cross_entropy(
            logits.astype(jnp.float32), labels))
        return (loss, {}), mut.get("state", {})

    trainer = MeshTrainer(
        model, Adam(1e-4), loss_fn, mesh,
        strategy=DistStrategy(reduce_strategy=ReduceStrategy.REDUCE,
                              gradient_accumulation_steps=args.grad_accum),
        rules=fsdp_rules(min_size=1024))

    gbs = args.per_chip_batch * dp * args.grad_accum
    rs = np.random.RandomState(0)
    tokens0 = rs.randint(0, vocab, (gbs, seq)).astype(np.int32)
    pos0 = np.sort(rs.rand(gbs, seq).argsort(1)[:, :k], 1).astype(np.int32)
    ts = trainer.init_state(jnp.asarray(tokens0), jnp.asarray(pos0))
    mgr = CheckpointManager(args.ckpt, max_to_keep=2, async_save=True)
    restored, start = mgr.restore_latest(ts)
    if restored is not None:
        ts, step0 = restored, start
        print(f"resumed from step {start}")
    else:
        step0 = 0

    for step in range(step0, args.steps):
        rs = np.random.RandomState(step)
        batch = trainer.put_batch((
            rs.randint(0, vocab, (gbs, seq)).astype(np.int32),
            np.sort(rs.rand(gbs, seq).argsort(1)[:, :k], 1).astype(np.int32),
            rs.randint(0, vocab, (gbs, k)).astype(np.int32)))
        ts, fetches = trainer.train_step(ts, batch,
                                         rng=jax.random.key(step))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step} loss {float(fetches['loss']):.4f}")
        if (step + 1) % 25 == 0:
            mgr.save(ts, step=step + 1)
    mgr.save(ts, step=args.steps)
    mgr.wait()
    print(f"done: mesh {dict(mesh.shape)}, global batch {gbs}, "
          f"checkpoints at {args.ckpt}")


if __name__ == "__main__":
    main()
