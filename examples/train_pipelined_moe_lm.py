"""Pipeline + expert parallelism in one training run.

A decoder-only LM whose transformer blocks are pipeline stages (pp axis,
GPipe microbatch streaming — O(batch/S) resident input per device) trained
through MeshTrainer on a pp×dp mesh, next to a standalone top-2 MoE FFN
dispatched with all_to_all over the ep axis — the two parallelism modes the
reference lacks (SURVEY §2.6), in their TPU-native form. Runs unchanged on
one chip, a TPU slice, or the virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_pipelined_moe_lm.py --pp 4 --dp 2

Multi-host: wrap with `python -m paddle_tpu.parallel.launch --nproc N`.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.optim.optimizer import Adam
from paddle_tpu.parallel import (DistStrategy, MeshConfig, MeshTrainer,
                                 PipelinedLM, make_mesh, pipeline_rules,
                                 pipelined_lm_loss)
from paddle_tpu.parallel.moe import (init_moe_params, load_balancing_loss,
                                     moe_ffn_a2a)
from paddle_tpu.utils.compile_cache import enable_compile_cache


def sequence_batch(rs, batch, seq, vocab):
    """Learnable stream: next token = (token + 1) mod vocab."""
    start = rs.randint(0, vocab, (batch, 1))
    toks = (start + np.arange(seq + 1)) % vocab
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=0,
                    help="pipeline stages (0 = largest divisor of the "
                         "device count <= 4)")
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel width (0 = remaining devices)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism INSIDE each pipeline stage "
                         "(Megatron column/row splits; pp×tp×dp 3D)")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence parallelism INSIDE each stage (ring "
                         "attention over sequence shards; composes with "
                         "--tp for pp×tp×sp×dp)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=64)
    args = ap.parse_args()

    # ---- pipelined LM on pp(×tp×sp)×dp ---------------------------------
    n = jax.device_count()
    if n % (args.tp * args.sp):
        raise SystemExit(
            f"--tp {args.tp} × --sp {args.sp} must divide device count {n}")
    if not args.pp:   # adapt to whatever devices exist (1 chip included)
        args.pp = max(c for c in (1, 2, 4)
                      if n % (c * args.tp * args.sp) == 0)
    args.dp = args.dp or n // (args.pp * args.tp * args.sp)
    mesh = make_mesh(MeshConfig(pp=args.pp, tp=args.tp, sp=args.sp,
                                dp=args.dp))
    tp_axis = "tp" if args.tp > 1 else None
    sp_axis = "sp" if args.sp > 1 else None
    lm = PipelinedLM(args.vocab, d_model=64, n_heads=4, d_ff=128,
                     num_stages=args.pp, max_len=args.seq)
    trainer = MeshTrainer(
        lm, Adam(3e-3),
        pipelined_lm_loss(mesh, num_microbatches=2 * args.pp,
                          tp_axis=tp_axis, sp_axis=sp_axis),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules(tp_axis=tp_axis))

    rs = np.random.RandomState(0)
    src, trg = sequence_batch(rs, args.batch, args.seq, args.vocab)
    state = trainer.init_state(jnp.asarray(src))
    batch = trainer.put_batch((src, trg))
    for step in range(args.steps):
        state, fetches = trainer.train_step(state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"[lm pp={args.pp}×tp={args.tp}×sp={args.sp}"
                  f"×dp={args.dp}] "
                  f"step {step:3d} "
                  f"loss {float(fetches['loss']):.4f}")

    logits = lm.apply({"params": jax.device_get(state.params)},
                      jnp.asarray(src))
    acc = float((jnp.argmax(logits, -1) == jnp.asarray(trg)).mean())
    print(f"[lm] greedy next-token accuracy (dense forward): {acc:.3f}")

    # ---- top-2 MoE FFN with all_to_all dispatch on ep ------------------
    ep = n   # all devices become expert shards
    mesh_ep = make_mesh(MeshConfig(ep=ep))
    params = init_moe_params(jax.random.key(0), num_experts=2 * ep,
                             d_model=32, d_hidden=64)
    x = jnp.asarray(rs.randn(16 * ep, 32), jnp.float32)
    y, aux = jax.jit(lambda p, x: moe_ffn_a2a(
        p, x, mesh=mesh_ep, k=2, capacity_factor=1.5))(params, x)
    print(f"[moe ep={ep}] tokens {x.shape[0]} -> y {tuple(y.shape)}, "
          f"dropped {float(aux['dropped_fraction']):.3f}, "
          f"balance loss {float(load_balancing_loss(aux)):.3f}")


if __name__ == "__main__":
    main()
