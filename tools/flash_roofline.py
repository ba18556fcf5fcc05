"""Flash-kernel roofline at long sequence lengths.

Measures the Pallas flash attention kernels IN ISOLATION — forward, and
the two backward kernels via the custom-vjp — at the lm_longctx
attention shape (bs 1, 8 heads, head_dim 64, causal, bf16), sweeping
sequence length and block sizes, with the ResNet-standard analysis:
FLOPs, bytes streamed, arithmetic intensity, achieved TFLOP/s vs the
same-day sustained-matmul ceiling.

FLOPs convention (model basis, matching benchmark/models.py): causal
attention does 4*T^2*d*h/2 fwd MACs*2 = 2*T^2*d*h fwd FLOPs and 2x that
bwd (the dq/dkv recompute is NOT counted as useful work — the remat
convention).

Bytes model per fwd kernel launch (grid bh x nq x nk, causal skips
compute but still streams skipped blocks' K/V):
  reads = bh * nq * nk * (bq + 2*bk) * d * 2B, writes = bh*T*d*2B.

Run: python tools/flash_roofline.py [--seqs 8192,16384,32768]
"""

import argparse
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.benchmark.harness import (run_timed,
                                          sustained_matmul_flops)
from paddle_tpu.kernels import flash as FL


def _measure(step, state, min_time=1.2):
    """DCE-proof chained timing: carry = sum(out)*1e-30 feeds the next
    call, so the pool cannot cache and XLA cannot narrow the op."""
    f = jax.jit(step)

    def once(s):
        out = f(s)
        return out, out

    sec, _, _ = run_timed(once, state, min_time=min_time)
    return sec


def kernel_rates(t, bq, bk, heads=8, d=64, bs=1):
    rs = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rs.randn(bs, t, heads, d), jnp.bfloat16) * 0.3
    q, k, v = mk(), mk(), mk()

    fwd_flops = 2.0 * bs * t * t * d * heads      # causal model basis
    bwd_flops = 2.0 * fwd_flops

    def fwd_step(c):
        o = FL.flash_attention(q + c.astype(q.dtype), k, v, causal=True,
                               block_q=bq, block_k=bk)
        return (jnp.sum(o.astype(jnp.float32)) * 1e-30).astype(jnp.float32)

    def bwd_step(c):
        def loss(q_, k_, v_):
            o = FL.flash_attention(q_, k_, v_, causal=True,
                                   block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32))
        g = jax.grad(loss, argnums=(0, 1, 2))(q + c.astype(q.dtype), k, v)
        return (sum(jnp.sum(x.astype(jnp.float32)) for x in g)
                * 1e-30).astype(jnp.float32)

    z = jnp.zeros((), jnp.float32)
    t_fwd = _measure(fwd_step, z)
    t_all = _measure(bwd_step, z)
    t_bwd = max(t_all - t_fwd, 1e-9)

    nq, nk = -(-t // bq), -(-t // bk)
    bh = bs * heads
    fwd_bytes = bh * nq * nk * (bq + 2 * bk) * d * 2 + bh * t * d * 2
    return {
        "fwd_ms": t_fwd * 1e3, "bwd_ms": t_bwd * 1e3,
        "fwd_tflops": fwd_flops / t_fwd / 1e12,
        "bwd_tflops": bwd_flops / t_bwd / 1e12,
        "fwd_GB": fwd_bytes / 1e9,
        "fwd_flop_per_byte": fwd_flops / fwd_bytes,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="8192,16384,32768")
    ap.add_argument("--blocks", default="256x512,512x512,512x1024,"
                                        "1024x1024,512x2048")
    args = ap.parse_args()
    assert jax.devices()[0].platform == "tpu", "roofline needs the TPU"

    ceil = sustained_matmul_flops() or 197e12
    print(f"device {jax.devices()[0].device_kind}; same-day sustained "
          f"matmul {ceil/1e12:.1f} TFLOP/s")

    seqs = [int(s) for s in args.seqs.split(",")]
    blocks = [tuple(map(int, b.split("x")))
              for b in args.blocks.split(",")]
    for t in seqs:
        for (bq, bk) in blocks:
            if bk > t or bq > t:
                continue
            r = kernel_rates(t, bq, bk)
            print(f"T={t:6d} blocks=({bq:4d},{bk:4d})  "
                  f"fwd {r['fwd_ms']:7.2f} ms {r['fwd_tflops']:6.1f} TF/s "
                  f"({r['fwd_tflops']*1e12/ceil*100:4.1f}% ceil)  "
                  f"bwd {r['bwd_ms']:7.2f} ms {r['bwd_tflops']:6.1f} TF/s "
                  f"({r['bwd_tflops']*1e12/ceil*100:4.1f}% ceil)  "
                  f"AI {r['fwd_flop_per_byte']:5.0f} FLOP/B "
                  f"streamed {r['fwd_GB']:5.1f} GB")


if __name__ == "__main__":
    main()
