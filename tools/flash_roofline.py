"""Flash-kernel roofline at long sequence lengths.

Measures the Pallas flash attention kernels IN ISOLATION — forward, and
the backward via the custom-vjp (two kernels at these lengths) — at the
lm_longctx attention shape (bs 1, 8 heads, head_dim 64, causal, bf16), sweeping
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

`--train-cell` prints one layer instead: the Pallas calls of
`gpt2m-train-1k`'s attention (bs 8, 16 heads, T 1,024, head_dim 64,
causal, bf16, the default blocks) timed apart on [BH, T, D] operands, so
without the transposes around them: the forward with its lse, and the
backward as `_bwd_impl` picks it from the shapes; where the module has
them, also the two schedules `_bwd_impl` picks between. It reads only
names that the kernels before PR 34 have too (`_fwd`, `_bwd_impl`,
`_default_blocks`), so the same file times a parent checkout.
`--other-callers` does the same for two shapes no cell runs: T 2,048
causal, and a packed batch that is not causal (four documents a row of
1,024); `--block N` takes blocks of N in place of the default.

Run: python tools/flash_roofline.py [--seqs 8192,16384,32768]
     python tools/flash_roofline.py --train-cell [--other-callers]
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


def layer_rows(ceil, bs=8, t=1024, heads=16, d=64, causal=True, docs=None,
               block=None):
    """Forward and backward of one layer's attention, apart. `docs`: the
    lengths of the documents packed into a row (segment ids); `block`:
    a block size in place of `_default_blocks`'."""
    rs = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rs.randn(bs * heads, t, d) * 0.5, jnp.bfloat16)
    q, k, v, do = mk(), mk(), mk(), mk()
    bq, bk = (min(block or b, t) for b in FL._default_blocks(t, t))
    scale = 1.0 / d ** 0.5
    seg = None
    if docs is not None:
        if sum(docs) != t:
            raise ValueError(f"documents {docs} do not fill a row of {t}")
        seg = jnp.tile(jnp.repeat(jnp.arange(len(docs), dtype=jnp.int32),
                                  np.asarray(docs))[None], (bs, 1))

    def fwd(q_):
        return FL._fwd(q_, k, v, seg, seg, None, scale, causal, None, bq,
                       bk, False, want_lse=True, dropout_rate=0.0,
                       heads=heads)

    residuals = jax.jit(fwd)
    o, lse = residuals(q)

    def fold(xs):
        return (sum(jnp.sum(x.astype(jnp.float32)) for x in xs)
                * 1e-30).astype(jnp.float32)

    def bwd(impl):
        return lambda c: fold(impl(
            q + c.astype(q.dtype), k, v, o, lse, do, seg, seg, None,
            scale, causal, None, bq, bk, False, 0.0, heads))

    z = jnp.zeros((), jnp.float32)
    # model basis: the visible (query, key) pairs, 4 FLOPs x d each
    pairs = sum(n * n for n in docs or (t,)) / (2.0 if causal else 1.0)
    fwd_flops = 4.0 * bs * heads * pairs * d
    need = {"fwd": fwd_flops, "bwd": 2.0 * fwd_flops}
    rows = [("fwd", need["fwd"],
             # o alone feeds the chain: the call writes its lse all the
             # same, and summing 128 lanes of it would be timed with it
             _measure(lambda c: fold(fwd(q + c.astype(q.dtype))[:1]), z))]
    for name in ("_bwd_impl", "_bwd_one_pass", "_bwd_two_kernels"):
        if hasattr(FL, name):
            rows.append((name, need["bwd"],
                         _measure(bwd(getattr(FL, name)), z)))
    print(f"bs={bs} heads={heads} T={t} d={d} causal={causal} "
          f"docs={docs} blocks=({bq},{bk}), a layer:")
    for name, flops, sec in rows:
        print(f"  {name:17s} {sec * 1e3:7.3f} ms  "
              f"{flops / sec / 1e12:6.1f} TF/s needed  "
              f"{100 * flops / sec / ceil:5.1f}% of ceil", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-cell", action="store_true")
    ap.add_argument("--other-callers", action="store_true")
    ap.add_argument("--block", type=int, default=None)
    ap.add_argument("--seqs", default="8192,16384,32768")
    ap.add_argument("--blocks", default="256x512,512x512,512x1024,"
                                        "1024x1024,512x2048")
    args = ap.parse_args()
    assert jax.devices()[0].platform == "tpu", "roofline needs the TPU"
    ceil = sustained_matmul_flops() or 197e12
    print(f"device {jax.devices()[0].device_kind}; same-day sustained "
          f"matmul {ceil/1e12:.1f} TFLOP/s")
    if args.train_cell:
        layer_rows(ceil, block=args.block)
    if args.other_callers:
        layer_rows(ceil, bs=4, t=2048, block=args.block)
        layer_rows(ceil, causal=False, docs=(384, 128, 256, 256),
                   block=args.block)
    if args.train_cell or args.other_callers:
        return

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
