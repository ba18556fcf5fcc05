"""Seq2seq Transformer MFU attack kit (48.6% -> >=55%).

Run ON TPU. Sweeps structural variants of the Transformer-base train step
and prints tokens/s + MFU per variant, then dumps the device-tier op
table for the baseline and the best variant so the residual time (decoder
cross-attention, short-seq dense attention, vocab/logits path) can be
attributed. Variants are pure re-layouts or dtype-path choices — model
math is unchanged (tests/test_transformer.py pins fused-qkv parity).

Usage: python tools/profile_transformer.py [--bs 64] [--seq 256]
       [--trace]   (trace: also dump profiler op tables, slower)
"""

import argparse
import sys

import _bootstrap  # noqa: F401  (repo path)
import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=64)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--min-time", type=float, default=2.5)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--sweep-bs", action="store_true",
                    help="also sweep batch sizes for the best variant")
    args = ap.parse_args()

    import jax.numpy as jnp

    from paddle_tpu.benchmark import run_model

    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    if not on_tpu:
        print("WARNING: not on TPU — numbers are CPU smoke only")

    # raw_ce and fused_ce address the same logits path (fused_ce subsumes
    # raw_ce), so sweep fused_qkv x {plain, raw_ce, fused_ce}
    variants = [(f, r, c) for f in (False, True)
                for r, c in ((False, False), (True, False), (False, True))]

    results = {}
    for fused, raw, fce in variants:
        label = "+".join(n for n, on in (("fused_qkv", fused),
                                         ("raw_ce", raw),
                                         ("fused_ce", fce)) if on) or "baseline"
        try:
            r = run_model(
                "transformer", batch_size=args.bs, dtype=dtype,
                min_time=args.min_time, seq_len=args.seq,
                fused_qkv=fused, raw_ce=raw, fused_ce=fce)
        except Exception as e:  # a dead variant shouldn't kill the sweep
            print(f"{label:24s} FAILED: {type(e).__name__}: {e}")
            continue
        results[label] = r
        print(f"{label:24s} {r.value:12.0f} tok/s  "
              f"mfu={r.mfu:.4f}  {r.ms_per_step:7.2f} ms"
              if r.mfu else f"{label:24s} {r.value:12.0f} tok/s")

    if not results:
        print("\nall variants failed")
        return 1
    best = max(results, key=lambda k: results[k].value)
    base = results.get("baseline")
    rel = (f"  (+{(results[best].value / base.value - 1) * 100:.1f}%"
           f" vs baseline)") if base else ""
    print(f"\nbest: {best}{rel}")

    def _knobs(label):
        return dict(fused_qkv="fused_qkv" in label,
                    raw_ce="raw_ce" in label,
                    fused_ce="fused_ce" in label)

    if args.sweep_bs:
        for bs in (32, 64, 96, 128):
            try:
                r = _retry(lambda: run_model(
                    "transformer", batch_size=bs, dtype=dtype,
                    min_time=args.min_time, seq_len=args.seq,
                    **_knobs(best)))
                print(f"bs={bs:4d}  {r.value:12.0f} tok/s  "
                      f"mfu={r.mfu:.4f}" if r.mfu
                      else f"bs={bs:4d}  {r.value:12.0f} tok/s")
            except Exception as e:   # OOM at large bs is a data point
                print(f"bs={bs:4d}  failed: {type(e).__name__}: {e}")

    if args.trace:
        import tempfile

        from paddle_tpu.profiler.device_trace import op_table
        for label in dict.fromkeys(("baseline", best)):
            if label not in results:
                continue
            d = tempfile.mkdtemp(prefix=f"xf_{label.replace('+', '_')}_")
            with jax.profiler.trace(d):
                _retry(lambda: run_model(
                    "transformer", batch_size=args.bs, dtype=dtype,
                    min_time=1.0, seq_len=args.seq, **_knobs(label)))
            print(f"\n=== op table: {label} ===")
            try:
                print(op_table(d, by="category", steps=3))
            except Exception as e:
                print(f"(op_table failed: {e}; raw trace in {d})")


if __name__ == "__main__":
    sys.exit(main())
