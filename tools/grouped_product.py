"""Time the experts' grouped products alone at the expert cells' shapes:
the kernel's two calls (gate and up in one, down in the other) against
`jax.lax.ragged_dot`'s three, ms a call and the weights' GB/s.

A case is one expert layer of a serving step: the flat rows the step
packs (its tokens x top-k pairs, the pads sorted behind every expert),
the pairs its real rows make (a decode step's rows, or a question's
chunk beside them), routed by seeded scores to the top-k of the
layer's experts. Bytes are the weights of the experts the pairs touch
(the least a call must read), over the call's time, against the chip's
819 GB/s.

`--row-tiles 64,128,256` also times the kernel's layer at other row
tiles than the one `_tiles` picks (the weight block's width stays
`_tiles`').

Off the TPU both run at a cut size (widths / 16), the kernel
interpreted: it checks the identity and the zeros, and times nothing.

Run: python tools/grouped_product.py [--cases a,b] [--row-tiles 64,128]
"""

import argparse
import functools
import sys

import _bootstrap  # noqa: F401  (repo path)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import grouped_product as gp

HBM_BYTES_PER_S = 819e9
# name: d, f, experts, top-k, the step's flat rows, its real rows
CASES = {
    "lfm2.decode": dict(d=2048, f=1792, experts=32, k=4, flat=512, real=32),
    "lfm2.chunk": dict(d=2048, f=1792, experts=32, k=4, flat=512,
                       real=256 + 31),
    "docs8k.decode": dict(d=2048, f=1536, experts=64, k=4, flat=1152,
                          real=16),
    "docs8k.chunk": dict(d=2048, f=1536, experts=64, k=4, flat=1152,
                         real=200 + 15),
}


def _layer(c: dict, seed: int, cut: int):
    """(x sorted by expert [flat * k, d], gate, up, down, counts)."""
    rng = np.random.default_rng(seed)
    d, f, e, k = c["d"] // cut, c["f"] // cut, c["experts"], c["k"]
    scores = rng.random((c["real"], e))
    picks = np.argsort(-scores, axis=1)[:, :k].reshape(-1)
    counts = np.bincount(picks, minlength=e).astype(np.int32)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 4)
    x = jax.random.normal(keys[0], (c["flat"] * k, d), jnp.bfloat16)
    gate, up = (jax.random.normal(kk, (e, d, f), jnp.bfloat16) / 32
                for kk in keys[1:3])
    down = jax.random.normal(keys[3], (e, f, d), jnp.bfloat16) / 32
    return x, gate, up, down, jnp.asarray(counts)


def _timed(fn, x, ops, on_tpu: bool):
    """ms a call of `fn(x, *ops)`, each call consuming the one before
    (a zero added to one input row), or None off the TPU."""
    if not on_tpu:
        return None
    from paddle_tpu.benchmark.harness import run_timed

    @functools.partial(jax.jit, donate_argnums=0)
    def chained(x_, *ops_):
        return x_.at[0, 0].add(fn(x_, *ops_)[0, 0] * 0)

    def once(x_):
        x_ = chained(x_, *ops)
        return x_, x_
    sec, _, _ = run_timed(once, x + 0, min_time=1.0)
    return sec * 1e3


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--row-tiles", default="")
    ap.add_argument("--seed", type=int, default=40)
    args = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cut = 1 if on_tpu else 16
    print(f"device {dev.platform} {getattr(dev, 'device_kind', '')}; "
          + ("ms a call, GB/s of the touched experts' weights" if on_tpu
             else "interpreted at a cut size: times not measured"))
    print(f"{'case':<15}{'pairs':>6}{'touched':>8}{'MB':>8}"
          f"{'ragged_ms':>10}{'gate_up':>9}{'down':>8}{'layer':>8}"
          f"{'GB/s':>7}{'%819':>6}{'ragged%':>8}  same")
    ok = True
    for name in args.cases.split(","):
        c = CASES[name]
        x, gate, up, down, counts = _layer(c, args.seed, cut)
        touched = int((counts > 0).sum())
        pairs = int(counts.sum())
        mb = touched * 3 * gate.shape[1] * gate.shape[2] * 2 / 1e6

        def kernel(x_, g_, u_, d_, c_, tm=None):
            if tm is None:
                h = gp.gated_grouped_product(x_, g_, u_, c_, use_kernel=True,
                                             interpret=not on_tpu)
                return gp.grouped_product(h, d_, c_, use_kernel=True,
                                          interpret=not on_tpu)
            _, tn = gp._tiles(x_.shape[0], x_.shape[1], g_.shape[2], 2, 2)
            h = gp._call(x_, (g_, u_), c_, tm=tm, tn=tn,
                         name="grouped_gate_up", interpret=False)
            _, tn = gp._tiles(h.shape[0], h.shape[1], d_.shape[2], 1, 2)
            return gp._call(h, (d_,), c_, tm=tm, tn=tn,
                            name="grouped_product", interpret=False)

        def reference(x_, g_, u_, d_, c_):
            h = gp.gated_grouped_product(x_, g_, u_, c_, use_kernel=False)
            return gp.grouped_product(h, d_, c_, use_kernel=False)

        def gate_up(x_, g_, u_, c_):
            return gp.gated_grouped_product(x_, g_, u_, c_, use_kernel=True,
                                            interpret=not on_tpu)

        def down_only(h_, d_, c_):
            return gp.grouped_product(h_, d_, c_, use_kernel=True,
                                      interpret=not on_tpu)
        ops = (gate, up, down, counts)
        got = jax.jit(kernel)(x, *ops)
        want = jax.jit(reference)(x, *ops)
        got32 = np.asarray(got, np.float32)
        diff = np.abs(got32[:pairs] - np.asarray(want, np.float32)[:pairs])
        scale = max(float(np.abs(np.asarray(want, np.float32)).max()), 1e-30)
        same = bool(diff.max() <= 0.02 * scale) and not got32[pairs:].any()
        ok &= same
        ms_ref = _timed(reference, x, ops, on_tpu)
        ms_layer = _timed(kernel, x, ops, on_tpu)
        ms_gu = _timed(gate_up, x, (gate, up, counts), on_tpu)
        h = jax.random.normal(jax.random.PRNGKey(1),
                              (x.shape[0], gate.shape[2]), jnp.bfloat16)
        ms_down = _timed(down_only, h, (down, counts), on_tpu)

        def num(v, w, fmt):
            return f"{v:>{w}{fmt}}" if v is not None else f"{'-':>{w}}"
        gbs = mb / 1e3 / (ms_layer / 1e3) if ms_layer else None
        gbs_ref = mb / 1e3 / (ms_ref / 1e3) if ms_ref else None
        print(f"{name:<15}{pairs:>6}{touched:>8}{mb:>8.1f}"
              + num(ms_ref, 10, ".3f") + num(ms_gu, 9, ".3f")
              + num(ms_down, 8, ".3f") + num(ms_layer, 8, ".3f")
              + num(gbs, 7, ".0f")
              + num(gbs and 100 * gbs * 1e9 / HBM_BYTES_PER_S, 6, ".1f")
              + num(gbs_ref and 100 * gbs_ref * 1e9 / HBM_BYTES_PER_S, 8,
                    ".1f")
              + f"  {same} (max diff {diff.max():.3g} of {scale:.3g})",
              flush=True)
        for tm in (int(t) for t in args.row_tiles.split(",") if t):
            if not on_tpu:
                break
            ms = _timed(functools.partial(kernel, tm=tm), x, ops, on_tpu)
            print(f"  row tile {tm}: layer {ms:.3f} ms, "
                  f"{mb / ms:.0f} GB/s", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
