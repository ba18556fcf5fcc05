"""Time the ragged paged-attention kernel alone at each serving cell's
shapes: ms a call, the spans it walks and skips, µs a walked span.

A cell here is one call of the kernel as a serving cell's step makes it:
the pool (blocks, block size, row lanes), the table's width, the step's
query tiles (the prefill budget's and the batch's), and a step's rows as
the cell's traffic fills them — decode rows at the contexts its mix
reaches and a question's or a prompt's chunk — with the rest of the
tiles pads. `CELLS` below states each.

`--parent DIR` times the same calls on the kernel module of another
checkout (an unpacked `git archive` of the parent commit) in the same
process, and checks that every tile with work gives the same bytes on
both. A pad tile points at the null row: context 0 for this checkout's
kernel (it walks nothing), 1 for a kernel from before the walk (PR 38),
which gave every tile one cell and hangs on a tile without any.

Off the TPU the kernel runs in interpret mode at a cut size (a tile in
eight, a context in sixteen): it checks the identity and the counts,
and times nothing.

Run: python tools/ragged_cells.py [--parent _proof/parent] [--cells a,b]
"""

import argparse
import functools
import importlib.util
import os
import sys

import _bootstrap  # noqa: F401  (repo path)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import paged_attention as paged

TILE_Q = 8
# name: pool (blocks, block, kv heads, head dim; latent: its lanes), query
# heads, table width, step tiles, a step's decode rows (count and the
# contexts they reach) and chunks (start, length); the call's keywords
CELLS = {
    "gpt2m-chat": dict(blocks=3072, block=16, heads=16, kv_heads=16, dim=64,
                       table=64, tiles=96, decodes=(31, 60, 560),
                       chunks=[(0, 128)]),
    "gpt2l-docs": dict(blocks=1280, block=16, heads=20, kv_heads=20, dim=64,
                       table=64, tiles=80, decodes=(15, 680, 900),
                       chunks=[(640, 112)]),
    "glm47f-docs8k": dict(blocks=2048, block=128, heads=20, kv_heads=1,
                          dim=576, table=72, tiles=144,
                          decodes=(15, 8250, 8500), chunks=[(8192, 48)],
                          latent=512, name="ragged_latent_attention"),
    "phi4mf-reason.full": dict(blocks=1024, block=128, heads=40, kv_heads=10,
                               dim=128, table=27, tiles=64,
                               decodes=(32, 700, 3000), chunks=[],
                               name="ragged_diff_attention"),
    "phi4mf-reason.window": dict(blocks=225, block=128, heads=40,
                                 kv_heads=10, dim=128, table=27, tiles=64,
                                 decodes=(32, 700, 3000), chunks=[],
                                 window=512, name="ragged_diff_attention"),
    "sala-docs32k": dict(blocks=6144, block=64, heads=16, kv_heads=1,
                         dim=128, table=520, tiles=64,
                         decodes=(31, 32800, 33100), chunks=[(32768, 88)],
                         sparse=97, name="ragged_sparse_attention"),
    "falconh1-reason": dict(blocks=1024, block=128, heads=20, kv_heads=4,
                            dim=128, table=27, tiles=64,
                            decodes=(32, 700, 3000), chunks=[]),
}


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _step(c: dict, seed: int, cut: int):
    """The call's operands as the engine packs a step of the cell's rows
    (numpy), its keywords, and which tiles are pads. `cut` > 1 shrinks
    the tiles and contexts for an interpreted run."""
    rng = np.random.default_rng(seed)
    bs, tq = c["block"], TILE_Q
    shrink = 8 if cut > 1 else 1
    nt = c["tiles"] // shrink
    count, lo, hi = c["decodes"]
    rows = [(int(n) // cut, 1)
            for n in rng.integers(lo, hi, max(count // shrink, 1))]
    rows += [(s // cut + -(-n // cut), -(-n // cut)) for s, n in c["chunks"]]
    if sum(-(-n // tq) for _, n in rows) > nt:
        raise ValueError("the cell's rows do not fit its step's tiles")
    b = len(rows)
    mb = max(c["table"] // cut, 1) if cut > 1 else c["table"]
    nb = max(c["blocks"] // cut, mb + 1)
    bt = rng.integers(1, nb, (b + 1, mb)).astype(np.int32)
    bt[b] = 0
    cl, qs = np.zeros((b + 1,), np.int32), np.zeros((b + 1,), np.int32)
    tr, to = np.full((nt,), b, np.int32), np.zeros((nt,), np.int32)
    mask = rng.random((nt * tq, mb)) < 0.2
    mask[:, 0] = True
    cursor = 0
    for i, (ctx, n) in enumerate(rows):
        ctx = min(ctx, mb * bs)
        cl[i], qs[i] = ctx, ctx - n
        if c.get("sparse") and n == 1:
            # a decode row past dense_len reads its kept blocks through a
            # compacted table, its context shortened to match
            kept = min(c["sparse"] // max(cut, 1) or 1, -(-ctx // bs))
            cl[i] = (kept - 1) * bs + ctx - (ctx - 1) // bs * bs
            qs[i] = cl[i] - 1
            mask[cursor:cursor + tq] = True
        for k in range(-(-n // tq)):
            tr[cursor // tq], to[cursor // tq] = i, k * tq
            cursor += tq
    kw = dict(groups=c["heads"] // c["kv_heads"], name=c.get("name"))
    if "latent" in c:
        kw.update(value_lanes=(0, c["latent"]), scale=0.05)
        lanes = paged.latent_lanes(c["dim"])
    else:
        lanes = c["kv_heads"] * paged.head_lanes(c["dim"])
    if "window" in c:
        kw.update(window=c["window"] // cut if cut > 1 else c["window"])
    if "sparse" in c:
        kw.update(block_mask=jnp.asarray(mask))
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (nt * tq, c["heads"], c["dim"]),
                          jnp.bfloat16)
    pool = jax.random.normal(jax.random.fold_in(key, 1), (nb, bs, lanes),
                             jnp.bfloat16)
    meta = dict(bt=bt, cl=cl, qs=qs, tr=tr, to=to)
    return q, pool, meta, kw, tr == b


def _timed(mod, q, pool, meta, kw, interpret: bool):
    """(ms a call or None, the output) of `mod`'s kernel on the step."""
    # every array an operand: a captured pool would be compiled in
    ops = [pool] + [jnp.asarray(meta[k]) for k in ("bt", "cl", "qs", "tr",
                                                   "to")]
    ops.append(kw.pop("block_mask", None))

    def call(q_, pool_, bt, cl, qs, tr, to, mask):
        return mod.ragged_paged_attention(
            q_, pool_, bt, cl, qs, tr, to, use_kernel=True,
            interpret=interpret, block_mask=mask, **kw)

    run = jax.jit(call)
    out = jax.block_until_ready(run(q, *ops))
    if interpret:
        return None, out
    from paddle_tpu.benchmark.harness import run_timed

    # each call consumes the one before (a zero added to one query), in
    # place: the window holds the kernel and nothing of size beside it
    @functools.partial(jax.jit, donate_argnums=0)
    def chained(q_, *ops_):
        return q_.at[0, 0, 0].add(call(q_, *ops_)[0, 0, 0] * 0)

    def once(q_):
        q_ = chained(q_, *ops)
        return q_, q_
    sec, _, _ = run_timed(once, q + 0, min_time=1.0)
    return sec * 1e3, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--parent", default=None,
                    help="a checkout whose kernel is timed beside this one")
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seed", type=int, default=38)
    args = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cut = 1 if on_tpu else 16
    parent = (_load(os.path.join(args.parent, "paddle_tpu", "kernels",
                                 "paged_attention.py"), "parent_paged")
              if args.parent else None)
    print(f"device {dev.platform} {getattr(dev, 'device_kind', '')}; "
          + ("times in ms a call" if on_tpu else
             "interpreted at a cut size: times not measured"))
    print(f"{'cell':<22}{'tiles':>6}{'pads':>5}{'S':>3}{'grid':>7}"
          f"{'walked':>7}{'skipped':>8}{'parent_ms':>10}{'ms':>9}"
          f"{'us/span':>8}{'us/cell_p':>10}  same")
    ok = True
    for name in args.cells.split(","):
        c = CELLS[name]
        q, pool, meta, kw, pads = _step(c, args.seed, cut)
        nt, bs, mb = meta["tr"].shape[0], pool.shape[1], meta["bt"].shape[1]
        span = paged.ragged_span(bs, pool.shape[2], 2, mb)
        spans = -(-mb // span)
        walk = np.asarray(paged._tile_walk(
            *(jnp.asarray(meta[k]) for k in ("cl", "qs", "tr", "to")),
            tile_q=TILE_Q, span_keys=span * bs, window=kw.get("window")))
        walked = int((walk[1, :nt] - walk[0, :nt]).sum())
        ms, out = _timed(paged, q, pool, meta, dict(kw), not on_tpu)
        line = (f"{name:<22}{nt:>6}{int(pads.sum()):>5}{span:>3}"
                f"{nt * spans:>7}{walked:>7}{nt * spans - walked:>8}")
        if parent is not None:
            old = dict(meta, cl=meta["cl"].copy())
            old["cl"][-1] = 1             # the null row as it was
            pms, pout = _timed(parent, q, pool, old, dict(kw), not on_tpu)
            real = ~np.repeat(pads, out.shape[0] // nt)
            same = bool(np.array_equal(np.asarray(out)[real],
                                       np.asarray(pout)[real]))
            ok &= same
            # the parent's cells with work: the walked spans, a pad one
            per_cell = (f"{pms * 1e3 / (walked + pads.sum()):>10.3f}"
                        if pms else f"{'-':>10}")
            line += (f"{pms:>10.3f}" if pms else f"{'-':>10}") + \
                (f"{ms:>9.3f}{ms * 1e3 / walked:>8.3f}" if ms else
                 f"{'-':>9}{'-':>8}") + per_cell + f"  {same}"
        else:
            line += f"{'-':>10}" + (f"{ms:>9.3f}{ms * 1e3 / walked:>8.3f}"
                                    if ms else f"{'-':>9}{'-':>8}")
        zero = not np.asarray(out).reshape(nt, -1)[pads].any()
        ok &= zero and bool(np.isfinite(np.asarray(out, np.float32)).all())
        print(line + ("" if zero else "  PAD TILES NOT ZERO"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
