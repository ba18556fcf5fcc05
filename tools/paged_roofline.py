"""Paged-KV roofline: size the block pool against HBM, bound decode.

Sweeps (block_size x num_blocks) cells and reports, per cell:

- pool_gb:    KV pool footprint = layers * NB * BS * Hkv * W * 2B, W =
              kernels/paged_attention.py head_lanes(Dh): a head's K and V
              side by side in one bf16 row, padded to whole 128-lane
              tiles (2 * Dh at Dh 64 and 128), and the fraction of the
              rig's HBM it claims (--hbm-gb).
- capacity:   tokens the pool can hold (NB * BS) and the context each
              of --batch concurrent decodes gets at full occupancy.
- decode bytes/token: a decode step streams every live block of the
              row's context once (the ragged kernel's skip predicate
              elides only past-context blocks, so partial tail blocks
              still stream whole): layers * ceil(ctx/BS) * BS *
              Hkv * W * 2B. Arithmetic intensity of paged decode is
              ~1 FLOP/byte, far left of the ridge, so the HBM ceiling
              IS the decode ceiling:
- tok_s_ceiling: --hbm-gbps / bytes_per_token — the best any kernel
              can do at that context length on this rig.

`--spec-k K1,K2,...` appends one column per K modelling speculative
decoding's amortization: a verification step streams the SAME context
bytes as a plain decode step (the window rides the existing per-row
tile, so the kernel's streamed bytes don't grow with K), but emits
E = (1-a^(K+1))/(1-a) tokens in expectation at per-token acceptance
`--spec-accept a` (K+1 when a == 1). Effective bytes/emitted-token =
bytes_per_token / E, so the emitted-token ceiling scales by E. Output
is unchanged when the flag is absent.

`--compress-blocks C` models the in-device int8 compressed tier
(engine `kv_compress_blocks` knob): a parallel C-block int8 pool holds
cold prefix blocks at half the fp bytes (+4 B of scales per block per
plane, negligible), so warm-prefix capacity grows to (NB + C) * BS
tokens for C * BS * Hkv * Dh bytes/layer of extra HBM (the `qpool_gb`
column). The `KB/t_mix` column is the streamed-bytes account at mixed
residency r = C / (NB + C): the SHIPPED ragged step reads int8-resident
blocks in place (bias-encoded block-table ids steer each block's DMA to
the fp or the int8 pool; per-block scales ride scalar prefetch), so the
compressed fraction streams half the bytes. `--direct-int8` exercises
that path: the CPU smoke runs the mixed kernel on a half-quantized pool
(parity vs the XLA reference AND bit-identity vs dequantize-then-read),
and `--rig` times the mixed kernel at the cell's residency instead of
the fp-only kernel. Output is unchanged when the flags are absent.

`--tp-size N` models tensor-parallel serving (engine `tp_size` knob):
the KV pool is sharded over kv-heads, so the per-chip pool and the
per-chip streamed bytes/token both drop by N, lifting the per-chip
decode ceiling by N — at the price of one decode-MLP allreduce per
layer. The `ar_fp/ar_i8` columns price that collective's wire bytes
per token (serve_collective.allreduce_wire_bytes: fp ring vs EQuARX
int8 all-gather with per-256-chunk scales); it rides the ICI, not HBM,
so it widens no HBM column but bounds how small a per-token step can
shrink before the collective dominates.

Default run is a CPU smoke: prints the analytic sweep and validates the
ragged kernel end-to-end in interpret mode on one tiny cell (finite
output, matches the XLA reference). `--rig` additionally times the
real kernel per cell on the TPU (run_timed two-window subtraction,
state-chained) and reports achieved GB/s against --hbm-gbps.

Run: python tools/paged_roofline.py [--rig] [--block-sizes 8,16,32]
     [--num-blocks 512,2048,8192] [--hbm-gb 16 --hbm-gbps 819]
     [--spec-k 2,4,8 --spec-accept 0.7] [--tp-size 2]
"""

import argparse
import sys

import _bootstrap  # noqa: F401  (repo path)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels.paged_attention import head_lanes, pack_kv, unpack_kv


def kv_pool_bytes(layers, num_blocks, block_size, kv_heads, head_dim,
                  dtype_bytes=2):
    return layers * num_blocks * block_size * kv_heads \
        * head_lanes(head_dim) * dtype_bytes


def decode_bytes_per_token(layers, ctx, block_size, kv_heads, head_dim,
                           dtype_bytes=2):
    blocks = -(-ctx // block_size)
    return layers * blocks * block_size * kv_heads \
        * head_lanes(head_dim) * dtype_bytes


def expected_emitted(spec_k, accept):
    """Expected tokens emitted per verification step with a K-token
    draft at i.i.d. per-token acceptance `accept`: the accepted prefix
    length is geometric, truncated at K, plus the one token the step
    always emits — sum_{j=0..K} accept^j = (1-a^(K+1))/(1-a)."""
    if accept >= 1.0:
        return float(spec_k + 1)
    return (1.0 - accept ** (spec_k + 1)) / (1.0 - accept)


def _ragged_decode_operands(batch, ctx, block_size, num_blocks, heads,
                            kv_heads, head_dim, tile_q=8, seed=0):
    """Flat-packed pure-decode batch: one tile per row, query at the
    last written position, distinct blocks per row. Returns the
    kernel's positional operands (the pool in the cache's layout) and
    its `groups` keyword."""
    rs = np.random.RandomState(seed)
    mb = -(-ctx // block_size)
    assert batch * mb <= num_blocks, "pool too small for the sweep cell"
    t_flat = batch * tile_q
    q = jnp.asarray(rs.randn(t_flat, heads, head_dim), jnp.float32) * 0.3
    kv_pool = pack_kv(*(
        jnp.asarray(rs.randn(num_blocks, block_size, kv_heads, head_dim),
                    jnp.float32) * 0.3 for _ in range(2)))
    perm = rs.permutation(num_blocks)
    bt = np.zeros((batch + 1, mb), np.int32)
    for i in range(batch):
        bt[i] = perm[i * mb:(i + 1) * mb]
    cl = np.full((batch + 1,), ctx, np.int32)
    cl[batch] = 1                               # null row contract
    qs = np.full((batch + 1,), ctx - 1, np.int32)
    qs[batch] = 0
    tr = np.arange(batch, dtype=np.int32)       # one tile per row
    to = np.zeros((batch,), np.int32)
    return ((q, kv_pool, jnp.asarray(bt), jnp.asarray(cl),
             jnp.asarray(qs), jnp.asarray(tr), jnp.asarray(to)),
            {"groups": heads // kv_heads})


def _quantize_operand_blocks(ops, int8_frac, seed=1):
    """Move ~int8_frac of each row's referenced blocks into an int8
    side pool, bias-encoding their table entries (-slot-1). Returns
    (mixed_ops, qpool_kwargs, promoted_ops, n_int8, n_total):
    promoted_ops is the same batch with the quantized blocks
    dequantized back into the fp pool — the direct-read output must be
    byte-identical to reading THAT (the promote path)."""
    from paddle_tpu.quant.int8_compute import dequantize_block, \
        quantize_block

    (q, kv_pool, bt, cl, qs, tr, to) = ops
    k_pool, v_pool = unpack_kv(kv_pool, q.shape[-1])
    bt = np.asarray(bt).copy()
    stride = max(1, round(1.0 / max(int8_frac, 1e-9)))
    kq, vq, ksc, vsc = [], [], [], []
    k_pro = np.asarray(k_pool).copy()
    v_pro = np.asarray(v_pool).copy()
    bt_mixed = bt.copy()
    n_total = 0
    rows = bt.shape[0] - 1                      # last row is the null row
    for i in range(rows):
        blocks = -(-int(cl[i]) // k_pool.shape[1])
        n_total += blocks
        for j in range(blocks):
            if j % stride != stride - 1:
                continue
            b = int(bt[i, j])
            q1, s1 = quantize_block(k_pool[b][None])
            q2, s2 = quantize_block(v_pool[b][None])
            bt_mixed[i, j] = -(len(kq) + 1)
            kq.append(np.asarray(q1[0]))
            ksc.append(float(s1[0]))
            vq.append(np.asarray(q2[0]))
            vsc.append(float(s2[0]))
            k_pro[b] = np.asarray(dequantize_block(q1, s1, k_pool.dtype)[0])
            v_pro[b] = np.asarray(dequantize_block(q2, s2, v_pool.dtype)[0])
    if not kq:                                  # keep the pools non-empty
        kq.append(np.zeros(k_pool.shape[1:], np.int8))
        vq.append(np.zeros(v_pool.shape[1:], np.int8))
        ksc.append(1.0)
        vsc.append(1.0)
    qkw = dict(kvq_pool=jnp.asarray(pack_kv(np.stack(kq), np.stack(vq))),
               k_scales=jnp.asarray(ksc, jnp.float32),
               v_scales=jnp.asarray(vsc, jnp.float32))
    mixed = (q, kv_pool, jnp.asarray(bt_mixed), cl, qs, tr, to)
    promoted = (q, jnp.asarray(pack_kv(k_pro, v_pro)),
                jnp.asarray(bt), cl, qs, tr, to)
    return mixed, qkw, promoted, len(kq), n_total


def smoke_interpret(direct_int8=False):
    """Tiny end-to-end validation: interpret-mode kernel vs reference;
    with direct_int8 also the mixed-precision path on a half-quantized
    pool, including bit-identity vs the promote (dequantize-first)
    read."""
    from paddle_tpu.kernels import paged_attention as paged

    ops, gkw = _ragged_decode_operands(batch=2, ctx=10, block_size=4,
                                       num_blocks=16, heads=4, kv_heads=2,
                                       head_dim=8)
    ref = paged.ragged_paged_attention(*ops, use_kernel=False, **gkw)
    out = paged.ragged_paged_attention(*ops, use_kernel=True,
                                       interpret=True, **gkw)
    diff = float(jnp.max(jnp.abs(out - ref)))
    ok = bool(np.isfinite(diff) and diff < 1e-5)
    print(f"interpret smoke: kernel vs reference max|diff| = {diff:.2e} "
          f"-> {'OK' if ok else 'FAIL'}")
    if not direct_int8:
        return ok
    mixed, qkw, promoted, n8, nt = _quantize_operand_blocks(ops, 0.5)
    mref = paged.ragged_paged_attention_reference(*mixed, **qkw, **gkw)
    mout = paged.ragged_paged_attention(*mixed, use_kernel=True,
                                        interpret=True, **qkw, **gkw)
    mdiff = float(jnp.max(jnp.abs(mout - mref)))
    pout = paged.ragged_paged_attention(*promoted, use_kernel=True,
                                        interpret=True, **gkw)
    exact = bool(np.array_equal(np.asarray(mout), np.asarray(pout)))
    mok = bool(np.isfinite(mdiff) and mdiff < 1e-5 and exact)
    print(f"direct-int8 smoke: {n8}/{nt} blocks int8; mixed kernel vs "
          f"reference max|diff| = {mdiff:.2e}; bit-identical to the "
          f"promote read: {exact} -> {'OK' if mok else 'FAIL'}")
    return ok and mok


def measure_cell(batch, ctx, block_size, num_blocks, heads, kv_heads,
                 head_dim, tile_q=8, int8_frac=0.0):
    """Time one ragged decode launch on the rig; returns (ms, GB/s).
    int8_frac > 0 times the MIXED kernel with that fraction of each
    row's blocks int8-resident (the shipped direct-read path); the
    streamed-bytes account prices those blocks at 1 B/elem."""
    from paddle_tpu.benchmark.harness import run_timed
    from paddle_tpu.kernels import paged_attention as paged

    ops, qkw = _ragged_decode_operands(batch, ctx, block_size, num_blocks,
                                       heads, kv_heads, head_dim, tile_q)
    n8, nt = 0, batch * -(-ctx // block_size)
    if int8_frac > 0.0:
        ops, q8, _, n8, nt = _quantize_operand_blocks(ops, int8_frac)
        qkw.update(q8)
    q = ops[0]

    def step(c):
        out = paged.ragged_paged_attention(q + c.astype(q.dtype),
                                           *ops[1:], **qkw)
        return (jnp.sum(out.astype(jnp.float32)) * 1e-30
                ).astype(jnp.float32)

    f = jax.jit(step)

    def once(s):
        out = f(s)
        return out, out

    sec, _, _ = run_timed(once, jnp.zeros((), jnp.float32), min_time=1.0)
    # one attention layer's streamed bytes (fp32 operands here: 4B;
    # int8-resident blocks stream 1B + a 4B scale per block per plane)
    streamed = batch * decode_bytes_per_token(1, ctx, block_size,
                                              kv_heads, head_dim,
                                              dtype_bytes=4)
    if n8:
        blk = block_size * kv_heads * head_lanes(head_dim)
        streamed -= n8 * blk * 3            # 4B -> 1B on the int8 share
        streamed += n8 * 2 * 4              # per-plane scales
    return sec * 1e3, streamed / sec / 1e9


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--block-sizes", default="8,16,32")
    ap.add_argument("--num-blocks", default="512,2048,8192")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8,
                    help="concurrent decode rows at full occupancy")
    ap.add_argument("--hbm-gb", type=float, default=16.0)
    ap.add_argument("--hbm-gbps", type=float, default=819.0,
                    help="rig HBM bandwidth (v5e datasheet: 819 GB/s)")
    ap.add_argument("--rig", action="store_true",
                    help="time the real kernel on the TPU per cell")
    ap.add_argument("--spec-k", default=None, metavar="K1,K2,...",
                    help="append an emitted-token ceiling column per "
                    "speculative draft length K")
    ap.add_argument("--spec-accept", type=float, default=0.7,
                    help="modelled per-token draft acceptance "
                    "probability for the --spec-k columns")
    ap.add_argument("--compress-blocks", type=int, default=0,
                    help="model the device int8 compressed tier: "
                    "effective-pool and mixed-residency streamed-bytes "
                    "columns for a C-block int8 side pool")
    ap.add_argument("--direct-int8", action="store_true",
                    help="exercise the shipped direct-read mixed step: "
                    "the CPU smoke validates the mixed kernel (parity "
                    "vs reference, bit-identity vs promote-then-read); "
                    "--rig times the mixed kernel at each cell's "
                    "residency r = C/(NB+C) instead of the fp kernel")
    ap.add_argument("--tp-size", type=int, default=1,
                    help="model tensor-parallel serving: per-chip "
                    "pool/bytes columns (/N) plus the decode-MLP "
                    "allreduce wire bytes per token, fp vs int8")
    args = ap.parse_args()

    if args.rig:
        assert jax.devices()[0].platform == "tpu", "--rig needs the TPU"

    block_sizes = [int(s) for s in args.block_sizes.split(",")]
    num_blocks = [int(s) for s in args.num_blocks.split(",")]
    spec_ks = ([int(s) for s in args.spec_k.split(",")]
               if args.spec_k else [])
    L, Hkv, Dh = args.layers, args.kv_heads, args.head_dim
    tp = args.tp_size
    if tp < 1 or Hkv % tp != 0 or args.heads % tp != 0:
        raise SystemExit(
            f"--tp-size {tp} must be >= 1 and divide both --heads "
            f"{args.heads} and --kv-heads {Hkv} (the pool shards over "
            f"kv-heads; GQA groups must stay device-local)")

    print(f"model: {L} layers, {args.heads} heads ({Hkv} kv), "
          f"head_dim {Dh}, bf16 pool; rig: {args.hbm_gb:.0f} GB HBM "
          f"@ {args.hbm_gbps:.0f} GB/s; batch {args.batch}")
    if tp > 1:
        from paddle_tpu.parallel.serve_collective import \
            allreduce_wire_bytes
        model_dim = args.heads * Dh
        ar_fp = L * allreduce_wire_bytes(model_dim, "fp", tp)
        ar_i8 = L * allreduce_wire_bytes(model_dim, "int8", tp)
        print(f"tp={tp}: per-chip columns divide pool and streamed "
              f"bytes by {tp}; decode-MLP allreduce "
              f"{ar_fp/1e3:.2f} KB/tok fp vs {ar_i8/1e3:.2f} KB/tok "
              f"int8 over ICI")
    if spec_ks:
        print(f"spec columns: emitted-token ceiling at per-token "
              f"acceptance {args.spec_accept:.2f} "
              f"(E[emitted] = "
              + ", ".join(f"k={k}: {expected_emitted(k, args.spec_accept):.2f}"
                          for k in spec_ks) + ")")
    cb = args.compress_blocks
    if cb < 0:
        raise SystemExit(f"--compress-blocks {cb} must be >= 0")
    if args.direct_int8 and not cb:
        raise SystemExit("--direct-int8 needs --compress-blocks > 0 "
                         "(it prices the mixed-residency column)")
    if cb:
        print(f"compress: {cb}-block int8 side pool; eff_tok counts "
              f"warm-prefix capacity, KB/t_mix prices the shipped "
              f"direct-read step at residency r = C/(NB+C) "
              f"(int8-resident blocks stream half bytes in place"
              + (", measured on the mixed kernel"
                 if args.direct_int8 and args.rig else "") + ")")
    hdr = (f"{'BS':>4} {'NB':>6} {'pool_gb':>8} {'%hbm':>6} "
           f"{'cap_tok':>8} {'ctx/row':>8} {'KB/tok':>8} "
           f"{'tok_s_ceil':>10}")
    if cb:
        hdr += (f" {'qpool_gb':>8} {'eff_tok':>8} {'KB/t_mix':>8} "
                f"{'tok_s_mix':>10}")
    if tp > 1:
        hdr += (f" {'chip_gb':>8} {'KB/t/chip':>9} {'ar_fp_KB':>8} "
                f"{'ar_i8_KB':>8} {'tok_s_chip':>10}")
    for k in spec_ks:
        hdr += f" {f'spec_k={k}':>10}"
    if args.rig:
        hdr += f" {'kern_ms':>8} {'GB/s':>7} {'%bw':>5}"
    print(hdr)

    ok = True
    for bs in block_sizes:
        for nb in num_blocks:
            pool = kv_pool_bytes(L, nb, bs, Hkv, Dh)
            cap = nb * bs
            ctx = (nb // args.batch) * bs       # full-occupancy context
            bpt = decode_bytes_per_token(L, ctx, bs, Hkv, Dh)
            ceil_tok = args.hbm_gbps * 1e9 / bpt
            frac = pool / (args.hbm_gb * 1e9)
            line = (f"{bs:>4} {nb:>6} {pool/1e9:>8.3f} {frac*100:>5.1f}% "
                    f"{cap:>8} {ctx:>8} {bpt/1e3:>8.1f} "
                    f"{ceil_tok:>10.0f}")
            if cb:
                # int8 side pool: half the fp bytes per block (scales
                # are 4 B per plane per block — noise at this scale)
                qpool = kv_pool_bytes(L, cb, bs, Hkv, Dh) // 2
                eff_tok = (nb + cb) * bs
                r = cb / (nb + cb)
                bpt_mix = bpt * (1.0 - r / 2.0)
                line += (f" {qpool/1e9:>8.3f} {eff_tok:>8} "
                         f"{bpt_mix/1e3:>8.1f} "
                         f"{args.hbm_gbps * 1e9 / bpt_mix:>10.0f}")
            if tp > 1:
                # kv-head sharding: per-chip pool AND per-chip streamed
                # bytes are exactly 1/tp of the replicated numbers, so
                # the per-chip HBM decode ceiling scales by tp.
                line += (f" {pool/tp/1e9:>8.3f} {bpt/tp/1e3:>9.1f} "
                         f"{ar_fp/1e3:>8.2f} {ar_i8/1e3:>8.2f} "
                         f"{args.hbm_gbps * 1e9 / (bpt / tp):>10.0f}")
            for k in spec_ks:
                line += (f" {ceil_tok * expected_emitted(k, args.spec_accept):>10.0f}")
            if frac > 1.0:
                line += "  (exceeds HBM -- skipped)"
                print(line)
                continue
            if args.rig:
                frac8 = (cb / (nb + cb)) if args.direct_int8 else 0.0
                ms, gbs = measure_cell(args.batch, ctx, bs, nb,
                                       args.heads, Hkv, Dh,
                                       int8_frac=frac8)
                line += (f" {ms:>8.3f} {gbs:>7.1f} "
                         f"{gbs/args.hbm_gbps*100:>4.1f}%")
            print(line)

    if not args.rig:
        ok = smoke_interpret(direct_int8=args.direct_int8)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
