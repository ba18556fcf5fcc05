"""Benchmark entrypoint (driver contract: a parseable primary-metric
JSON line, whatever happens).

The primary line prints TWICE: once the moment the primary metric is
measured (flushed, with `extra.partial: true`, before any optional
entry can run long) and once complete at the end — so a driver timeout
mid-extras still leaves a parseable line, and a finished run's last
line carries everything.

Primary metric: ResNet-50 training throughput (imgs/s, bs=64) — the
reference's headline trainable-model metric (BASELINE.md: 81.69 imgs/s on
2x Xeon E5-2650v4, the only published trainable ResNet-50 number in the
reference tree). `extra` carries the rest of the north-star metrics:

- resnet50 best-batch-size throughput/MFU (bs=128 saturates v5e),
- Transformer-base tokens/s + MFU,
- flash_check: on-TPU numerical validation of the Pallas flash-attention
  kernel against the XLA reference path (fwd+bwd) with the dispatch gate
  asserted — the only hardware the kernels run on doubles as their
  correctness gate,
- dp8_scaling_eff: weak-scaling efficiency at dp=8 measured on the
  8-device virtual CPU mesh in a subprocess (plumbing correctness; the
  platform label makes clear it is not a hardware scaling claim),
- serving axis (serve_*): in-process ServeEngine decode tokens/s,
  TTFT/TPOT p99 read from the metrics registry, and speculative-decode
  steps per token — measured on every platform and re-flushed as a
  partial primary line the moment it lands, so a driver kill later in
  the run cannot cost the serving series.

Every line names the device it ran on (`extra.device`); off the chip the
windows shrink and the numbers are counts of a CPU run, never speeds.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

# Soft wall-clock budget: optional entries are skipped (with a marker)
# once exceeded, and even required entries stop starting once the
# budget is SPENT, so the run always finishes inside any sane driver
# timeout. Override with PTPU_BENCH_BUDGET_S.
_T0 = time.time()
_BUDGET_S = float(os.environ.get("PTPU_BENCH_BUDGET_S", "900"))


def _elapsed() -> float:
    return time.time() - _T0


def _budget_ok(est_s: float = 120.0) -> bool:
    return _elapsed() + est_s < _BUDGET_S


def _scaling_subprocess_start():
    """Launch the dp=1..8 weak-scaling sweep on a virtual CPU mesh as a
    BACKGROUND subprocess (own process: platform choice is frozen at
    first jax import; it runs on the CPU and needs no chip, so it is
    safe under a parent that holds one)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import json\n"
        "from paddle_tpu.benchmark.scaling import run_scaling, "
        "scaling_summary\n"
        "out = {}\n"
        "rows = run_scaling('mlp', sizes=(1, 2, 4, 8), per_chip_batch=64,"
        " min_time=0.3)\n"
        "out.update(scaling_summary(rows))\n"
        "rows = run_scaling('bert_tiny', sizes=(1, 2, 4, 8),"
        " per_chip_batch=8, min_time=0.3)\n"
        "out.update(scaling_summary(rows, prefix='bert_'))\n"
        "print('SCALING ' + json.dumps(out))\n")
    # stdout/stderr go to a FILE, not a pipe: JAX/absl warnings exceed
    # the pipe buffer long before the sweep finishes, and an undrained
    # pipe would block the child until the final join — serializing the
    # "background" work exactly where it must overlap the TPU entries
    out_f = tempfile.TemporaryFile(mode="w+", encoding="utf-8")
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=here,
                            env=env, stdout=out_f,
                            stderr=subprocess.STDOUT, text=True)
    proc._ptpu_out = out_f          # keep the fd alive with the handle
    return proc


def _scaling_subprocess_join(proc, timeout: float = 900):
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()                 # reap — no zombie for the bench's life
        return {"scaling_error": f"scaling subprocess >{timeout:.0f}s"}
    out_f = proc._ptpu_out
    out_f.seek(0)
    text = out_f.read()
    out_f.close()
    for line in text.splitlines():
        if line.startswith("SCALING "):
            return json.loads(line[len("SCALING "):])
    return {"scaling_error": text[-200:]}


def _longcontext_bench(seq: int = 16384):
    """fwd+bwd attention time at 16k tokens: Pallas flash vs XLA dense —
    the long-context headline (SURVEY §5.7)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import chain_k, run_timed
    from paddle_tpu.kernels import attention as A
    from paddle_tpu.utils.flags import FLAGS

    rs = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rs.randn(1, seq, 8, 64), jnp.bfloat16) * 0.3
    q, k, v = mk(), mk(), mk()
    out = {}
    prev = FLAGS.get("flash_attention")
    try:
        for label, flag in (("flash", True), ("dense", False)):
            FLAGS.set("flash_attention", flag)

            def loss(q, k, v):
                return jnp.sum(A.mha(q, k, v, causal=True)
                               .astype(jnp.float32))

            g = jax.grad(loss, argnums=(0, 1, 2))

            # harness.chain_k: K backwards per dispatch, carry touching
            # ALL THREE grads (else XLA dead-code-eliminates the dense
            # path's dk/dv matmuls while the fused flash kernel cannot
            # be pruned, biasing the comparison).
            K = 4
            kg = chain_k(lambda c, q, k, v: g(q + c, k, v), K)

            sec_k, _, _ = run_timed(
                lambda s: (kg(s, q, k, v),) * 2,
                jnp.zeros((), q.dtype), min_time=1.0)
            out[f"attn16k_{label}_ms"] = round(sec_k / K * 1e3, 2)
    finally:
        FLAGS.set("flash_attention", prev)
    out["attn16k_flash_speedup"] = round(
        out["attn16k_dense_ms"] / out["attn16k_flash_ms"], 2)
    return out


def _ptq_bench(min_time: float = 1.0):
    """int8 PTQ inference story on this chip (BASELINE int8 infer rows,
    reference benchmark/IntelOptimizedPaddle.md:73-107 + contrib/
    int8_inference). Three numbers:

    - resnet50 bf16 vs PTQ-int8 *simulated* inference (the framework's
      PTQ path stores int8 weights and dequantizes at compute — the
      reference contrib flow's semantics; on TPU this measures the
      simulation overhead, typically a slowdown),
    - a raw int8 matmul (preferred_element_type=int32) vs bf16 matmul
      microbench, documenting what the MXU int8 path yields from JAX —
      i.e. whether a true-int8 serving path would pay off.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import chain_k, run_timed
    from paddle_tpu.models import vision as V
    from paddle_tpu.quant.ptq import calibrate

    on_tpu = jax.devices()[0].platform == "tpu"
    bs, img = (16, 224) if on_tpu else (2, 64)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(bs, img, img, 3), jnp.float32)
    out = {}

    def time_fwd(apply_fn, label):
        K = 8 if on_tpu else 2
        kf = chain_k(lambda c, xx: apply_fn(xx + c), K)
        sec_k, _, _ = run_timed(lambda s: (kf(s, x),) * 2,
                                jnp.zeros((), x.dtype), min_time=min_time)
        out[f"{label}_ms"] = round(sec_k / K * 1e3, 2)

    model = V.resnet50(1000, dtype=jnp.bfloat16)
    variables = model.init(jax.random.key(0), x)
    time_fwd(lambda xx: model.apply(variables, xx, training=False),
             f"resnet50_infer_bf16_bs{bs}")

    qmodule, qvars = calibrate(model, variables, [(x,)])
    time_fwd(lambda xx: qmodule.apply(qvars, xx, training=False),
             f"resnet50_infer_ptq_int8_bs{bs}")
    out["ptq_vs_bf16"] = round(out[f"resnet50_infer_bf16_bs{bs}_ms"]
                               / out[f"resnet50_infer_ptq_int8_bs{bs}_ms"],
                               2)

    # raw MXU story: is a TRUE int8 path worth building on this chip?
    n = 4096 if on_tpu else 256
    a8 = jnp.asarray(rs.randint(-127, 127, (n, n)), jnp.int8)
    ab = jnp.asarray(rs.randn(n, n), jnp.bfloat16)
    for label, mat, dt in (("int8", a8, jnp.int32), ("bf16", ab, None)):
        def mm(c, m, dt=dt):
            # carry perturbs the input (runtime zero): the matmul stays
            # loop-carried inside chain_k's fori_loop, so XLA cannot
            # hoist it; chain_k's carry threading defeats DCE
            mp = m + (c * 1e-30).astype(m.dtype)
            return jax.lax.dot_general(
                mp, m, (((1,), (0,)), ((), ())),
                preferred_element_type=dt).ravel()[:1]
        kf = chain_k(mm, 8)
        sec, _, _ = run_timed(lambda s: (kf(s, mat),) * 2,
                              jnp.zeros((), jnp.float32),
                              min_time=min_time)
        out[f"matmul{n}_{label}_ms"] = round(sec / 8 * 1e3, 3)
    out["matmul_int8_vs_bf16"] = round(
        out[f"matmul{n}_bf16_ms"] / out[f"matmul{n}_int8_ms"], 2)
    return out


def _moe_bench(min_time: float = 1.0):
    """Masked vs all_to_all MoE dispatch cost at E=8 (top-2, cf=1.25).

    Even single-chip the difference is structural: masked dispatch runs
    every token through every expert (E× dense-FFN FLOPs), a2a runs each
    expert on only its capacity buffer (k·cf× dense) — so the step-cost
    ratio approaches E/(k·cf) ≈ 3.2 when FFN compute dominates."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import chain_k, run_timed
    from paddle_tpu.parallel import MeshConfig, make_mesh
    from paddle_tpu.parallel.moe import init_moe_params, moe_ffn, moe_ffn_a2a

    on_tpu = jax.devices()[0].platform == "tpu"
    E, D, HID, T = (8, 1024, 4096, 8192) if on_tpu else (8, 64, 128, 512)
    mesh = make_mesh(MeshConfig(ep=1), devices=jax.devices()[:1])
    mp = init_moe_params(jax.random.key(0), E, D, HID, dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.RandomState(0).randn(T, D),
                    jnp.bfloat16) * 0.3
    out = {}
    # cf 1.0 and 2.0 bracket the capacity contract: smaller buffers are
    # faster but drop more under skew (training behavior under pressure
    # is tested in tests/test_moe.py::test_moe_a2a_under_capacity_pressure)
    for label, fn in (
            ("masked", lambda p, xx: moe_ffn(p, xx, k=2)[0]),
            ("a2a", lambda p, xx: moe_ffn_a2a(p, xx, mesh=mesh, k=2,
                                              capacity_factor=1.25)[0]),
            ("a2a_cf1", lambda p, xx: moe_ffn_a2a(p, xx, mesh=mesh, k=2,
                                                  capacity_factor=1.0)[0]),
            ("a2a_cf2", lambda p, xx: moe_ffn_a2a(p, xx, mesh=mesh, k=2,
                                                  capacity_factor=2.0)[0])):
        g = jax.grad(lambda p, xx: jnp.mean(
            fn(p, xx).astype(jnp.float32) ** 2))
        K = 4
        kg = chain_k(lambda c, p, xx: g(p, xx + c)["gate"], K)
        sec_k, _, _ = run_timed(lambda s: (kg(s, mp, x),) * 2,
                                jnp.zeros((), x.dtype), min_time=min_time)
        out[f"moe_e8_{label}_ms"] = round(sec_k / K * 1e3, 2)
    out["moe_a2a_speedup"] = round(
        out["moe_e8_masked_ms"] / out["moe_e8_a2a_ms"], 2)
    return out


def _decode_bench(min_time: float = 0.8):
    """Autoregressive decode: CausalLM.generate (parallel prefill +
    bf16-KV-cached steps) at the lm_longctx model size, swept over
    batch {1, 8, 32} at prompt 32 and prompt {2048, 8192} at bs 8 —
    with a bytes/token HBM roofline per point (decode reads the full
    parameter set + the KV cache every step).

    Prefill is timed separately (its own jit of model.prefill) and
    subtracted, so decode_ms_per_token is steady-state decode only
    (dividing the whole generate wall time by the step count overstated
    per-token latency).

    Roofline caveat (measured): hbm_bound_frac can exceed 1 at small
    batch/prompt because the model's 70 MB of bf16 weights fit v5e VMEM
    and XLA keeps them RESIDENT across the decode fori_loop — the
    "params re-read every step" premise only binds once the KV cache +
    activations push weights out (the long-prompt points, frac ~0.3-0.4,
    are the genuinely HBM-bound regime). The frac is reported per point
    so the regime is visible, not asserted away."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import run_timed
    from paddle_tpu.benchmark.models import LM_BASE, LM_VOCAB
    from paddle_tpu.core.module import Context, PARAMS, _CtxCore
    from paddle_tpu.models.transformer import CausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    HBM_GBPS = 819.0            # v5e datasheet HBM bandwidth
    points = ([(1, 32), (8, 32), (32, 32), (8, 2048), (8, 8192)]
              if on_tpu else [(2, 8)])
    steps = 128 if on_tpu else 8
    out = {}
    rs = np.random.RandomState(0)
    for bs, t0 in points:
        model = CausalLM(LM_VOCAB, max_len=t0 + steps,
                         dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                         **LM_BASE)
        tok = jnp.asarray(rs.randint(0, LM_VOCAB, (bs, t0)), jnp.int32)
        variables = model.init(jax.random.key(0), tok)
        gen = jax.jit(lambda v, pr: model.generate(v, pr, steps))

        def prefill_fn(v, pr, model=model, t0=t0):
            cx = Context(_CtxCore(mode="apply", variables=v, mutated={},
                                  rng=None, rng_count=0, training=False))
            caches = model.init_cache(pr.shape[0], t0 + steps)
            return model.prefill(cx, pr, caches)[0]

        pre = jax.jit(prefill_fn)

        # loop-carry a PROMPT THAT NEVER REPEATS: an untrained model's
        # greedy continuation collapses to a constant token, so feeding
        # out[:, -t0:] back makes every dispatch after the first
        # identical. Mixing in the previous prompt AND a step counter
        # keeps inputs injective.
        def step_gen(carry):
            pr, i = carry
            o = gen(variables, pr)
            nxt = (o[:, -t0:].astype(jnp.int32) + pr + i) % LM_VOCAB
            return (nxt, i + 1), o

        def step_pre(carry):
            pr, i = carry
            o = pre(variables, pr)
            nxt = (pr + o[:, :1].astype(jnp.int32) + i) % LM_VOCAB
            return (nxt, i + 1), o

        sec_gen, _, _ = run_timed(step_gen, (tok, jnp.int32(1)),
                                  min_time=min_time)
        sec_pre, _, _ = run_timed(step_pre, (tok, jnp.int32(1)),
                                  min_time=min_time / 2)
        # two independently-noisy windows: clamp the subtraction so a
        # prefill-dominated point on a noisy pool day cannot emit a
        # negative rate or divide by zero (keep >=5% of the gen window)
        dec_sec = max(sec_gen - sec_pre, sec_gen * 0.05)
        dec_ms = dec_sec / steps * 1e3
        key = f"decode_bs{bs}_p{t0}"
        out[f"{key}_tokens_per_sec"] = round(bs * steps / dec_sec, 1)
        out[f"{key}_ms_per_token"] = round(dec_ms, 3)
        if on_tpu:
            # HBM roofline: every decode step reads all params (bf16)
            # plus the live KV cache (bf16, 2 x layers x T x D x bs)
            nparams = sum(x.size for x in
                          jax.tree.leaves(variables[PARAMS]))
            t_avg = t0 + steps / 2
            kv = (2 * LM_BASE["num_layers"] * t_avg
                  * LM_BASE["model_dim"] * bs)
            min_ms = (nparams + kv) * 2 / (HBM_GBPS * 1e6)
            out[f"{key}_hbm_bound_frac"] = round(min_ms / dec_ms, 3)
    if on_tpu:
        r = (out.get("decode_bs32_p32_tokens_per_sec", 0)
             / max(out.get("decode_bs8_p32_tokens_per_sec", 1), 1e-9))
        out["decode_bs32_vs_bs8"] = round(r, 2)
        out["decode_note"] = (
            "frac>1 = weights VMEM-resident across the decode loop "
            "(70MB bf16 fits); long-prompt points are the HBM-bound "
            "regime")
    return out


def _packed_vs_padded_bench(min_time: float = 1.0):
    """Packed ragged batches vs padded batches — the capability the
    segment-id flash kernel buys (the LoD->dense packing idiom,
    lod_tensor.h:44-58). Seven documents of mixed lengths
    (512..2048, sum 8192) trained either PACKED into [2, 8192] rows
    with segment ids + per-doc positions (flash skips cross-doc blocks:
    cost ~sum len_i^2) or PADDED to [14, 2048] (75% more tokens, all
    attended). Metric: REAL (non-pad) tokens/s through a full train
    step; the ratio is the packing win."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import run_timed
    from paddle_tpu.benchmark.models import LM_BASE, LM_VOCAB
    from paddle_tpu.core.executor import Trainer
    from paddle_tpu.ops.fused_ce import linear_cross_entropy
    from paddle_tpu.optim.optimizer import Adam

    from paddle_tpu.models.transformer import CausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        doc_lens = [512, 768, 1024, 1280, 1536, 1024, 2048]   # sum 8192
        pad_to, rows = 2048, 2
    else:
        doc_lens = [64, 96, 96]                               # sum 256
        pad_to, rows = 128, 1
    total = sum(doc_lens)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rs = np.random.RandomState(0)

    def make_model(seq):
        return CausalLM(LM_VOCAB, max_len=seq + 8, dtype=dtype,
                        **LM_BASE)

    # ---- packed: [rows, total] with segs + per-doc positions ---------
    segs = np.concatenate([np.full(n, i, np.int32)
                           for i, n in enumerate(doc_lens)])
    pos = np.concatenate([np.arange(n, dtype=np.int32)
                          for n in doc_lens])
    wts = np.ones(total, np.float32)
    wts[np.cumsum(doc_lens) - 1] = 0.0       # doc-final predicts across
    tokens = rs.randint(0, LM_VOCAB, (rows, total + 1)).astype(np.int32)
    segs_b = jnp.asarray(np.tile(segs, (rows, 1)))
    pos_b = jnp.asarray(np.tile(pos, (rows, 1)))
    wts_b = jnp.asarray(np.tile(wts, (rows, 1)))

    def make_loss(seg_ids, positions, weights):
        def loss_fn(module, variables, batch, rng, training):
            hid, mut = module.apply(variables, batch[0], training=training,
                                    rngs=rng, mutable=True,
                                    return_hidden=True,
                                    segment_ids=seg_ids,
                                    positions=positions)
            w, _ = module.head_weights(variables)
            ce = linear_cross_entropy(hid, w.astype(hid.dtype),
                                      batch[1], None)
            return (jnp.sum(ce * weights) / jnp.sum(weights), {}), \
                mut.get("state", {})
        return loss_fn

    out = {}
    real_tokens = rows * total

    def run(model, loss_fn, batch, label, tokens_per_step):
        tr = Trainer(model, Adam(1e-4), loss_fn)
        ts = tr.init_state(jnp.asarray(batch[0]))
        db = jax.device_put(batch)

        def step(ts):
            ts, f = tr.train_step(ts, db)
            return ts, f["loss"]

        sec, _, _ = run_timed(step, ts, min_time=min_time)
        out[f"{label}_tokens_per_sec"] = round(tokens_per_step / sec, 1)
        out[f"{label}_ms_per_step"] = round(sec * 1e3, 2)

    run(make_model(total), make_loss(segs_b, pos_b, wts_b),
        (tokens[:, :-1], tokens[:, 1:]), "lm_packed", real_tokens)

    # ---- padded: each doc its own row, padded to pad_to --------------
    n_rows = rows * len(doc_lens)
    ptoks = np.zeros((n_rows, pad_to + 1), np.int32)
    pw = np.zeros((n_rows, pad_to), np.float32)
    r = 0
    for b in range(rows):
        off = 0
        for n in doc_lens:
            # row b's token stream, cut per doc — both arms train on the
            # same data
            ptoks[r, :n + 1] = tokens[b, off:off + n + 1]
            pw[r, :n - 1 + 1] = 1.0
            pw[r, n - 1] = 0.0               # last real token: no target
            off += n
            r += 1
    lens_col = np.array([n for _ in range(rows) for n in doc_lens])
    pseg = jnp.asarray((np.arange(pad_to)[None, :]
                        < lens_col[:, None]).astype(np.int32))
    pwts = jnp.asarray(pw)

    run(make_model(pad_to), make_loss(pseg, None, pwts),
        (ptoks[:, :-1], ptoks[:, 1:]), "lm_padded", real_tokens)
    out["packed_vs_padded"] = round(
        out["lm_packed_tokens_per_sec"]
        / max(out["lm_padded_tokens_per_sec"], 1e-9), 2)
    return out


def _int8_compute_bench(min_time: float = 1.0):
    """TRUE int8 inference (quant/int8_compute.py): ResNet-50 frozen to
    int8 MXU compute with calibrated static activation scales, vs the
    bf16 model — at bs16 (bandwidth-bound, int8 loses) and bs128 (compute-bound, int8 wins ~1.4x measured).
    Accuracy: top-1 agreement + max relative logit error vs bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import chain_k, run_timed
    from paddle_tpu.models import vision as V
    from paddle_tpu.quant.int8_compute import freeze_int8

    on_tpu = jax.devices()[0].platform == "tpu"
    sizes = (16, 128) if on_tpu else (2,)
    img = 224 if on_tpu else 64
    rs = np.random.RandomState(0)
    out = {}
    for bs in sizes:
        x = jnp.asarray(rs.randn(bs, img, img, 3), jnp.float32)
        model = V.resnet50(1000, dtype=jnp.bfloat16 if on_tpu
                           else jnp.float32)
        variables = model.init(jax.random.key(0), x)

        def time_fwd(apply_fn):
            K = 8 if on_tpu else 2
            kf = chain_k(lambda c, xx: apply_fn(xx + c), K)
            sec, _, _ = run_timed(lambda s: (kf(s, x),) * 2,
                                  jnp.zeros((), x.dtype),
                                  min_time=min_time)
            return sec / K * 1e3

        tb = time_fwd(lambda xx: model.apply(variables, xx,
                                             training=False))
        ref = np.asarray(model.apply(variables, x, training=False),
                         np.float32)
        qmodel, qvars = freeze_int8(model, variables,
                                    calib_batches=[(x,)])
        t8 = time_fwd(lambda xx: qmodel.apply(qvars, xx,
                                              training=False))
        got = np.asarray(qmodel.apply(qvars, x, training=False),
                         np.float32)
        out[f"int8_vs_bf16_bs{bs}"] = round(tb / t8, 2)
        out[f"resnet50_int8_infer_imgs_per_sec_bs{bs}"] = round(
            bs / t8 * 1e3, 1)
        out[f"int8_top1_agree_bs{bs}"] = round(
            float((got.argmax(-1) == ref.argmax(-1)).mean()), 3)
        out[f"int8_max_rel_logit_err_bs{bs}"] = round(
            float(np.abs(got - ref).max()
                  / (np.abs(ref).max() + 1e-9)), 4)
    return out


def _resnet_s2d(min_time: float, bs: int = 128):
    """ResNet-50 with the space-to-depth stem (equivalent-capacity
    reparameterization; PERF_NOTES.md addendum)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.benchmark.harness import bench_trainer
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.metrics import accuracy
    from paddle_tpu.models import vision as V
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Momentum

    model = V.ResNet((3, 4, 6, 3), 1000, dtype=jnp.bfloat16, s2d_stem=True)
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y),
        metrics={"acc": accuracy})
    trainer = Trainer(model, Momentum(0.1, momentum=0.9), loss_fn)
    rs = np.random.RandomState(0)
    x = rs.randn(bs, 224, 224, 3).astype(np.float32)
    y = rs.randint(0, 1000, bs).astype(np.int64)
    ts = trainer.init_state(jnp.zeros((bs, 224, 224, 3)))
    batch = jax.device_put((x, y))
    return bench_trainer("resnet50_s2d", trainer, ts, batch,
                         items_per_step=bs, unit="imgs/s", batch_size=bs,
                         min_time=min_time)


def _serving_bench(requests: int = 8, new_tokens: int = 32):
    """Serving axis (ENGINE.md): an in-process ServeEngine under
    continuous batching + speculative decode on a lookup-friendly
    workload. Emits decode throughput plus the latency numbers a
    production scrape would read — TTFT/TPOT p99 straight from the
    metrics registry, and decode steps per generated token (< 1.0 when
    the n-gram drafter is earning its keep). CPU-cheap: the model is
    tiny, so the entry runs on every platform."""
    import logging

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.engine import ServeEngine
    from paddle_tpu.models.transformer import CausalLM
    from paddle_tpu.obs.metrics import MetricsRegistry

    model = CausalLM(vocab=128, model_dim=64, num_heads=4, num_layers=2,
                     ffn_dim=256, dropout=0.0, max_len=128)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(9)
    # repetitive prompts: the self-drafter's best case, so steps/token
    # reflects the speculation mechanism rather than model noise
    prompts = [np.tile(rng.integers(0, 127, 6), 4).tolist()
               for _ in range(requests)]
    # bench stdout carries METRIC lines only: mute the engine's
    # per-step serve_event chatter for the duration of the run
    # (.disabled, not setLevel — the lazy _stream_logger creation
    # path resets the level to INFO on first emit)
    lg = logging.getLogger("paddle_tpu.serve")
    prev_disabled = lg.disabled
    lg.disabled = True
    try:
        eng = ServeEngine(model, variables, max_batch_size=4,
                          block_size=16, num_blocks=64, spec_k=4,
                          registry=MetricsRegistry())
        eng.generate([[127] * 4], max_new_tokens=2)  # compile untimed
        eng.reset_stats()
        t0 = time.time()
        for p in prompts:
            eng.add_request(list(p), max_new_tokens=new_tokens)
        eng.run()
        wall = time.time() - t0
    finally:
        lg.disabled = prev_disabled
    gen = int(eng.obs.get("ptpu_serve_tokens_total")
              .labels(kind="generated").value)
    ttft = eng.obs.get("ptpu_serve_ttft_ms")
    tpot = eng.obs.get("ptpu_serve_tpot_ms")
    step_h = eng.obs.get("ptpu_serve_step_ms")
    decode_steps = sum(c.count for kind, c in step_h.children().items()
                       if kind != ("prefill",))
    # direct-read columns (ISSUE 20): repeat traffic against a
    # compression-enabled engine. The cold turn caches the prompt,
    # filler churn evicts its blocks into the int8 tier, and the warm
    # turn re-reads them IN PLACE (kv_promote_hits=0, no promote
    # round-trip). The streamed-KB/token pair prices the warm decode's
    # per-token KV traffic twice — all-fp account vs the measured
    # mixed-residency account (int8-resident tokens at 1 B/elem).
    lg.disabled = True
    try:
        eng2 = ServeEngine(model, variables, max_batch_size=4,
                           block_size=4, num_blocks=24, spec_k=4,
                           kv_compress_blocks=256, kv_promote_hits=0,
                           registry=MetricsRegistry())
        prompt = prompts[0][:23]    # off block stride: the final
        # partial block stays fp-writable, so no forced promote
        eng2.generate([list(prompt)], max_new_tokens=4)      # cold
        for _ in range(6):          # churn: evict into the int8 tier
            eng2.generate([rng.integers(0, 127, 33).tolist()],
                          max_new_tokens=2)
        eng2.reset_stats()
        eng2.generate([list(prompt)], max_new_tokens=4)      # warm
    finally:
        lg.disabled = prev_disabled
    c2 = eng2.cache
    st2 = c2.stats()
    direct_toks = int(st2.get("direct_int8_tokens", 0))
    itemsize = jnp.dtype(c2.dtype).itemsize
    per_tok_fp = len(c2.pools) * 2 * c2.num_kv_heads * c2.head_dim \
        * itemsize
    ctx = -(-len(prompt) // c2.block_size) * c2.block_size
    mix_bytes = (ctx - direct_toks) * per_tok_fp \
        + direct_toks * (per_tok_fp // itemsize)
    return {
        "serve_decode_tok_per_sec": round(gen / max(wall, 1e-9), 1),
        "serve_ttft_p99_ms": round(ttft.quantile(0.99), 3),
        "serve_tpot_p99_ms": round(tpot.quantile(0.99), 3),
        "serve_spec_steps_per_token": round(decode_steps / max(gen, 1), 4),
        # tensor-parallel serving columns (ISSUE 14): the bench engine
        # runs tp=1 (CPU, single device); the columns exist so rig rows
        # at tp>1 land in the same schema, and per-chip pool bytes is
        # MEASURED off the pool arrays' addressable shards
        "serve_tp_size": eng.tp_size,
        "serve_kv_pool_bytes_per_chip": eng.cache.per_chip_pool_bytes(),
        "serve_kv_direct_int8_reads": int(st2.get("direct_int8_reads",
                                                  0)),
        "serve_kv_direct_int8_tokens": direct_toks,
        "serve_kv_streamed_kb_per_tok_fp": round(ctx * per_tok_fp / 1e3,
                                                 3),
        "serve_kv_streamed_kb_per_tok_mix": round(mix_bytes / 1e3, 3),
    }


def _training_bench(steps: int = 10):
    """Training telemetry axis (ISSUE 13 satellite): step-phase p99 and
    MFU for a tiny causal LM through MeshTrainer's instrumented path,
    read back from the SAME ptpu_train_* families a production scrape
    would — so BENCH_r* rows carry the training numbers next to the
    serving axis. CPU-cheap (tiny model, private registry)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.executor import supervised_loss
    from paddle_tpu.models.transformer import CausalLM
    from paddle_tpu.obs.goodput import (causal_lm_step_flops, param_count,
                                        resolve_peak_flops)
    from paddle_tpu.obs.metrics import MetricsRegistry
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.ops import functional as F
    from paddle_tpu.parallel import MeshConfig, MeshTrainer, make_mesh

    vocab, dm, layers, t, b = 128, 64, 2, 32, 8
    model = CausalLM(vocab=vocab, model_dim=dm, num_heads=4,
                     num_layers=layers, ffn_dim=256, dropout=0.0, max_len=t)
    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    reg = MetricsRegistry()
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y))
    trainer = MeshTrainer(model, Adam(1e-3), loss_fn, mesh)
    trainer.enable_metrics(reg)
    rs = np.random.RandomState(0)
    tok = rs.randint(0, vocab, (b, t + 1)).astype(np.int32)
    ts = trainer.init_state(jnp.asarray(tok[:, :-1]))
    batch = trainer.put_batch((tok[:, :-1], tok[:, 1:]))
    for _ in range(steps):
        ts, _ = trainer.train_step(ts, batch)

    step_h = reg.get("ptpu_train_step_ms")
    phase = reg.get("ptpu_train_phase_ms")
    out = {
        "train_step_p99_ms": round(step_h.quantile(0.99), 3),
        "train_dispatch_p99_ms": round(
            phase.labels(phase="dispatch").quantile(0.99), 3),
        "train_wait_p99_ms": round(
            phase.labels(phase="wait").quantile(0.99), 3),
        "train_compiles": int(reg.get("ptpu_train_compiles").value),
    }
    peak = resolve_peak_flops()
    if peak:
        flops = causal_lm_step_flops(
            batch_size=b, seq_len=t, d_model=dm, n_layers=layers,
            n_params=param_count(ts.params))
        # p50 excludes the compile-laden warmup step from the MFU clock
        sec = step_h.quantile(0.5) / 1e3
        if sec > 0:
            out["train_mfu"] = round(flops / sec / peak, 4)
    return out


def main():
    import jax.numpy as jnp

    from paddle_tpu.benchmark import run_model

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    # short windows: a cold run is compile-dominated, and smaller
    # windows buy entries inside the budget
    min_time = 1.5 if on_tpu else 0.2
    bs = 64 if on_tpu else 8

    # DRIVER CONTRACT bootstrap: a driver run once ended rc=124 with
    # nothing parseable because the first flushed line printed only
    # AFTER backend init AND the full resnet50 build/compile.
    # Print a zero-valued no_measurement line the moment the metric
    # name is known, BEFORE any model build: a driver kill at any later
    # point still finds a parseable primary line. Every subsequent
    # partial/complete line supersedes it for last-line consumers;
    # first-line consumers see no_measurement=true and know no
    # measurement was taken.
    print(json.dumps({
        "metric": f"resnet50_train_imgs_per_sec_bs{bs}", "value": 0,
        "unit": "imgs/s", "vs_baseline": 0, "no_measurement": True,
        "extra": {"bootstrap": True,
                  "note": "bench starting; measurement pending"},
    }), flush=True)

    # weak-scaling runs on a VIRTUAL CPU mesh in its own process. On TPU
    # it starts NOW and overlaps the device-bound entries (host CPU is
    # nearly idle between dispatches); on a CPU-only run it would steal the very
    # cores the foreground entries are timed on, so there it runs
    # sequentially at the end.
    scaling_proc = _scaling_subprocess_start() if on_tpu else None

    resnet = run_model("resnet50", batch_size=bs, dtype=dtype,
                       min_time=min_time)
    extra = {
        "device": resnet.device,
        "resnet50_mfu": round(resnet.mfu, 4) if resnet.mfu else None,
        "resnet50_tflops_per_sec": (round(resnet.tflops_per_sec, 1)
                                    if resnet.tflops_per_sec else None),
        "resnet50_ms_per_step": round(resnet.ms_per_step, 2),
        "timed_steps": resnet.steps,
    }

    # Entry gate. required=True entries are the priority set (decode,
    # s2d, infer, sustained_matmul, scaling, plus the flash correctness
    # gate): they ignore the per-entry estimate and only stop once the
    # budget is actually SPENT — on a pathologically slow day they too
    # must yield rather than run into the driver's kill. Optional
    # entries check the soft budget up front so a slow day degrades to
    # fewer extras first.
    def _gate(key, est_s=120.0, tpu_only=True, required=False):
        if tpu_only and not on_tpu:
            return False
        if required:
            if _elapsed() < _BUDGET_S:
                return True
        elif _budget_ok(est_s):
            return True
        extra[f"{key}_skipped"] = "bench budget"
        return False

    def _primary_line(partial):
        return json.dumps({
            "metric": f"resnet50_train_imgs_per_sec_bs{bs}",
            "value": round(resnet.value, 2), "unit": "imgs/s",
            "vs_baseline": round(resnet.vs_baseline, 3),
            "extra": dict(extra, partial=True) if partial else extra,
        })

    # DRIVER CONTRACT: the measured primary metric prints the moment it
    # exists, flushed, BEFORE any optional entry can run long — a
    # driver timeout then still finds a parseable line (the bootstrap line above covers kills
    # before this point). The complete line prints again at the end.
    print(_primary_line(partial=True), flush=True)

    # ---- serving axis: runs EVERYWHERE, right behind the partial
    # primary line (the in-process engine is tiny, and printing another
    # flushed partial line directly after means a later driver kill
    # cannot cost the serving series)
    if _gate("serving", est_s=60, tpu_only=False, required=True):
        try:
            extra.update(_serving_bench())
        except Exception as e:
            extra["serving_error"] = f"{type(e).__name__}: {e}"[:160]
        print(_primary_line(partial=True), flush=True)

    # ---- training telemetry axis: step-phase p99 + MFU off the live
    # ptpu_train_* families (tiny model, runs everywhere)
    if _gate("training_telemetry", est_s=60, tpu_only=False, required=True):
        try:
            extra.update(_training_bench())
        except Exception as e:
            extra["training_telemetry_error"] = \
                f"{type(e).__name__}: {e}"[:160]
        print(_primary_line(partial=True), flush=True)

    try:
        # winning config from the tools/profile_transformer.py sweep:
        # raw_ce (bf16 logits straight into the promoting CE) at bs=32 —
        # 283k tok/s / 56.7% MFU vs 243k / 48.7% at the bs=64 config
        # (fused_qkv and fused_ce both measured slower; PERF_NOTES).
        xf = run_model(
            "transformer", batch_size=32 if on_tpu else 2,
            dtype=dtype, min_time=min_time, raw_ce=True)
        extra.update({
            "transformer_tokens_per_sec": round(xf.value, 1),
            "transformer_mfu": round(xf.mfu, 4) if xf.mfu else None,
            "transformer_ms_per_step": round(xf.ms_per_step, 2),
            "transformer_bs": xf.batch_size,
            "transformer_cfg": "raw_ce",
        })
    except Exception as e:  # primary metric must still print
        extra["transformer_error"] = f"{type(e).__name__}: {e}"[:200]

    # ---- never-skip set -------------------------------------------------
    if _gate("sustained_matmul", required=True):
        # same-run matmul ceiling NEXT TO the headline numbers
        try:
            from paddle_tpu.benchmark.harness import sustained_matmul_flops
            mp = sustained_matmul_flops()
            if mp:
                extra["sustained_matmul_tflops"] = round(mp / 1e12, 1)
        except Exception as e:
            extra["sustained_matmul_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("flash_check", required=True):
        # the on-hardware kernel correctness gate (now incl. segment-id
        # masking and in-kernel dropout) must survive any budget squeeze
        try:
            from paddle_tpu.kernels.selfcheck import flash_selfcheck
            extra.update(flash_selfcheck())
        except Exception as e:
            extra["flash_check"] = f"FAILED: {type(e).__name__}: {e}"[:220]

    if _gate("lm16k", required=True):  # 16k-token causal-LM TRAIN step:
        # flash causal attention + fused CE (no [T,V] logits) — the
        # long-context training headline (SURVEY §5.7)
        try:
            lm = run_model("lm_longctx", batch_size=1, dtype=dtype,
                           min_time=min_time)
            extra["lm16k_tokens_per_sec"] = round(lm.value, 1)
            extra["lm16k_mfu"] = round(lm.mfu, 4) if lm.mfu else None
            extra["lm16k_ms_per_step"] = round(lm.ms_per_step, 2)
        except Exception as e:
            extra["lm16k_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("decode", required=True):  # KV-cached generate: bs x prompt
        # sweep + HBM roofline (bf16 caches; prefill subtracted)
        try:
            extra.update(_decode_bench(
                min_time=max(min_time / 2, 0.6)))
        except Exception as e:
            extra["decode_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("packed", required=True):  # packed ragged batches through
        # the segment-id flash kernel vs padded rows
        try:
            extra.update(_packed_vs_padded_bench(
                min_time=max(min_time / 2, 0.6)))
        except Exception as e:
            extra["packed_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("resnet50_s2d", required=True):  # s2d stem: best measured
        # ResNet-50 training config (PERF_NOTES: 0.334 MFU at bs=128)
        try:
            s2d = _resnet_s2d(min_time=min_time)
            extra["resnet50_s2d_imgs_per_sec_bs128"] = round(s2d.value, 1)
            extra["resnet50_s2d_mfu"] = (round(s2d.mfu, 4)
                                         if s2d.mfu else None)
        except Exception as e:
            extra["resnet50_s2d_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("infer", required=True):  # inference (reference infer tables)
        try:
            from paddle_tpu.benchmark.models import run_infer
            inf = run_infer(
                "resnet50", batch_size=16, dtype=dtype,
                min_time=min_time)
            extra["resnet50_infer_imgs_per_sec_bs16"] = round(inf.value, 1)
            extra["resnet50_infer_vs_baseline"] = (
                round(inf.vs_baseline, 1) if inf.vs_baseline else None)
        except Exception as e:
            extra["infer_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("int8", required=True):  # TRUE int8 compute
        try:
            extra.update(_int8_compute_bench(
                min_time=max(min_time / 2, 0.8)))
        except Exception as e:
            extra["int8_error"] = f"{type(e).__name__}: {e}"[:160]

    # ---- optional extras, most important first --------------------------
    # (The budget is ONE fixed ceiling; required entries drain it
    # first, optionals get what remains.)
    if _gate("bert"):  # BERT-base MLM (BASELINE BERT row)
        try:
            b = run_model("bert", batch_size=64, dtype=dtype,
                          min_time=min_time)
            extra["bert_tokens_per_sec"] = round(b.value, 1)
            extra["bert_mfu"] = round(b.mfu, 4) if b.mfu else None
        except Exception as e:
            extra["bert_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("moe", est_s=240):  # MoE dispatch: masked (E×) vs a2a
        # (k·cf×) + the cf 1.0/2.0 sweep — 4 timed configs
        try:
            extra.update(_moe_bench(min_time=min_time))
        except Exception as e:
            extra["moe_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("longcontext"):  # long-context: flash vs dense at 16k
        try:
            extra.update(_longcontext_bench())
        except Exception as e:
            extra["longcontext_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("ptq", est_s=180):  # int8 PTQ SIMULATION story (the
        # reference contrib semantics; the true-int8 path is `int8` above)
        try:
            extra.update(_ptq_bench(min_time=min_time))
        except Exception as e:
            extra["ptq_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("resnet50_best_bs"):  # best-bs point (report bs=64 AND best)
        try:
            best = run_model(
                "resnet50", batch_size=128, dtype=dtype,
                min_time=min_time)
            extra["resnet50_best_bs"] = 128
            extra["resnet50_imgs_per_sec_best_bs"] = round(best.value, 1)
            extra["resnet50_mfu_best_bs"] = (round(best.mfu, 4)
                                             if best.mfu else None)
        except Exception as e:
            extra["resnet50_best_bs_error"] = f"{type(e).__name__}: {e}"[:160]

    if _gate("transformer_bs64"):  # r3-comparable config, for the series
        try:
            x64 = run_model("transformer", batch_size=64, dtype=dtype,
                            min_time=min_time)
            extra["transformer_bs64_tokens_per_sec"] = round(x64.value, 1)
            extra["transformer_bs64_mfu"] = (round(x64.mfu, 4)
                                             if x64.mfu else None)
        except Exception as e:
            extra["transformer_bs64_error"] = f"{type(e).__name__}: {e}"[:160]

    if on_tpu:  # reference GPU-table headline models (K40m ms/batch,
        # BASELINE.md: AlexNet 334 ms, GoogLeNet 1149 ms at bs=128)
        for name, ref_ms in (("alexnet", 334.0), ("googlenet", 1149.0)):
            if not _gate(name):
                continue
            try:
                r = run_model(name, batch_size=128, dtype=dtype,
                              min_time=min_time)
                extra[f"{name}_train_ms_bs128"] = round(r.ms_per_step, 2)
                extra[f"{name}_vs_k40m_speedup"] = round(
                    ref_ms / r.ms_per_step, 1)
            except Exception as e:
                extra[f"{name}_error"] = f"{type(e).__name__}: {e}"[:160]

    # collect the CPU-mesh weak-scaling sweep (on TPU it ran
    # concurrently with everything above; on CPU it runs now,
    # sequentially, so it never contended with the timed entries). The
    # join is bounded by the REMAINING budget: a wedged subprocess must
    # not hold the final JSON line past the driver timeout.
    try:
        if scaling_proc is None:
            scaling_proc = _scaling_subprocess_start()
        extra.update(_scaling_subprocess_join(
            scaling_proc, timeout=max(30.0, _BUDGET_S - _elapsed())))
    except Exception as e:
        extra["scaling_error"] = f"{type(e).__name__}: {e}"[:160]

    print(_primary_line(partial=False), flush=True)


if __name__ == "__main__":
    main()
