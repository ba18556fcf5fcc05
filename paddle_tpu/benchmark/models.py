"""Benchmark model zoo — mirrors /root/reference/benchmark/fluid/models/
(mnist, vgg, resnet, se_resnext, machine_translation, stacked_dynamic_lstm)
plus the CTR model (dist_ctr capability) and the extra nets the reference
publishes baselines for (AlexNet, GoogLeNet: benchmark/README.md,
IntelOptimizedPaddle.md).

Each spec builds (trainer, state, batch) on synthetic data with the
reference's benchmark shapes, then hands off to harness.bench_trainer.
Published reference numbers ride along as `baseline` so every result
carries a vs_baseline ratio.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.benchmark.harness import (BenchResult, bench_trainer,
                                           chain_k)
from paddle_tpu.core.executor import Trainer, supervised_loss
from paddle_tpu.metrics import accuracy
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import Adam, Momentum

# Published reference numbers (BASELINE.md). value = items/s unless ms.
BASELINES = {
    "resnet50": 81.69,        # imgs/s bs=64, 2x Xeon MKL-DNN
    "vgg16": 28.46,           # VGG-19 imgs/s bs=64 (closest published)
    "alexnet": 399.00,        # imgs/s bs=64
    "googlenet": 250.46,      # imgs/s bs=64
    "stacked_lstm": 184.0,    # ms/batch bs=64 hidden=512, K40m
}


def _trainer_for(model, loss_fn, optimizer, mesh=None, strategy=None,
                 rules=None):
    if mesh is not None:
        from paddle_tpu.parallel.trainer import MeshTrainer
        return MeshTrainer(model, optimizer, loss_fn, mesh,
                           strategy=strategy, rules=rules)
    return Trainer(model, optimizer, loss_fn)


def _put(trainer, batch):
    if hasattr(trainer, "put_batch"):
        return trainer.put_batch(batch)
    return jax.device_put(batch)


def _image_spec(model_ctor, img: int = 224, classes: int = 1000,
                default_bs: int = 64):
    def build(name, batch_size, dtype, mesh, strategy, rules, min_time):
        bs = batch_size or default_bs
        model = model_ctor(num_classes=classes, dtype=dtype)
        loss_fn = supervised_loss(
            lambda lg, y: F.softmax_with_cross_entropy(
                lg.astype(jnp.float32), y),
            metrics={"acc": accuracy})
        trainer = _trainer_for(model, loss_fn, Momentum(0.1, momentum=0.9),
                               mesh, strategy, rules)
        rs = np.random.RandomState(0)
        x = rs.randn(bs, img, img, 3).astype(np.float32)
        y = rs.randint(0, classes, bs).astype(np.int64)
        ts = trainer.init_state(jnp.zeros((bs, img, img, 3)))
        batch = _put(trainer, (x, y))
        return bench_trainer(name, trainer, ts, batch, items_per_step=bs,
                             unit="imgs/s", batch_size=bs, min_time=min_time,
                             baseline=BASELINES.get(name))
    return build


def _mnist(name, batch_size, dtype, mesh, strategy, rules, min_time):
    from paddle_tpu.models import LeNet
    bs = batch_size or 128
    model = LeNet(num_classes=10, dtype=dtype)
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(lg.astype(jnp.float32), y),
        metrics={"acc": accuracy})
    trainer = _trainer_for(model, loss_fn, Adam(1e-3), mesh, strategy, rules)
    rs = np.random.RandomState(0)
    x = rs.randn(bs, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, bs).astype(np.int64)
    ts = trainer.init_state(jnp.zeros((bs, 28, 28, 1)))
    batch = _put(trainer, (x, y))
    return bench_trainer(name, trainer, ts, batch, items_per_step=bs,
                         unit="imgs/s", batch_size=bs, min_time=min_time)


def _transformer(name, batch_size, dtype, mesh, strategy, rules, min_time,
                 seq_len: int = 256, vocab: int = 32000,
                 fused_qkv: bool = False, raw_ce: bool = False,
                 fused_ce: bool = False):
    """Transformer-base WMT (machine_translation.py / dist_transformer.py):
    tokens/s on the teacher-forced train step.

    fused_qkv / raw_ce / fused_ce are perf-variant knobs
    (tools/profile_transformer.py A/B sweep): Megatron-packed projections;
    feeding bf16 logits straight to the internally-promoting CE instead of
    materializing an f32 [B,T,V] copy first; and the chunked
    linear_cross_entropy that never materializes [B,T,V] at all
    (ops/fused_ce.py)."""
    from paddle_tpu.models.transformer import Transformer
    bs = batch_size or 32
    dim = 512
    model = Transformer(src_vocab=vocab, trg_vocab=vocab, model_dim=dim,
                        num_heads=8, num_layers=6, ffn_dim=2048,
                        dropout=0.0, max_len=seq_len + 1, dtype=dtype,
                        fused_qkv=fused_qkv)

    def loss_fn(module, variables, batch, rng, training):
        src, trg_in, trg_out = batch
        if fused_ce:
            from paddle_tpu.ops.fused_ce import linear_cross_entropy
            hid, mut = module.apply(variables, src, trg_in,
                                    training=training, rngs=rng,
                                    mutable=True, return_hidden=True)
            head = variables["params"]["head"]
            loss = jnp.mean(linear_cross_entropy(
                hid, head["weight"].astype(hid.dtype), trg_out,
                head["bias"].astype(hid.dtype)))
            return (loss, {}), mut.get("state", {})
        logits, mut = module.apply(variables, src, trg_in, training=training,
                                   rngs=rng, mutable=True)
        if not raw_ce:
            logits = logits.astype(jnp.float32)
        loss = jnp.mean(F.softmax_with_cross_entropy(logits, trg_out))
        return (loss, {}), mut.get("state", {})

    trainer = _trainer_for(model, loss_fn, Adam(1e-4), mesh, strategy, rules)
    rs = np.random.RandomState(0)
    src = rs.randint(0, vocab, (bs, seq_len)).astype(np.int32)
    trg = rs.randint(0, vocab, (bs, seq_len + 1)).astype(np.int32)
    ts = trainer.init_state(jnp.asarray(src), jnp.asarray(trg[:, :-1]))
    batch = _put(trainer, (src, trg[:, :-1], trg[:, 1:]))
    tokens = bs * seq_len
    extra_flops = 0.0
    if fused_ce:
        from paddle_tpu.ops.fused_ce import mfu_flops_correction
        extra_flops = mfu_flops_correction(tokens, dim, vocab)
    return bench_trainer(name, trainer, ts, batch, items_per_step=tokens,
                         unit="tokens/s", batch_size=bs, min_time=min_time,
                         extra_flops=extra_flops)


# CausalLM size shared by the lm_longctx train bench and bench.py's
# _decode_bench ("same model size" must stay true by construction)
LM_BASE = dict(model_dim=512, num_heads=8, num_layers=6, ffn_dim=2048,
               dropout=0.0)
LM_VOCAB = 32000


def _lm_longctx(name, batch_size, dtype, mesh, strategy, rules, min_time,
                seq_len: int = 16384, vocab: int = LM_VOCAB):
    """Single-chip long-context causal-LM train step: CausalLM with
    block-causal Pallas flash attention (O(T) score memory) + the
    chunked fused CE (no [T, V] logits) — the pairing that makes
    16k-token LM training fit one chip at all. tokens/s + MFU headline
    for SURVEY §5.7's long-context story; the dense-attention
    alternative at this length would materialize a [1, 8, 16k, 16k]
    score tensor (2 TB-scale traffic) and a 1 GB logits round-trip.

    MFU accounting: the flash kernel is a custom call XLA's cost
    analysis scores at ZERO flops (measured), and the fused-CE scan
    body is counted once — both corrected analytically on the
    model-FLOPs basis (causal attention at half the full matmul count,
    recompute excluded; see bench_trainer.extra_flops)."""
    from paddle_tpu.kernels.attention import would_use_flash
    from paddle_tpu.models.transformer import CausalLM
    from paddle_tpu.ops.fused_ce import (linear_cross_entropy,
                                         mfu_flops_correction)

    bs = batch_size or 1
    dim = LM_BASE["model_dim"]
    heads, layers = LM_BASE["num_heads"], LM_BASE["num_layers"]
    model = CausalLM(vocab, max_len=seq_len, dtype=dtype, **LM_BASE)

    def loss_fn(module, variables, batch, rng, training):
        inp, tgt = batch
        hid, mut = module.apply(variables, inp, training=training,
                                rngs=rng, mutable=True, return_hidden=True)
        w, b_ = module.head_weights(variables)
        loss = jnp.mean(linear_cross_entropy(
            hid, w.astype(hid.dtype), tgt,
            None if b_ is None else b_.astype(hid.dtype)))
        return (loss, {}), mut.get("state", {})

    trainer = _trainer_for(model, loss_fn, Adam(1e-4), mesh, strategy, rules)
    rs = np.random.RandomState(0)
    tok = rs.randint(0, vocab, (bs, seq_len + 1)).astype(np.int32)
    ts = trainer.init_state(jnp.asarray(tok[:, :-1]))
    batch = _put(trainer, (tok[:, :-1], tok[:, 1:]))
    tokens = bs * seq_len

    # fused-CE scan correction (model basis, tied head => no bias)
    extra_flops = mfu_flops_correction(tokens, dim, vocab)
    # flash custom-call correction: cost analysis scores it 0 (measured,
    # PERF_NOTES). Causal model flops = fwd 2BT^2D + bwd 4BT^2D per
    # layer. Applied exactly when the kernel dispatches (the shared mha
    # gate); on the XLA dense path the T^2 matmuls ARE counted.
    qkv_shape = (bs, seq_len, heads, dim // heads)
    if would_use_flash(qkv_shape, qkv_shape):
        extra_flops += 6.0 * bs * float(seq_len) ** 2 * dim * layers
    return bench_trainer(name, trainer, ts, batch, items_per_step=tokens,
                         unit="tokens/s", batch_size=bs, min_time=min_time,
                         extra_flops=extra_flops)


def _stacked_lstm(name, batch_size, dtype, mesh, strategy, rules, min_time,
                  seq_len: int = 100, vocab: int = 10000):
    """Stacked-LSTM text classifier (stacked_dynamic_lstm.py; the LSTM
    headline number README.md:112-120 is ms/batch bs=64 hidden=512)."""
    from paddle_tpu.models.nlp import TextClassifier
    bs = batch_size or 64
    model = TextClassifier(vocab=vocab, embed_dim=128, hidden=512, layers=2)
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(lg.astype(jnp.float32), y),
        metrics={"acc": accuracy})
    trainer = _trainer_for(model, loss_fn, Adam(1e-3), mesh, strategy, rules)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, vocab, (bs, seq_len)).astype(np.int32)
    y = rs.randint(0, 2, bs).astype(np.int64)
    ts = trainer.init_state(jnp.asarray(toks))
    batch = _put(trainer, (toks, y))
    return bench_trainer(name, trainer, ts, batch,
                         items_per_step=bs * seq_len, unit="tokens/s",
                         batch_size=bs, min_time=min_time,
                         baseline=BASELINES.get(name), baseline_is_ms=True)


def _bert(name, batch_size, dtype, mesh, strategy, rules, min_time,
          seq_len: int = 128, vocab: int = 30522, model_dim: int = 768,
          num_layers: int = 12, num_heads: int = 12, ffn_dim: int = 3072,
          mask_frac: float = 0.15, fused_qkv: bool = False):
    """BERT-base MLM pretraining step (BASELINE BERT row: pod-scale
    allreduce / 8->32 chip scaling). Static masked-position count keeps
    the step one compile."""
    from paddle_tpu.models.transformer import BertEncoder
    bs = batch_size or 32
    k = max(1, int(seq_len * mask_frac))
    model = BertEncoder(vocab=vocab, model_dim=model_dim,
                        num_heads=num_heads, num_layers=num_layers,
                        ffn_dim=ffn_dim, max_len=seq_len, dropout=0.0,
                        dtype=dtype, fused_qkv=fused_qkv)

    def loss_fn(module, variables, batch, rng, training):
        tokens, positions, labels = batch
        logits, mut = module.apply(variables, tokens, positions,
                                   training=training, rngs=rng,
                                   mutable=True)
        loss = jnp.mean(F.softmax_with_cross_entropy(
            logits.astype(jnp.float32), labels))
        return (loss, {}), mut.get("state", {})

    trainer = _trainer_for(model, loss_fn, Adam(1e-4), mesh, strategy,
                           rules)
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, vocab, (bs, seq_len)).astype(np.int32)
    positions = np.sort(
        rs.rand(bs, seq_len).argsort(axis=1)[:, :k], axis=1).astype(np.int32)
    labels = rs.randint(0, vocab, (bs, k)).astype(np.int32)
    ts = trainer.init_state(jnp.asarray(tokens), jnp.asarray(positions))
    batch = _put(trainer, (tokens, positions, labels))
    return bench_trainer(name, trainer, ts, batch,
                         items_per_step=bs * seq_len, unit="tokens/s",
                         batch_size=bs, min_time=min_time)


def _bert_tiny(name, batch_size, dtype, mesh, strategy, rules, min_time):
    """Small-config BERT for CPU-mesh scaling CI (same code path)."""
    return _bert(name, batch_size, dtype, mesh, strategy, rules, min_time,
                 seq_len=32, vocab=1024, model_dim=64, num_layers=2,
                 num_heads=4, ffn_dim=128)


def _deepfm(name, batch_size, dtype, mesh, strategy, rules, min_time,
            fields: int = 26, vocab_per_field: int = 1000, dense_dim: int = 13):
    """DeepFM CTR (dist_ctr capability; BASELINE DeepFM target)."""
    from paddle_tpu.models.nlp import DeepFM
    bs = batch_size or 512
    model = DeepFM(num_fields=fields, vocab_per_field=vocab_per_field,
                   dense_dim=dense_dim)

    def loss_fn(module, variables, batch, rng, training):
        dense, sparse, y = batch
        logit, mut = module.apply(variables, dense, sparse,
                                  training=training, rngs=rng, mutable=True)
        loss = jnp.mean(F.sigmoid_cross_entropy_with_logits(logit, y))
        return (loss, {}), mut.get("state", {})

    trainer = _trainer_for(model, loss_fn, Adam(1e-3), mesh, strategy, rules)
    rs = np.random.RandomState(0)
    dense = rs.randn(bs, dense_dim).astype(np.float32)
    sparse = rs.randint(0, vocab_per_field, (bs, fields)).astype(np.int32)
    y = rs.randint(0, 2, bs).astype(np.float32)
    ts = trainer.init_state(jnp.asarray(dense), jnp.asarray(sparse))
    batch = _put(trainer, (dense, sparse, y))
    return bench_trainer(name, trainer, ts, batch, items_per_step=bs,
                         unit="samples/s", batch_size=bs, min_time=min_time)


def _registry() -> Dict[str, Callable]:
    from paddle_tpu.models import vision as V
    return {
        "mnist": _mnist,
        "mlp": _image_spec(lambda num_classes, dtype: V.MLP(
            num_classes=num_classes, dtype=dtype), img=28, classes=10,
            default_bs=128),
        "alexnet": _image_spec(
            lambda num_classes, dtype: V.AlexNet(num_classes, dtype=dtype)),
        "vgg16": _image_spec(
            lambda num_classes, dtype: V.vgg16(num_classes, dtype=dtype)),
        "resnet50": _image_spec(
            lambda num_classes, dtype: V.resnet50(num_classes, dtype=dtype)),
        "se_resnext50": _image_spec(
            lambda num_classes, dtype: V.se_resnext50(num_classes,
                                                      dtype=dtype)),
        "googlenet": _image_spec(
            lambda num_classes, dtype: V.GoogLeNet(num_classes, dtype=dtype)),
        "transformer": _transformer,
        "lm_longctx": _lm_longctx,
        "bert": _bert,
        "bert_tiny": _bert_tiny,
        "stacked_lstm": _stacked_lstm,
        "deepfm": _deepfm,
    }


MODELS = _registry()


def run_model(name: str, batch_size: Optional[int] = None,
              dtype=jnp.float32, mesh=None, strategy=None, rules=None,
              min_time: float = 2.0, **model_kwargs) -> BenchResult:
    if name not in MODELS:
        raise ValueError(f"unknown benchmark model {name!r}; "
                         f"choose from {sorted(MODELS)}")
    return MODELS[name](name, batch_size, dtype, mesh, strategy, rules,
                        min_time, **model_kwargs)


# Published reference INFERENCE numbers (BASELINE.md: Xeon E5-2650v4,
# MKL-DNN): imgs/s at the listed batch size.
INFER_BASELINES = {
    ("resnet50", 1): 107.83,
    ("resnet50", 16): 217.69,
    ("googlenet", 16): 600.94,
    ("alexnet", 16): 850.51,
    ("vgg16", 1): 75.07,        # VGG-19 figure; closest published
}

def _infer_models():
    from paddle_tpu.models import vision as V
    return {
        "resnet50": lambda d: V.resnet50(1000, dtype=d),
        "googlenet": lambda d: V.GoogLeNet(1000, dtype=d),
        "alexnet": lambda d: V.AlexNet(1000, dtype=d),
        "vgg16": lambda d: V.vgg16(1000, dtype=d),
    }


# derived from the ctor table so the CLI gate and run_infer can
# never drift apart
INFER_MODELS = tuple(sorted(_infer_models()))


def run_infer(name: str, batch_size: int = 16, dtype=jnp.float32,
              min_time: float = 2.0, img: int = 224) -> BenchResult:
    """Inference throughput (reference IntelOptimizedPaddle.md infer
    table; served-model path: eval-mode forward, no grads)."""
    from paddle_tpu.benchmark.harness import (compiled_flops,
                                              device_peak_flops, run_timed)
    ctors = _infer_models()
    if name not in ctors:
        raise ValueError(f"unknown infer model {name!r}; "
                         f"choose from {sorted(ctors)}")
    model = ctors[name](dtype)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch_size, img, img, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x)

    # Two layers of chaining (run_timed caller contract, harness.chain_k):
    # K forwards chained INSIDE one program (amortizes the per-dispatch
    # overhead that dominates a single small forward), and the carry
    # chained ACROSS steps (independent calls could overlap or be elided).
    K = 8 if jax.devices()[0].platform == "tpu" else 2
    kfwd_j = chain_k(
        lambda c, v, xx: model.apply(v, xx + c, training=False), K)

    def step(s):
        s2 = kfwd_j(s, variables, x)
        return s2, s2

    sec_k, steps, _ = run_timed(step, jnp.zeros((), x.dtype),
                                min_time=min_time)
    sec = sec_k / K
    steps *= K
    # XLA's cost analysis doesn't model fori_loop trip counts — the
    # chained program's body is counted ONCE — so the undivided figure
    # already equals one forward (plus a negligible carry add). Dividing
    # by K (as before) understated flops ~K-fold; recompiling the
    # unchained forward just for FLOPs would cost a second full compile.
    flops = compiled_flops(kfwd_j, jnp.zeros((), x.dtype), variables, x)
    peak = device_peak_flops()
    baseline = INFER_BASELINES.get((name, batch_size))
    value = batch_size / sec
    return BenchResult(
        model=f"{name}_infer", unit="imgs/s", value=value,
        ms_per_step=sec * 1e3, steps=steps, batch_size=batch_size,
        flops_per_step=flops,
        tflops_per_sec=(flops / sec / 1e12) if flops else None,
        mfu=(flops / sec / peak) if (flops and peak) else None,
        device=getattr(jax.devices()[0], "device_kind",
                       jax.devices()[0].platform),
        vs_baseline=(value / baseline) if baseline else None)
