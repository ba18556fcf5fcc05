"""Train (briefly) → freeze to TRUE int8 → compare → export for serving.

The int8 counterpart of the MNIST book chapter: a small CNN is trained
for a few steps, frozen to the real int8 execution path
(quant/int8_compute.py — int8 x int8 -> int32 on the MXU, per-channel
weight scales, calibrated static activation scales), its accuracy
checked against the float model, and exported with
save_inference_model so the C-ABI server (serving/serving.cc) or
InferencePredictor can serve the quantized artifact.

    python examples/quantize_int8_serve.py            # CPU or TPU
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.executor import Trainer, supervised_loss
from paddle_tpu.data import datasets
from paddle_tpu.metrics import accuracy
from paddle_tpu.models import LeNet
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import Adam
from paddle_tpu.quant.int8_compute import freeze_int8
from paddle_tpu.testing import export_servable
from paddle_tpu.utils.compile_cache import enable_compile_cache


def batches(reader, bs):
    rows = list(reader())
    for i in range(0, len(rows) - bs + 1, bs):
        chunk = rows[i:i + bs]
        x = np.stack([r[0] for r in chunk]).astype(np.float32)
        y = np.asarray([r[1] for r in chunk], np.int64)
        yield x.reshape(len(chunk), 28, 28, 1), y


def main():
    enable_compile_cache()
    model = LeNet(num_classes=10)
    loss = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y),
        metrics={"acc": accuracy})
    trainer = Trainer(model, Adam(1e-3), loss)
    ts = trainer.init_state(jnp.zeros((32, 28, 28, 1)))
    train = list(batches(datasets.mnist_train(synthetic_n=512), 32))
    for epoch in range(2):
        for b in train:
            ts, f = trainer.train_step(ts, b)
    print(f"trained: loss {float(f['loss']):.3f} "
          f"acc {float(f['acc']):.3f}")

    # float accuracy on held-out batches
    held = list(batches(datasets.mnist_test(synthetic_n=128), 32))
    variables = ts.variables

    def acc_of(m, v):
        hits = tot = 0
        for x, y in held:
            p = np.asarray(m.apply(v, jnp.asarray(x), training=False))
            hits += (p.argmax(-1) == y).sum()
            tot += len(y)
        return hits / tot

    a_f32 = acc_of(model, variables)

    # freeze to int8 compute, calibrating static activation scales on a
    # couple of training batches
    qmodel, qvars = freeze_int8(model, variables,
                                calib_batches=[(jnp.asarray(train[0][0]),),
                                               (jnp.asarray(train[1][0]),)])
    a_int8 = acc_of(qmodel, qvars)
    print(f"accuracy: float {a_f32:.3f}  int8 {a_int8:.3f} "
          f"(delta {a_f32 - a_int8:+.3f})")

    # export the QUANTIZED model for serving; export_servable(verify=True)
    # round-trips the batch through InferencePredictor and asserts the
    # served logits match direct apply
    d = tempfile.mkdtemp(prefix="int8_serve_")
    path = export_servable(os.path.join(d, "model"), qmodel, qvars,
                           [jnp.asarray(held[0][0])], input_names=["x"],
                           verify=True)
    print(f"exported + served from {path}: predictions match direct apply")


if __name__ == "__main__":
    main()
