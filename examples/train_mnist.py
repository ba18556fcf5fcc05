"""Train LeNet on MNIST end to end, checkpoint, export, and serve.

The "recognize digits" book chapter (reference tests/book/
test_recognize_digits.py) as a runnable script: real dataset (synthetic
fallback when the files are absent), train loop, CheckpointManager,
inference export, and a prediction through InferencePredictor.

    python examples/train_mnist.py [--epochs 1] [--bf16]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import Trainer, supervised_loss
from paddle_tpu.data import datasets, readers
from paddle_tpu.io import (CheckpointManager, InferencePredictor,
                           save_inference_model)
from paddle_tpu.metrics import accuracy
from paddle_tpu.models import LeNet
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import Adam
from paddle_tpu.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--max-steps", type=int, default=None,
                    help="cap steps per epoch (smoke runs)")
    ap.add_argument("--outdir", default="/tmp/ptpu_mnist")
    args = ap.parse_args()

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = LeNet(num_classes=10, dtype=dtype)
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y),
        metrics={"acc": accuracy})
    trainer = Trainer(model, Adam(1e-3), loss_fn)
    ts = trainer.init_state(jnp.zeros((args.batch_size, 28, 28, 1)))
    mgr = CheckpointManager(f"{args.outdir}/ckpt", max_to_keep=2,
                            async_save=True)

    train = readers.batch(
        readers.shuffle(datasets.mnist_train(), buf_size=5000),
        args.batch_size, drop_last=True)
    step = 0
    for epoch in range(args.epochs):
        for bi, (xs, ys) in enumerate(train()):
            if args.max_steps and bi >= args.max_steps:
                break
            ts, fetches = trainer.train_step(
                ts, (jnp.asarray(xs), jnp.asarray(ys)))
            step += 1
            if step % 100 == 0:
                print(f"epoch {epoch} step {step} "
                      f"loss {float(fetches['loss']):.4f} "
                      f"acc {float(fetches['acc']):.3f}")
        mgr.save(ts, step=step)
    mgr.wait()

    # evaluate
    test = readers.batch(datasets.mnist_test(), args.batch_size,
                         drop_last=True)
    accs = []
    for bi, (xs, ys) in enumerate(test()):
        if args.max_steps and bi >= args.max_steps:
            break
        accs.append(float(trainer.eval_step(
            ts, (jnp.asarray(xs), jnp.asarray(ys)))["acc"]))
    print(f"test acc: {np.mean(accs):.4f}")

    # export + serve one prediction
    export = f"{args.outdir}/export"
    save_inference_model(
        export, model, {"params": ts.params, "state": ts.state},
        example_inputs=(jnp.zeros((1, 28, 28, 1)),))
    pred = InferencePredictor(export)
    xs, ys = next(iter(test()))
    digit = int(np.argmax(pred.run([xs[:1]])[0]))
    print(f"predicted {digit}, label {int(ys[0])}; export at {export}")


if __name__ == "__main__":
    main()
