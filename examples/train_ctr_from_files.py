"""CTR training straight from slot-format files (the AsyncExecutor flow).

DeepFM over multi-slot text files: the native C++ DataFeed parses files
off the training thread, sparse ids convert to padded+mask form, and
device prefetch overlaps H2D with compute — the reference's
AsyncExecutor.run_from_file / MultiSlotDataFeed capability
(framework/async_executor.cc, data_feed.cc) in TPU form.

    python examples/train_ctr_from_files.py [--rows 20000] [--epochs 2]
"""

import argparse
import glob
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import Trainer, train_from_files
from paddle_tpu.data.datafeed import write_slot_file
from paddle_tpu.models.nlp import DeepFM
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import Adam
from paddle_tpu.utils.compile_cache import enable_compile_cache

CONFIG = "label:int64:dense:1;dense:float:dense:13;ids:int64:sparse"
FIELDS, VOCAB, DENSE = 26, 1000, 13


def synthesize(datadir: str, rows: int, n_files: int = 4) -> None:
    """Criteo-shaped slot files with a learnable signal in the ids."""
    os.makedirs(datadir, exist_ok=True)
    rs = np.random.RandomState(0)
    per = rows // n_files
    for fi in range(n_files):
        exs = []
        for _ in range(per):
            ids = rs.randint(0, VOCAB, FIELDS)
            dense = rs.randn(DENSE)
            label = int((ids[0] % 2) ^ (dense[0] > 0))
            exs.append(([label],
                        [float(np.float32(v)) for v in dense],
                        [int(v) for v in ids]))
        write_slot_file(os.path.join(datadir, f"part-{fi:03d}.txt"),
                        exs, CONFIG)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--datadir", default="/tmp/ptpu_ctr")
    ap.add_argument("--rows", type=int, default=20000)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--nthreads", type=int, default=4)
    args = ap.parse_args()

    files = sorted(glob.glob(os.path.join(args.datadir, "part-*.txt")))
    if not files:
        synthesize(args.datadir, args.rows)
        files = sorted(glob.glob(os.path.join(args.datadir, "part-*.txt")))
    print(f"{len(files)} slot files in {args.datadir}")

    model = DeepFM(num_fields=FIELDS, vocab_per_field=VOCAB,
                   dense_dim=DENSE)

    def loss_fn(module, variables, batch, rng, training):
        dense, sparse, y = batch
        logit, mut = module.apply(variables, dense, sparse,
                                  training=training, rngs=rng, mutable=True)
        loss = jnp.mean(F.sigmoid_cross_entropy_with_logits(logit, y))
        return (loss, {}), mut.get("state", {})

    def batch_fn(b):
        padded, _ = b["ids"]
        return (jnp.asarray(b["dense"]), jnp.asarray(padded),
                jnp.asarray(b["label"][:, 0], jnp.float32))

    trainer = Trainer(model, Adam(1e-3), loss_fn)
    ts = trainer.init_state(jnp.zeros((args.batch_size, DENSE)),
                            jnp.zeros((args.batch_size, FIELDS), jnp.int32))

    losses = []
    ts = train_from_files(
        trainer, ts, files, CONFIG, batch_fn,
        batch_size=args.batch_size, nthreads=args.nthreads,
        epochs=args.epochs, max_sparse_len=FIELDS,
        callback=lambda s, f: losses.append(float(f["loss"])))
    n = max(1, len(losses) // 10)
    print(f"{len(losses)} steps; loss {np.mean(losses[:n]):.4f} -> "
          f"{np.mean(losses[-n:]):.4f}")


if __name__ == "__main__":
    main()
