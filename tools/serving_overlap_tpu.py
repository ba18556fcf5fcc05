"""Serving clone-thread overlap ON THE REAL TPU.

The README's serving-concurrency number was measured on a tiny CPU MLP
(1.09x — dispatch-bound); the claim that bigger models overlap more
because JAX releases the GIL during device execution was untested. This
measures it: ResNet-50 bs16 inference exported via save_inference_model
and served through the C ABI (serving.cc clone-per-thread contract),
serial vs 4 clone threads, on the TPU.

Run: python tools/serving_overlap_tpu.py
"""

import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.io.inference import save_inference_model
from paddle_tpu.models import vision as V
from paddle_tpu.serving import CPredictor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _site_packages():
    return os.path.dirname(os.path.dirname(np.__file__))


def main():
    assert jax.devices()[0].platform == "tpu", "this measures the TPU"
    bs = 16
    x0 = jnp.zeros((bs, 224, 224, 3), jnp.float32)
    model = V.resnet50(1000, dtype=jnp.bfloat16)
    variables = model.init(jax.random.key(0), x0)
    d = tempfile.mkdtemp(prefix="serving_tpu_")
    path = os.path.join(d, "model")
    save_inference_model(path, model, variables, [x0], input_names=["x"])
    print("exported", path)

    base = CPredictor(path, sys_path=f"{REPO}:{_site_packages()}")
    rs = np.random.RandomState(0)
    x = rs.randn(bs, 224, 224, 3).astype(np.float32)
    base.run([x])                        # compile once
    n_threads, n = 4, 30

    t0 = time.perf_counter()
    for _ in range(n * n_threads):
        base.run([x])
    serial = n * n_threads / (time.perf_counter() - t0)

    clones = [base.clone() for _ in range(n_threads)]
    errors = []

    def worker(c):
        try:
            for _ in range(n):
                c.run([x])
        except Exception as e:
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(c,)) for c in clones]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors, errors
    conc = n * n_threads / (time.perf_counter() - t0)
    print(f"resnet50 bs16 on {jax.devices()[0].device_kind}: "
          f"serial {serial:.1f} req/s ({serial*bs:.0f} imgs/s), "
          f"4-thread clones {conc:.1f} req/s ({conc*bs:.0f} imgs/s), "
          f"overlap {conc/serial:.2f}x")
    for c in clones:
        c.close()
    base.close()


if __name__ == "__main__":
    main()
