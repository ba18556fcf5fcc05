"""C++ serving shim tests.

Reference bar: the inference C++ API + standalone demo consumer
(api/paddle_api.h, analysis_predictor_tester.cc, api/demo_ci/): a model
exported from training code must be servable through the native ABI, and
a plain C++ binary must produce the same numbers as the Python predictor.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (registers ml_dtypes, loads jax on CPU)
from paddle_tpu.io.inference import InferencePredictor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _site_packages() -> str:
    import numpy
    return os.path.dirname(os.path.dirname(numpy.__file__))


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from paddle_tpu.models import MLP
    from paddle_tpu.testing import export_servable
    import jax.numpy as jnp
    model = MLP(hidden=(8,), num_classes=3)
    x = jnp.zeros((4, 6), jnp.float32)
    variables = model.init(0, x)
    return export_servable(
        str(tmp_path_factory.mktemp("serving") / "model"),
        model, variables, [x], input_names=["x"])


def test_cpredictor_matches_python(model_dir):
    from paddle_tpu.serving import CPredictor
    x = np.linspace(-1, 1, 24).astype(np.float32).reshape(4, 6)

    py = InferencePredictor(model_dir).run([x])
    cp = CPredictor(model_dir, sys_path=f"{REPO}:{_site_packages()}")
    try:
        c_out = cp.run([x])
        assert len(c_out) == len(py)
        for a, b in zip(c_out, py):
            np.testing.assert_allclose(a, b, rtol=1e-6)
        # second run reuses the compiled path (ZeroCopyRun cadence)
        c_out2 = cp.run([x])
        np.testing.assert_allclose(c_out2[0], c_out[0])
    finally:
        cp.close()


def test_cpredictor_bad_model_dir():
    from paddle_tpu.serving import CPredictor
    with pytest.raises(RuntimeError, match="ptpu_create failed"):
        CPredictor("/nonexistent/model", sys_path=REPO)


def test_library_builds():
    from paddle_tpu.serving import build_library
    lib = build_library()
    assert lib is not None and os.path.exists(lib)


def test_cpp_demo_binary(model_dir):
    """Compile and run the standalone C++ consumer; its printed output sum
    must match the Python predictor on the same deterministic input."""
    from paddle_tpu.serving import build_demo
    demo = build_demo()
    assert demo is not None, "demo must compile (g++ is in this image)"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"            # embedded interp: CPU only
    env["PYTHONPATH"] = (f"{REPO}{os.pathsep}{_site_packages()}"
                         f"{os.pathsep}" + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [demo, model_dir, f"{REPO}:{_site_packages()}"],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, f"demo failed:\n{proc.stdout}\n{proc.stderr}"
    line = [l for l in proc.stdout.splitlines() if l.startswith("output 0")]
    assert line, proc.stdout
    assert "shape=4x3" in line[0]
    c_sum = float(line[0].split("sum=")[1])

    # python reference on the demo's deterministic ramp input
    x = (np.arange(24) % 100 / 100.0).astype(np.float32).reshape(4, 6)
    py_sum = float(InferencePredictor(model_dir).run([x])[0].sum())
    assert abs(c_sum - py_sum) < 1e-4 * max(1.0, abs(py_sum))


def test_cpp_train_demo(tmp_path):
    """Native C++ trainer demo (reference train/demo/demo_trainer.cc +
    test_train_recognize_digits.cc): the C++ binary owns the loop, the
    loss falls, and a checkpoint is committed."""
    from paddle_tpu.serving import build_train_demo
    demo = build_train_demo()
    assert demo is not None, "train demo must compile (g++ is in image)"

    ckpt = str(tmp_path / "cpp_ckpt")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [demo, f"{REPO}:{_site_packages()}", ckpt],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, \
        f"train demo failed:\n{proc.stdout}\n{proc.stderr}"
    assert "TRAIN DEMO OK" in proc.stdout
    # the checkpoint the C++ app requested exists and loads
    from paddle_tpu.io.checkpoint import load_checkpoint
    tree = load_checkpoint(ckpt)
    assert "params" in tree and "opt" in tree


def test_cpredictor_clone_concurrent(model_dir):
    """Reference threading contract (paddle_api.h: one predictor per
    thread via Clone): cloned handles serve concurrently with no output
    cross-talk; run() on a clone matches the single-threaded answer for
    that thread's input every time."""
    import threading

    from paddle_tpu.serving import CPredictor
    base = CPredictor(model_dir, sys_path=f"{REPO}:{_site_packages()}")
    n_threads, n_runs = 4, 15
    rs = np.random.RandomState(0)
    inputs = [rs.randn(4, 6).astype(np.float32) for _ in range(n_threads)]
    want = [base.run([x])[0] for x in inputs]   # single-thread reference

    clones = [base.clone() for _ in range(n_threads)]
    errors = []

    def worker(i):
        try:
            for _ in range(n_runs):
                out = clones[i].run([inputs[i]])[0]
                np.testing.assert_allclose(out, want[i], rtol=1e-6)
        except Exception as e:   # surfaced below; threads must not die
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    # a hung worker must FAIL (and must not let cleanup free in-use handles)
    assert not any(t.is_alive() for t in threads), "worker thread hung"
    try:
        assert not errors, errors
    finally:
        for c in clones:
            c.close()
        base.close()


def test_cpredictor_clone_throughput(model_dir):
    """Four clone threads drive the C ABI at once: every call of every
    clone returns the base predictor's output, none hangs and none
    raises. A CPU run gives counts, never a time — the ratio of serial
    to concurrent wall-clock rates this once asserted depended on what
    else loaded the machine."""
    import threading

    from paddle_tpu.serving import CPredictor
    base = CPredictor(model_dir, sys_path=f"{REPO}:{_site_packages()}")
    x = np.linspace(-1, 1, 24).astype(np.float32).reshape(4, 6)
    want = base.run([x])[0]                      # compile once
    n, n_threads = 40, 4

    clones = [base.clone() for _ in range(n_threads)]
    errors, served = [], []

    def worker(c):
        try:
            for _ in range(n):
                np.testing.assert_array_equal(c.run([x])[0], want)
                served.append(1)
        except Exception as e:   # a dead worker must fail the test
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(c,)) for c in clones]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "worker thread hung"
    assert not errors, errors
    assert len(served) == n * n_threads
    for c in clones:
        c.close()
    base.close()
