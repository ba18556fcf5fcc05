"""Bench driver contract (ISSUE 13 satellite).

A driver run once recorded rc=124 with nothing parsed: it killed
bench.py before its first flushed JSON line, because that line only
printed after backend init plus the full resnet50 build/compile.
The contract under test: `python bench.py` must flush a parseable
primary line (metric/value/unit) within a few seconds of starting —
before ANY model build — so a driver kill at any point still parses.
"""

import json
import os
import queue
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_flushes_primary_line_before_model_build():
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PTPU_BENCH_BUDGET_S="1",     # starve every gated entry
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env, cwd=REPO)
    lines: "queue.Queue[str]" = queue.Queue()

    def _pump(stream):
        for line in stream:
            lines.put(line)

    reader = threading.Thread(target=_pump, args=(proc.stdout,),
                              daemon=True)
    reader.start()
    t0 = time.time()
    try:
        # "within a few seconds": the bound is jax import + devices(),
        # NOT a model build/compile — generous CI margin, but far below
        # any compile window
        line = lines.get(timeout=45)
        elapsed = time.time() - t0
        rec = json.loads(line)
        assert rec["metric"].startswith("resnet50_train_imgs_per_sec_bs")
        assert "value" in rec and rec["unit"] == "imgs/s"
        # the bootstrap line is explicit that nothing was measured yet
        assert rec.get("no_measurement") is True
        assert elapsed < 45, elapsed
    finally:
        proc.kill()
        proc.wait(timeout=30)
