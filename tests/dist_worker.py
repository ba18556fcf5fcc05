"""Shared worker script for the multi-process distributed tests.

The analog of the reference's dist_mnist.py / dist_se_resnext.py model
files driven by TestDistBase (test_dist_base.py:35,341): every process
runs this same script; the parent compares the losses each process prints.

Phases:
1. bootstrap: paddle_tpu.parallel.distributed.init_distributed (the
   gen_nccl_id capability) from PTPU_* env;
2. collective sanity: global psum over every device in the world;
3. training: 3 MeshTrainer steps of an MLP on a dp mesh spanning both
   processes, global batch assembled from per-process local shards.

Prints ONE json line: {"proc":, "nprocs":, "ndev":, "psum":, "losses":}.

Metrics mode (`PTPU_WORKER_METRICS=1`): each process additionally
serves its training telemetry on a live MetricsServer, self-scrapes
`/metrics` over HTTP, and embeds the exposition body in the JSON line
(json.dumps keeps it one line) so the parent can run straggler
detection over real per-worker scrape bodies. `PTPU_WORKER_SLOW_PROC`
names the process whose input pipeline sleeps `PTPU_WORKER_SLOW_MS`
per step — the deliberate straggler.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from paddle_tpu.core.executor import supervised_loss
    from paddle_tpu.metrics import accuracy
    from paddle_tpu.models import MLP
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import MeshConfig, MeshTrainer, make_mesh
    from paddle_tpu.parallel.distributed import (
        init_distributed, process_count, process_index)

    init_distributed()
    nprocs = process_count()
    proc = process_index()
    ndev = jax.device_count()

    # -- phase 2: global collective --------------------------------------
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(MeshConfig(dp=ndev))
    sh = NamedSharding(mesh, P("dp"))
    local = np.full((len(jax.local_devices()),), float(proc + 1), np.float32)
    arr = jax.make_array_from_process_local_data(sh, local)
    psum = float(jax.jit(jnp.sum)(arr))

    # -- phase 3: 2-process data-parallel training -----------------------
    model = MLP(hidden=(16,), num_classes=4)
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(lg, y),
        metrics={"acc": accuracy})
    trainer = MeshTrainer(model, Adam(1e-2), loss_fn, mesh)

    gbs = 8 * ndev
    rs = np.random.RandomState(0)              # same on every process
    gx = rs.randn(gbs, 6).astype(np.float32)
    gy = rs.randint(0, 4, gbs).astype(np.int64)

    ts = trainer.init_state(jnp.zeros((gbs, 6)))

    # per-process local slice of the global batch (DataFeeder splitting
    # capability): rows are laid out in device order
    bsh = NamedSharding(mesh, P("dp"))
    rows_per_proc = gbs // nprocs
    lo = proc * rows_per_proc
    x = jax.make_array_from_process_local_data(
        bsh, gx[lo:lo + rows_per_proc])
    y = jax.make_array_from_process_local_data(
        bsh, gy[lo:lo + rows_per_proc])

    out = {"proc": proc, "nprocs": nprocs, "ndev": ndev, "psum": psum}

    metrics_mode = os.environ.get("PTPU_WORKER_METRICS") == "1"
    reg = srv = h_input = None
    slow_ms = 0.0
    if metrics_mode:
        import time
        import urllib.request
        from paddle_tpu.obs.http import MetricsServer
        from paddle_tpu.obs.metrics import MetricsRegistry
        reg = MetricsRegistry()
        trainer.enable_metrics(reg)
        h_input = reg.histogram(
            "ptpu_train_input_wait_ms",
            "Host wall time producing the step's input batch")
        if os.environ.get("PTPU_WORKER_SLOW_PROC") == str(proc):
            slow_ms = float(os.environ.get("PTPU_WORKER_SLOW_MS", "30"))
        srv = MetricsServer(reg).start()

    losses = []
    steps = 6 if metrics_mode else 3
    for i in range(steps):
        if metrics_mode:
            import time
            t0 = time.perf_counter()
            if slow_ms:
                time.sleep(slow_ms / 1e3)   # the wedged input pipeline
            h_input.observe((time.perf_counter() - t0) * 1e3)
        ts, fetches = trainer.train_step(ts, (x, y), rng=jax.random.key(i))
        losses.append(float(fetches["loss"]))
    out["losses"] = losses

    if metrics_mode:
        # scrape our own live /metrics endpoint — the parent gets the
        # exact body a fleet aggregator would
        with urllib.request.urlopen(srv.url, timeout=10) as resp:
            out["exposition"] = resp.read().decode("utf-8")
        srv.stop()

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
