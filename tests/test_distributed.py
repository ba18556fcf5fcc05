"""Multi-process-on-localhost distributed tests.

The reference pattern (test_dist_base.py:213,341): spawn real processes on
127.0.0.1, run the same model in each, pickle losses over stdout, compare
against a local single-process run. Here: 2 jax.distributed processes on
the CPU backend (2 virtual devices each = 4-device world), exercising
parallel/distributed.py bootstrap, a cross-process collective, and a
data-parallel MeshTrainer step — plus the launcher module itself
(python/paddle/distributed/launch.py capability)."""

import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu.parallel.launch import free_port, launch

WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cluster(nproc=2, devs=2):
    env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    results = launch(nproc, [sys.executable, WORKER],
                     cpu_devices_per_proc=devs, env=env, timeout=300)
    outs = []
    for r in results:
        line = [l for l in r.stdout.strip().splitlines()
                if l.startswith("{")][-1]
        outs.append(json.loads(line))
    return outs


def test_two_process_cluster():
    outs = _run_cluster(nproc=2, devs=2)
    assert {o["proc"] for o in outs} == {0, 1}
    for o in outs:
        assert o["nprocs"] == 2
        assert o["ndev"] == 4            # world = 2 procs x 2 devices
        # psum of [1,1] on proc0 + [2,2] on proc1
        assert o["psum"] == pytest.approx(6.0)
    # both processes observe identical global losses (allreduce worked)
    np.testing.assert_allclose(outs[0]["losses"], outs[1]["losses"],
                               rtol=1e-6)
    assert outs[0]["losses"][-1] < outs[0]["losses"][0]


def test_matches_single_process():
    """2-process dp run == single-process run with the same global batch
    (the reference's delta=1e-5 trainer-vs-local comparison,
    test_dist_mnist.py:26)."""
    outs = _run_cluster(nproc=2, devs=2)

    env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    single = launch(1, [sys.executable, WORKER],
                    cpu_devices_per_proc=4, env=env, timeout=300)
    line = [l for l in single[0].stdout.strip().splitlines()
            if l.startswith("{")][-1]
    solo = json.loads(line)
    assert solo["ndev"] == 4
    np.testing.assert_allclose(outs[0]["losses"], solo["losses"], atol=1e-5)


def test_straggler_detection_two_workers():
    """Tentpole acceptance: a deliberately slowed dp worker is surfaced
    by the straggler gauge. Each worker serves live /metrics and
    self-scrapes it; the parent runs StragglerDetector over the real
    per-worker exposition bodies. The slow worker stalls its INPUT
    pipeline — in lock-step SPMD its extra time bleeds into everyone's
    step wall via the collectives, so blame must come from
    ptpu_train_input_wait_ms, which stays local."""
    from paddle_tpu.obs.metrics import MetricsRegistry
    from paddle_tpu.obs.straggler import StragglerDetector

    env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "PTPU_WORKER_METRICS": "1",
           "PTPU_WORKER_SLOW_PROC": "1",
           "PTPU_WORKER_SLOW_MS": "40"}
    try:
        results = launch(2, [sys.executable, WORKER],
                         cpu_devices_per_proc=2, env=env, timeout=300)
    except RuntimeError as e:
        if "Multiprocess computations aren't implemented" in str(e):
            pytest.skip("jaxlib build lacks multi-process CPU support")
        raise
    outs = []
    for r in results:
        line = [l for l in r.stdout.strip().splitlines()
                if l.startswith("{")][-1]
        outs.append(json.loads(line))
    expositions = {}
    for o in outs:
        worker = f"w{o['proc']}"
        assert "ptpu_train_step_ms" in o["exposition"]
        assert "ptpu_train_input_wait_ms" in o["exposition"]
        expositions[worker] = o["exposition"]

    reg = MetricsRegistry()
    det = StragglerDetector(registry=reg)
    verdict = det.update(expositions)
    assert verdict["w1"]["straggler"] is True
    assert verdict["w0"]["straggler"] is False
    assert verdict["w1"]["input_wait_ms"] > 10 * verdict["w0"]["input_wait_ms"]
    g = reg.get("ptpu_train_straggler")
    assert g.labels(worker="w1").value == 1.0
    assert g.labels(worker="w0").value == 0.0
    # lock-step check: both workers' step walls inflate together
    assert reg.get("ptpu_train_step_dispersion").value < 3.0
    # the fleet body merges the per-worker histograms exactly
    fleet = det.fleet_exposition(expositions)
    assert "ptpu_train_step_ms_count" in fleet


def test_launcher_reports_failures():
    env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    with pytest.raises(RuntimeError, match="boom|rc="):
        launch(2, [sys.executable, "-c", "raise SystemExit('boom')"],
               cpu_devices_per_proc=1, env=env, timeout=60)


def test_free_port():
    p1, p2 = free_port(), free_port()
    assert 1024 <= p1 <= 65535 and 1024 <= p2 <= 65535


ELASTIC = os.path.join(os.path.dirname(__file__), "elastic_worker.py")
DEEPFM = os.path.join(os.path.dirname(__file__), "dist_worker_deepfm.py")


def _env(extra=None):
    env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.update(extra or {})
    return env


def test_fault_injection_and_elastic_restart(tmp_path):
    """SURVEY §5.3: kill one proc mid-run; survivors fail
    fast with a clear peer-death report; a restart resumes from the last
    committed checkpoint and reproduces the uninterrupted loss curve."""
    ckpt = str(tmp_path / "elastic")
    total = {"PTPU_CKPT_DIR": ckpt, "PTPU_TOTAL_STEPS": "6"}

    # run 1: proc 1 hard-crashes at step 3 (steps 0-2 checkpointed)
    with pytest.raises(RuntimeError) as e:
        launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
               env=_env({**total, "PTPU_FAULT_PROC": "1",
                         "PTPU_FAULT_STEP": "3"}),
               timeout=240, peer_failure_grace=3.0)
    msg = str(e.value)
    assert "peer failure: proc 1 died (rc=17)" in msg
    assert "survivors [0] terminated" in msg

    # restart: same command, no fault -> resumes from ckpt and finishes
    results = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                     env=_env(total), timeout=240)
    outs = [json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("{")][-1]) for r in results]
    assert all(o["start_step"] == 3 for o in outs)   # resumed, not restarted
    assert outs[0]["steps"] == [3, 4, 5]

    # the stitched loss curve equals an uninterrupted run
    clean = str(tmp_path / "clean")
    results2 = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                      env=_env({"PTPU_CKPT_DIR": clean,
                                "PTPU_TOTAL_STEPS": "6"}), timeout=240)
    solo = json.loads([l for l in results2[0].stdout.splitlines()
                       if l.startswith("{")][-1])
    np.testing.assert_allclose(outs[0]["losses"], solo["losses"][3:],
                               atol=1e-5)


def test_sigterm_preemption_resumes_exactly(tmp_path):
    """Resilience tentpole: SIGTERM lands mid-run (both processes, as a
    TPU slice reclaim delivers it); the supervisor defers it to the step
    boundary, writes an emergency synchronous checkpoint and exits with
    the distinct preemption code. The restarted run resumes at the
    preempted step and reproduces the uninterrupted loss curve exactly.
    save_every=3 makes the emergency save load-bearing: the last
    periodic checkpoint is ckpt-3, the preemption point is step 4."""
    from paddle_tpu.resilience.errors import PREEMPT_EXIT_CODE

    ckpt = str(tmp_path / "preempt")
    base = {"PTPU_CKPT_DIR": ckpt, "PTPU_TOTAL_STEPS": "8",
            "PTPU_SAVE_EVERY": "3"}

    with pytest.raises(RuntimeError) as e:
        launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
               env=_env({**base, "PTPU_CHAOS_SIGTERM_STEP": "4"}),
               timeout=240, peer_failure_grace=5.0)
    msg = str(e.value)
    if "Multiprocess computations aren't implemented" in msg:
        pytest.skip("jaxlib build lacks multi-process CPU support")
    assert f"rc={PREEMPT_EXIT_CODE}" in msg       # preempted, not crashed
    assert '"evt": "preempt"' in msg              # event on captured stdout
    # the emergency checkpoint is committed and intact
    from paddle_tpu.io.checkpoint import checkpoint_step, latest_checkpoint
    assert checkpoint_step(latest_checkpoint(ckpt)) == 4

    # restart: no chaos -> resumes at the preempted step and finishes
    results = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                     env=_env(base), timeout=240)
    outs = [json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("{") and '"evt"' not in l][-1])
            for r in results]
    assert all(o["start_step"] == 4 for o in outs)
    assert outs[0]["steps"] == [4, 5, 6, 7]

    # stitched curve == uninterrupted run (bit-level batch/rng parity)
    clean = str(tmp_path / "clean")
    results2 = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                      env=_env({"PTPU_CKPT_DIR": clean,
                                "PTPU_TOTAL_STEPS": "8"}), timeout=240)
    solo = json.loads([l for l in results2[0].stdout.splitlines()
                       if l.startswith("{") and '"evt"' not in l][-1])
    np.testing.assert_allclose(outs[0]["losses"], solo["losses"][4:],
                               atol=1e-5)


def test_two_process_async_checkpoint(tmp_path):
    """Async checkpointing across process boundaries: each process's
    worker thread runs the commit barriers; the final checkpoint restores
    and matches a sync-save run's loss curve."""
    ckpt = str(tmp_path / "async")
    results = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                     env=_env({"PTPU_CKPT_DIR": ckpt,
                               "PTPU_TOTAL_STEPS": "4",
                               "PTPU_ASYNC_CKPT": "1"}), timeout=240)
    outs = [json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("{")][-1]) for r in results]
    assert outs[0]["steps"] == [0, 1, 2, 3]
    # resume from the async-written checkpoint: nothing left to do
    results2 = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                      env=_env({"PTPU_CKPT_DIR": ckpt,
                                "PTPU_TOTAL_STEPS": "4",
                                "PTPU_ASYNC_CKPT": "1"}), timeout=240)
    outs2 = [json.loads([l for l in r.stdout.splitlines()
                         if l.startswith("{")][-1]) for r in results2]
    assert all(o["start_step"] == 4 and o["steps"] == [] for o in outs2)
    # loss curve identical to the sync-save path
    sync = str(tmp_path / "sync")
    results3 = launch(2, [sys.executable, ELASTIC], cpu_devices_per_proc=2,
                      env=_env({"PTPU_CKPT_DIR": sync,
                                "PTPU_TOTAL_STEPS": "4"}), timeout=240)
    solo = json.loads([l for l in results3[0].stdout.splitlines()
                       if l.startswith("{")][-1])
    np.testing.assert_allclose(outs[0]["losses"], solo["losses"], atol=1e-6)


def test_two_process_sharded_embedding_deepfm():
    """DeepFM + ShardedEmbedding through the launcher
    (2 procs x 2 devices) matches the single-process run, with the table
    row-sharded across process boundaries (pserver capability e2e)."""
    outs = []
    for r in launch(2, [sys.executable, DEEPFM], cpu_devices_per_proc=2,
                    env=_env(), timeout=300):
        outs.append(json.loads([l for l in r.stdout.splitlines()
                                if l.startswith("{")][-1]))
    assert {o["proc"] for o in outs} == {0, 1}
    for o in outs:
        assert o["ndev"] == 4
        # each device owns a strict slice of the table (vocab/fsdp rows)
        assert o["local_rows"] == o["total_rows"] // 2
    np.testing.assert_allclose(outs[0]["losses"], outs[1]["losses"],
                               rtol=1e-6)
    assert outs[0]["losses"][-1] < outs[0]["losses"][0]

    single = launch(1, [sys.executable, DEEPFM], cpu_devices_per_proc=4,
                    env=_env(), timeout=300)
    solo = json.loads([l for l in single[0].stdout.splitlines()
                       if l.startswith("{")][-1])
    np.testing.assert_allclose(outs[0]["losses"], solo["losses"], atol=1e-5)
