"""Multi-process launcher.

Capability-equivalent of /root/reference/python/paddle/distributed/launch.py
(one process per device, PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS env
contract) — here one process per *host* (TPU processes own all their local
chips), with the PTPU_* env contract consumed by
paddle_tpu.parallel.distributed.init_distributed:

    python -m paddle_tpu.parallel.launch --nproc 2 train.py --lr 0.1

--cpu_devices_per_proc N forces the CPU backend with N virtual devices per
process — the multi-process-on-localhost test recipe (reference
test_dist_base.py:341 spawns localhost pservers/trainers the same way).
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
from typing import List, Optional, Sequence


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(nproc: int, command: Sequence[str],
           coordinator: Optional[str] = None,
           cpu_devices_per_proc: Optional[int] = None,
           env: Optional[dict] = None,
           timeout: float = 600.0,
           peer_failure_grace: float = 5.0
           ) -> List[subprocess.CompletedProcess]:
    """Spawn `nproc` copies of `command` wired into one jax.distributed
    world. Returns per-process CompletedProcess (stdout/stderr captured).

    Failure detection (the reference has none — SURVEY §5.3 "no elastic
    re-scheduling"; this harness exceeds it): a watchdog polls the
    children, and when one dies with a nonzero rc while peers are still
    running, the peers get `peer_failure_grace` seconds to notice (barrier
    error) and are then terminated — survivors fail FAST with a clear
    "peer died" report instead of hanging in a collective until `timeout`.
    RuntimeError carries every process's rc and log tail.
    """
    import time as _time

    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    procs = []
    for i in range(nproc):
        penv = dict(os.environ)
        penv.update(env or {})
        penv["PTPU_COORDINATOR"] = coordinator
        penv["PTPU_NUM_PROCESSES"] = str(nproc)
        penv["PTPU_PROCESS_ID"] = str(i)
        if cpu_devices_per_proc:
            # localhost test mode: virtual CPU devices, no TPU grab
            penv["JAX_PLATFORMS"] = "cpu"
            flags = [f for f in penv.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f]
            flags.append("--xla_force_host_platform_device_count="
                         f"{cpu_devices_per_proc}")
            penv["XLA_FLAGS"] = " ".join(flags)
        procs.append(subprocess.Popen(
            list(command), env=penv, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))

    # Drain threads start IMMEDIATELY (communicate() in a thread per
    # child): a child that logs more than the ~64KB pipe buffer must
    # never block on write while the watchdog below polls exit codes.
    import threading

    outputs: List[Optional[tuple]] = [None] * nproc

    def drain(i, p):
        outputs[i] = p.communicate()     # returns at process EOF/exit

    threads = [threading.Thread(target=drain, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    for t in threads:
        t.start()

    # Watchdog loop: detect a dead child early and reap the survivors.
    deadline = _time.monotonic() + timeout
    first_fault: Optional[int] = None
    fault_time = 0.0
    killed_as_survivor: List[int] = []
    while True:
        codes = [p.poll() for p in procs]
        if all(c is not None for c in codes):
            break
        now = _time.monotonic()
        if first_fault is None:
            for i, c in enumerate(codes):
                if c is not None and c != 0:
                    first_fault, fault_time = i, now
                    break
        if first_fault is not None and now - fault_time > peer_failure_grace:
            for i, p in enumerate(procs):
                if p.poll() is None:
                    killed_as_survivor.append(i)
                    p.terminate()
            break
        if now > deadline:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            break
        _time.sleep(0.2)

    results = []
    for i, (p, t) in enumerate(zip(procs, threads)):
        t.join(timeout=30)
        if t.is_alive():                 # terminate didn't stick
            p.kill()
            t.join(timeout=10)
        out, err = outputs[i] or ("", "")
        results.append(subprocess.CompletedProcess(
            p.args, p.returncode if p.returncode is not None else -9,
            out, err))
    failed = any(r.returncode != 0 for r in results)
    if failed:
        msgs = []
        if first_fault is not None:
            msgs.append(
                f"peer failure: proc {first_fault} died "
                f"(rc={results[first_fault].returncode}); survivors "
                f"{killed_as_survivor} terminated after "
                f"{peer_failure_grace}s grace")
        for i, r in enumerate(results):
            msgs.append(f"--- proc {i} rc={r.returncode}\n"
                        f"stdout:\n{r.stdout[-2000:]}\n"
                        f"stderr:\n{r.stderr[-2000:]}")
        raise RuntimeError(f"launch of {command!r} failed:\n"
                           + "\n".join(msgs))
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="paddle_tpu.parallel.launch",
                                description=__doc__)
    p.add_argument("--nproc", type=int, required=True)
    p.add_argument("--coordinator", default=None,
                   help="host:port (default: free local port)")
    p.add_argument("--cpu_devices_per_proc", type=int, default=None)
    p.add_argument("script", nargs=argparse.REMAINDER,
                   help="script and its args")
    args = p.parse_args(argv)
    if not args.script:
        p.error("missing script to launch")
    results = launch(args.nproc, [sys.executable] + args.script,
                     coordinator=args.coordinator,
                     cpu_devices_per_proc=args.cpu_devices_per_proc)
    for i, r in enumerate(results):
        sys.stdout.write(r.stdout)
        sys.stderr.write(r.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
