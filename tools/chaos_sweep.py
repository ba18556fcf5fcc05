"""Chaos matrix for the resilience runtime (RESILIENCE.md).

Sweeps a grid of injected faults over the 2-process elastic cluster
(tests/elastic_worker.py via parallel.launch) and, where the platform
cannot run multi-process CPU jobs, over the in-process single-host
loop. Each cell runs train-to-fault, restart-to-completion, and a
fault-free twin, then checks the acceptance property: the stitched loss
curve equals the fault-free curve bit-for-bit and the run never aborts
while an intact checkpoint exists.

The FLEET cells extend the matrix to the serving side: a live
2-replica fleet with one replica behind a NetChaosProxy
(resilience/chaos.py), one cell per wire-fault mode (connect refusal,
503 burst, sustained black-hole, slow first byte) armed on the sticky
primary's path. Columns: failed_requests / truncated_streams (both
must be 0 — breaker failover, stream resume, and hedging absorb the
fault), retry_ratio (token-budget capped), and evicted/rejoined
membership events (the sustained black-hole must trip the breaker and,
after heal, rejoin through the half-open probe).

The KVXFER cells break the fleet KV block transfer itself
(serve/kvxfer.py): a prefill replica behind the proxy feeds a decode
replica through a kv_transfer router, and each cell faults the
/kvblocks pull a different way — blob bit-rot (crc-rejected), connect
refusal, swallowed socket. Acceptance: the pull falls back to plain
re-prefill (a counted fallback) and the client stream is byte-identical
to the warm source's own, zero failed / zero truncated.

One JSON line per cell on stdout:

    {"cell": "sigterm@4", "mode": "cluster", "ok": true, ...}
    {"cell": "fleet:blackhole", "mode": "fleet", "ok": true, ...}
    {"cell": "kvxfer:corrupt", "mode": "kvxfer", "ok": true, ...}

Exit code: 0 iff every cell is ok. The fast in-process subset of this
grid runs in tier-1 as tests/test_chaos.py (`chaos` marker); the fleet
cells' in-process twin is tests/test_fleet_ft.py (`serve` marker).

Run: python tools/chaos_sweep.py [--steps 8] [--inprocess-only]
     [--no-fleet]
"""

import argparse
import json
import os
import sys

import _bootstrap  # noqa: F401  (repo path)

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ELASTIC = os.path.join(REPO, "tests", "elastic_worker.py")


# -- cluster cells -----------------------------------------------------------

def _cluster_env(extra):
    env = {"PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "PTPU_RETRY_SCALE": "0.01"}
    env.update(extra)
    return env


def _cluster_run(ckpt, steps, extra=None, expect_rc=None):
    """Launch the 2-proc elastic worker; returns (outs, err_msg)."""
    from paddle_tpu.parallel.launch import launch
    env = _cluster_env({"PTPU_CKPT_DIR": ckpt, "PTPU_TOTAL_STEPS": str(steps),
                        **(extra or {})})
    try:
        results = launch(2, [sys.executable, ELASTIC],
                         cpu_devices_per_proc=2, env=env, timeout=240,
                         peer_failure_grace=5.0)
    except RuntimeError as e:
        return None, str(e)
    outs = []
    for r in results:
        line = [l for l in r.stdout.splitlines()
                if l.startswith("{") and '"evt"' not in l][-1]
        outs.append(json.loads(line))
    return outs, None


def _losses_by_step(out):
    return dict(zip(out["steps"], out["losses"]))


def _cluster_cell(name, tmp, steps, fault_env, fault_rc, clean_curve):
    """Run fault → restart → compare; returns the verdict dict."""
    ckpt = os.path.join(tmp, name.replace("@", "-").replace(":", "-"))
    detail = {}
    # leg 1: run with the fault armed (may die with fault_rc, may finish)
    outs, err = _cluster_run(ckpt, steps, fault_env)
    faulted = err is not None
    if faulted:
        if fault_rc is None or f"rc={fault_rc}" not in err:
            return {"cell": name, "mode": "cluster", "ok": False,
                    "error": err[-400:]}
        detail["fault_rc"] = fault_rc
        # leg 2: restart with no fault -> must resume and complete
        outs, err = _cluster_run(ckpt, steps)
        if err is not None:
            return {"cell": name, "mode": "cluster", "ok": False,
                    "error": err[-400:]}
        detail["resume_step"] = outs[0]["start_step"]
    # the (possibly stitched) curve must equal the fault-free one
    # bit-for-bit on every step it covers — and cover every step unless
    # the fault leg legitimately truncated the front
    stitched = _losses_by_step(outs[0])
    tail = {s: v for s, v in clean_curve.items() if s in stitched}
    ok = (stitched == tail
          and (faulted or sorted(stitched) == sorted(clean_curve)))
    return {"cell": name, "mode": "cluster", "ok": bool(ok), **detail}


def run_cluster_grid(tmp, steps):
    clean_dir = os.path.join(tmp, "clean")
    outs, err = _cluster_run(clean_dir, steps)
    if err is not None:
        if "Multiprocess computations aren't implemented" in err:
            print(json.dumps({"cell": "cluster_grid", "mode": "cluster",
                              "ok": None,
                              "skipped": "no multi-process CPU support"}))
            return []
        print(json.dumps({"cell": "clean", "mode": "cluster", "ok": False,
                          "error": err[-400:]}))
        return [False]
    clean_curve = _losses_by_step(outs[0])

    mid, late = steps // 2, steps - 1
    from paddle_tpu.resilience.errors import PREEMPT_EXIT_CODE
    grid = [
        # hard kill of one proc mid-run (the pre-existing fault knob)
        (f"kill:p1@{mid}", {"PTPU_FAULT_PROC": "1",
                            "PTPU_FAULT_STEP": str(mid)}, 17),
        # fleet-wide SIGTERM preemption -> emergency ckpt + exit 75
        (f"sigterm@{mid}", {"PTPU_CHAOS_SIGTERM_STEP": str(mid)},
         PREEMPT_EXIT_CODE),
        # newest checkpoint torn after commit (both corruption modes)
        (f"corrupt:truncate@{late}",
         {"PTPU_CHAOS_CORRUPT_STEP": str(late),
          "PTPU_CHAOS_CORRUPT_MODE": "truncate"}, None),
        (f"corrupt:manifest@{late}",
         {"PTPU_CHAOS_CORRUPT_STEP": str(late),
          "PTPU_CHAOS_CORRUPT_MODE": "manifest"}, None),
        # 2-step NaN burst absorbed by the bad-step guard
        (f"nan@{mid}:{mid + 1}",
         {"PTPU_CHAOS_NAN_STEP": f"{mid}:{mid + 1}",
          "PTPU_BAD_STEP_BUDGET": "3"}, None),
        # transient rendezvous + shard-write failures absorbed by retry
        ("init_flap+ckpt_io",
         {"PTPU_CHAOS_INIT_FAIL": "1", "PTPU_CHAOS_CKPT_IO": "2"}, None),
    ]
    oks = []
    for name, env, rc in grid:
        verdict = _cluster_cell(name, tmp, steps, env, rc, clean_curve)
        print(json.dumps(verdict))
        oks.append(verdict["ok"])
    return oks


# -- in-process cells (always runnable) -------------------------------------

def _inproc_run(ckpt, steps, budget=None):
    """Returns (losses_by_step, GoodputLedger) — each cell gets a fresh
    private registry so goodput/lost-time never bleed across cells."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.executor import supervised_loss
    from paddle_tpu.io.checkpoint import CheckpointManager
    from paddle_tpu.models import MLP
    from paddle_tpu.obs.goodput import GoodputLedger
    from paddle_tpu.obs.metrics import MetricsRegistry
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import (
        DistStrategy, MeshConfig, MeshTrainer, make_mesh)
    from paddle_tpu.resilience.supervisor import train_resilient

    mesh = make_mesh(MeshConfig(dp=jax.device_count()))
    trainer = MeshTrainer(
        MLP(hidden=(8,), num_classes=4), Adam(1e-2),
        supervised_loss(lambda lg, y: F.softmax_with_cross_entropy(lg, y)),
        mesh, strategy=DistStrategy(bad_step_budget=budget))
    ts = trainer.init_state(jnp.zeros((16, 6)))
    mgr = CheckpointManager(ckpt, max_to_keep=steps + 1)
    restored, start = mgr.restore_latest(ts)
    if restored is not None:
        ts = restored
    else:
        start = 0

    def batch_for(step):
        rs = np.random.RandomState(1000 + step)
        return (jnp.asarray(rs.randn(16, 6).astype(np.float32)),
                jnp.asarray(rs.randint(0, 4, 16).astype(np.int64)))

    losses = {}
    ledger = GoodputLedger(registry=MetricsRegistry())
    train_resilient(trainer, ts, batch_for, steps, mgr, start_step=start,
                    goodput=ledger,
                    on_step=lambda s, f: losses.__setitem__(
                        s, float(f["loss"])))
    return losses, ledger


def run_inprocess_grid(tmp, steps):
    from paddle_tpu.resilience import chaos

    clean, clean_ledger = _inproc_run(os.path.join(tmp, "ip-clean"), steps)
    print(json.dumps({"cell": "ip:clean", "mode": "inprocess", "ok": True,
                      "goodput": round(clean_ledger.goodput(), 4)}))
    mid, late = steps // 2, steps - 1
    grid = [
        (f"ip:nan@{mid}:{mid + 1}",
         {"PTPU_CHAOS_NAN_STEP": f"{mid}:{mid + 1}"}, 3),
        (f"ip:nan_budget_blown@{mid}",
         {"PTPU_CHAOS_NAN_STEP": str(mid),
          "PTPU_CHAOS_NAN_ATTEMPTS": "3"}, 2),
        (f"ip:corrupt:truncate@{late}",
         {"PTPU_CHAOS_CORRUPT_STEP": str(late),
          "PTPU_CHAOS_CORRUPT_MODE": "truncate"}, None),
        ("ip:ckpt_io", {"PTPU_CHAOS_CKPT_IO": "2"}, None),
    ]
    oks = []
    for name, env, budget in grid:
        os.environ.update(env)
        chaos.reload()
        try:
            losses, ledger = _inproc_run(
                os.path.join(tmp, name.replace(":", "-").replace("@", "-")),
                steps, budget=budget)
            ok = losses == clean
            # goodput column: the fraction of tracked time the faulted
            # cell spent on productive steps, plus where the rest went
            verdict = {"cell": name, "mode": "inprocess", "ok": bool(ok),
                       "goodput": round(ledger.goodput(), 4),
                       "lost_s": {c: round(v, 4) for c, v in
                                  sorted(ledger.lost_seconds().items())}}
        except Exception as e:  # a cell must never take the sweep down
            verdict = {"cell": name, "mode": "inprocess", "ok": False,
                       "error": f"{type(e).__name__}: {e}"}
        finally:
            for k in env:
                os.environ.pop(k, None)
            chaos.reset()
        print(json.dumps(verdict))
        oks.append(verdict["ok"])
    return oks


# -- fleet cells (serving fleet under wire faults) ---------------------------

def _fleet_member(router, url):
    for r in router.replicas:
        if r.url == url:
            return r
    return None


def _fleet_tallies(router):
    """Router-side counters the fleet columns difference against."""
    retr = router.obs.get("ptpu_router_retries_total")
    mem = router.obs.get("ptpu_router_membership_events_total")
    return {"retries": sum(retr.labels(kind=k).value
                           for k in ("connect", "shed", "stream")),
            "evicts": mem.labels(event="evict").value,
            "rejoins": mem.labels(event="rejoin").value}


def run_fleet_grid():
    """The net-chaos matrix over a LIVE serving fleet: two replica
    subprocesses, one reached through a NetChaosProxy, a Router over
    both. Each cell arms one wire-fault mode (resilience/chaos.py),
    drives requests whose sticky shard IS the faulted replica, then
    heals. Columns per cell: failed_requests (client 5xx — must be 0),
    truncated_streams (SSE without [DONE] — must be 0), retry_ratio
    (budget-capped), evicted/rejoined (breaker membership events; the
    sustained black-hole MUST evict and, after heal, rejoin)."""
    import threading  # noqa: F401  (parity with serve_bench helpers)
    import time

    from serve_bench import _spawn_replica, _terminate
    from paddle_tpu.resilience.chaos import NetChaosProxy
    from paddle_tpu.serve.router import Router, prefix_shard
    from paddle_tpu.serve.sse import collect_stream

    proc_a, base_a = _spawn_replica()
    proc_b, base_b = _spawn_replica()
    proxy = NetChaosProxy(upstream_port=int(base_b.rsplit(":", 1)[1]))
    proxy.start()
    proxy_url = f"http://127.0.0.1:{proxy.port}"
    router = Router([base_a, proxy_url], prefix_len=8,
                    scrape_interval_s=0.2, scrape_timeout_s=0.5,
                    connect_timeout_s=1.5, breaker_fails=2,
                    breaker_open_s=0.4, retry_budget_ratio=0.5,
                    retry_budget_burst=8.0, hedge_max_s=0.8).start()

    def wait_whole(timeout_s=15.0):
        """Both members ready with closed breakers (fleet healed)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if all(r.ready and r.breaker == "closed"
                   for r in router.replicas):
                return True
            time.sleep(0.02)
        return False

    def prompts_for(cell_idx):
        """4 FRESH prompts whose sticky shard is the PROXIED replica
        (table index 1): the armed fault must sit on the primary
        path, and the prompts must be new to the fleet — a prompt a
        previous cell already served would be directory-routed to the
        warm survivor and never touch the fault at all."""
        out, seed = [], 100 * cell_idx
        while len(out) < 4:
            cand = [seed % 53, (seed * 7 + 1) % 53, seed % 11,
                    (seed * 3 + 2) % 29] * 2
            if prefix_shard(cand, 2, 8) == 1:
                out.append(cand + [40 + len(out)])
            seed += 1
        return out

    def wait_evicted(timeout_s=8.0):
        """Breaker OPEN on the proxied member (sustained-fault gate)."""
        m = _fleet_member(router, proxy_url)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if m.breaker == "open":
                return True
            time.sleep(0.02)
        return False

    default_slow_ms = proxy.slow_ms
    grid = [("refuse", 2, {}),
            ("http_503", 2, {}),
            ("blackhole", 1 << 30, {}),
            ("slow", 4, {"slow_ms": 300})]
    oks = []
    try:
        for idx, (mode, n, attrs) in enumerate(grid):
            name = f"fleet:{mode}"
            if not wait_whole():
                print(json.dumps({"cell": name, "mode": "fleet",
                                  "ok": False,
                                  "error": "fleet never became whole"}))
                oks.append(False)
                continue
            before = _fleet_tallies(router)
            for k, v in attrs.items():
                setattr(proxy, k, v)
            proxy.arm(mode, n)
            try:
                results = [collect_stream(router.url,
                                          {"prompt": p,
                                           "max_new_tokens": 8},
                                          timeout=60)
                           for p in prompts_for(idx)]
                if mode == "blackhole":
                    # sustained fault: the scrape loop must breaker-
                    # evict the member BEFORE the wire heals
                    wait_evicted()
            finally:
                proxy.heal()
                proxy.slow_ms = default_slow_ms
            # a sustained fault must have tripped the breaker before
            # heal; every mode must leave the fleet whole again after
            recovered = wait_whole()
            after = _fleet_tallies(router)
            failed = sum(1 for r in results if r["status"] != 200)
            truncated = sum(1 for r in results
                            if r["status"] == 200 and not r["done"])
            successes = len(results) - failed
            retries = after["retries"] - before["retries"]
            ratio = retries / max(1, successes)
            cap = (router.retry_budget.burst
                   + router.retry_budget.ratio * successes)
            evicted = after["evicts"] - before["evicts"]
            rejoined = after["rejoins"] - before["rejoins"]
            ok = bool(failed == 0 and truncated == 0
                      and retries <= cap and recovered
                      and (mode != "blackhole"
                           or (evicted >= 1 and rejoined >= 1)))
            print(json.dumps({"cell": name, "mode": "fleet",
                              "ok": ok, "failed_requests": failed,
                              "truncated_streams": truncated,
                              "retry_ratio": round(ratio, 4),
                              "retries": retries,
                              "evicted": evicted, "rejoined": rejoined,
                              "recovered": recovered}))
            oks.append(ok)
    except Exception as e:    # a cell must never take the sweep down
        print(json.dumps({"cell": "fleet_grid", "mode": "fleet",
                          "ok": False,
                          "error": f"{type(e).__name__}: {e}"}))
        oks.append(False)
    finally:
        router.stop()
        proxy.stop()
        _terminate(proc_a)
        _terminate(proc_b)
    return oks


def run_kvxfer_grid():
    """The fleet KV-transfer fault matrix (serve/kvxfer.py): a prefill
    replica behind a NetChaosProxy feeding a decode replica through a
    kv_transfer router. Cells kvxfer:{corrupt,refuse,blackhole} each
    break the /kvblocks pull a different way — bit-rot on the blob
    (crc must reject it), connect refusal, and a swallowed socket
    (client-side timeout). The acceptance property is the tentpole's
    NEVER-A-WRONG-ANSWER: every cell must count a fallback and
    re-prefill to a stream byte-identical to the warm source's own,
    with zero failed and zero truncated client streams."""
    import time

    from serve_bench import _spawn_replica, _terminate
    from paddle_tpu.engine.kvtier import prefix_digest
    from paddle_tpu.resilience.chaos import NetChaosProxy
    from paddle_tpu.serve.router import Router
    from paddle_tpu.serve.sse import collect_stream

    proc_a, base_a = _spawn_replica(extra=(
        "--phase", "prefill", "--host-tier-bytes", str(1 << 20)))
    # the decode replica is born with a 1-blob corruption budget
    # (PTPU_CHAOS_KVXFER_CORRUPT counts down per process): the FIRST
    # cell's pull eats it, the later wire-fault cells pull clean
    proc_b, base_b = _spawn_replica(
        extra=("--phase", "decode", "--host-tier-bytes", str(1 << 20)),
        env_extra={"PTPU_CHAOS_KVXFER_CORRUPT": "1"})
    proxy = NetChaosProxy(upstream_port=int(base_a.rsplit(":", 1)[1]))
    proxy.start()
    proxy_url = f"http://127.0.0.1:{proxy.port}"
    # manual scrape_now() only (interval parked at 30s): an armed wire
    # fault must not let a background scrape breaker-evict the prefill
    # member — the plan has to keep seeing it to attach the hint. The
    # router's stream-open patience must exceed the pull deadline
    # (kvxfer.DEFAULT_TIMEOUT_S = 5s): a black-holed transfer delays
    # TTFT by one timeout, it must not kill the stream.
    router = Router([proxy_url, base_b], prefix_len=8,
                    scrape_interval_s=30.0, scrape_timeout_s=0.5,
                    connect_timeout_s=8.0, kv_transfer=True).start()

    def scrape_until(pred, timeout_s=20.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            router.scrape_now()
            if pred():
                return True
            time.sleep(0.1)
        return False

    def specialized():
        ms = [_fleet_member(router, u) for u in (proxy_url, base_b)]
        return (all(m is not None and m.ready for m in ms)
                and ms[0].phase == "prefill" and ms[1].phase == "decode")

    def advertised(prompt):
        m = _fleet_member(router, proxy_url)
        return m is not None and any(
            d == prefix_digest(tuple(prompt[:n]))
            for (n, d) in m.prefixes if n <= len(prompt))

    def b_fallbacks():
        from serve_bench import _scrape
        return _scrape(base_b).get("ptpu_kvxfer_fallbacks_total", 0.0)

    grid = ["corrupt", "refuse", "blackhole"]
    oks = []
    try:
        ready = scrape_until(specialized)
        for idx, fault in enumerate(grid):
            name = f"kvxfer:{fault}"
            if not ready:
                print(json.dumps({"cell": name, "mode": "kvxfer",
                                  "ok": False,
                                  "error": "fleet never specialized"}))
                oks.append(False)
                continue
            # a FRESH prefix per cell: warm it onto the prefill
            # replica (prefill-classified), wait for the directory
            # advert, snapshot the local-warm baseline
            prompt = [(idx * 13 + j * 5 + 3) % 53
                      for j in range(12)] + [41, 42, 43, 44 + idx]
            warm = collect_stream(router.url,
                                  {"prompt": prompt,
                                   "max_new_tokens": 2}, timeout=60)
            adv = scrape_until(lambda: advertised(prompt))
            want = collect_stream(base_a, {"prompt": prompt,
                                           "max_new_tokens": 16},
                                  timeout=60)
            before = b_fallbacks()
            if fault != "corrupt":      # corrupt is armed in B's env
                proxy.arm(fault)
            try:
                got = collect_stream(router.url,
                                     {"prompt": prompt,
                                      "max_new_tokens": 16},
                                     timeout=60)
            finally:
                proxy.heal()
            fallbacks = b_fallbacks() - before
            results = [warm, want, got]
            failed = sum(1 for r in results if r["status"] != 200)
            truncated = sum(1 for r in results
                            if r["status"] == 200 and not r["done"])
            ok = bool(adv and failed == 0 and truncated == 0
                      and fallbacks >= 1
                      and got["tokens"] == want["tokens"])
            print(json.dumps({"cell": name, "mode": "kvxfer", "ok": ok,
                              "advertised": adv,
                              "fallbacks": fallbacks,
                              "failed_requests": failed,
                              "truncated_streams": truncated,
                              "tokens_identical":
                                  got["tokens"] == want["tokens"]}))
            oks.append(ok)
    except Exception as e:    # a cell must never take the sweep down
        print(json.dumps({"cell": "kvxfer_grid", "mode": "kvxfer",
                          "ok": False,
                          "error": f"{type(e).__name__}: {e}"}))
        oks.append(False)
    finally:
        router.stop()
        proxy.stop()
        _terminate(proc_a)
        _terminate(proc_b)
    return oks


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--inprocess-only", action="store_true")
    ap.add_argument("--no-fleet", action="store_true",
                    help="skip the serving-fleet wire-fault cells "
                         "(they boot replica subprocesses)")
    ap.add_argument("--tmp", default=None, help="scratch dir (default mkdtemp)")
    args = ap.parse_args()

    import tempfile
    tmp = args.tmp or tempfile.mkdtemp(prefix="chaos_sweep_")
    os.environ.setdefault("PTPU_RETRY_SCALE", "0.01")

    oks = []
    if not args.inprocess_only:
        oks += run_cluster_grid(tmp, args.steps)
    oks += run_inprocess_grid(tmp, args.steps)
    if not args.inprocess_only and not args.no_fleet:
        oks += run_fleet_grid()
        oks += run_kvxfer_grid()
    ok = all(o for o in oks if o is not None)
    print(json.dumps({"cell": "TOTAL", "ok": bool(ok),
                      "cells": len(oks), "failed": sum(o is False for o in oks)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
