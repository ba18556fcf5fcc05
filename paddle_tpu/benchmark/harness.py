"""Benchmark harness: timed training windows with MFU accounting.

Capability-equivalent of the reference benchmark CLI
(/root/reference/benchmark/fluid/fluid_benchmark.py:139 train(), which
times passes over a model zoo and prints imgs/s) — extended with what a
TPU benchmark must report to be honest:

- a timed window >= `min_time` seconds (adaptive step count), fully
  synchronized with `jax.block_until_ready` at the window edges only, so
  the async dispatch pipeline stays filled inside the window;
- FLOPs per step taken from XLA's own cost analysis of the compiled
  executable (not a hand model), giving MFU = flops/sec vs the chip's
  published peak for the matmul dtype.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

# Published bf16 peak matmul throughput per chip, FLOP/s. Keyed by
# substring of jax.devices()[0].device_kind (lowercased).
PEAK_FLOPS_BF16 = {
    "v6e": 918e12,          # Trillium
    "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
    "v3": 123e12,           # per chip (2 cores)
    "v2": 46e12,
}


def device_peak_flops(dtype_bits: int = 16) -> Optional[float]:
    """Peak FLOP/s of device 0, or None if unknown (e.g. CPU)."""
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "").lower()
    for key, peak in PEAK_FLOPS_BF16.items():
        if key in kind:
            return peak if dtype_bits <= 16 else peak / 2
    return None


def chain_k(fn: Callable, k: int):
    """Jitted K-iteration chained step for run_timed's caller contract.

    `fn(carry, *args) -> array or tuple of arrays` runs K times inside
    ONE program (amortizing per-dispatch overhead), with a scalar
    carry derived from EVERY output threaded into the next iteration —
    touching all outputs so XLA cannot dead-code-eliminate any of them,
    scaled by 1e-30 rather than 0 because a mul-by-zero fold would sever
    the loop-carried dependence and let the body be eliminated silently.
    The returned jitted callable maps (carry, *args) -> carry; divide the
    measured step time by K.
    """
    def kstep(s, *args):
        def body(i, c):
            outs = fn(c, *args)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            carry = outs[0].ravel()[0]
            for o in outs[1:]:
                carry = carry + o.ravel()[0].astype(carry.dtype)
            return (carry * 1e-30).astype(s.dtype)
        return jax.lax.fori_loop(0, k, body, s)
    return jax.jit(kstep)


_SUSTAINED: Optional[float] = None


def sustained_matmul_flops(min_time: float = 1.5) -> Optional[float]:
    """Sustained single-chip bf16 matmul rate (FLOP/s), cached per
    process (first call's measurement wins).

    State-chained 8192x8192 matmul chains (step k+1 consumes step k's
    output — see the run_timed caller contract).
    Measured ~149 TFLOP/s on v5e = 76% of the published 197 peak, which
    calibrates what fraction of the datasheet a perfectly matmul-dense
    program can actually reach. Returns None off-TPU.
    """
    global _SUSTAINED
    if _SUSTAINED is not None:
        return _SUSTAINED
    if jax.devices()[0].platform != "tpu":
        return None
    import jax.numpy as jnp
    n, chain = 8192, 10
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.randn(n, n) * 0.01, jnp.bfloat16)
    b = jnp.asarray(rs.randn(n, n) * 0.01, jnp.bfloat16)
    g = jax.jit(lambda s, b: jax.lax.fori_loop(
        0, chain, lambda i, c: (c @ b).astype(jnp.bfloat16), s))
    sec, _, _ = run_timed(lambda s: (g(s, b),) * 2, a, min_time=min_time)
    _SUSTAINED = chain * 2 * n ** 3 / sec
    return _SUSTAINED


def compiled_flops(jitted, *args) -> Optional[float]:
    """FLOPs per invocation from the compiled executable's cost analysis."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = cost.get("flops")
        return float(f) if f else None
    except Exception:
        return None


@dataclasses.dataclass
class BenchResult:
    model: str
    unit: str                       # "imgs/s", "tokens/s", "samples/s"
    value: float                    # items per second
    ms_per_step: float
    steps: int
    batch_size: int
    flops_per_step: Optional[float]
    tflops_per_sec: Optional[float]
    mfu: Optional[float]            # fraction of chip peak
    device: str
    vs_baseline: Optional[float]

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        return {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in d.items()}


def _sync(out) -> None:
    """Force real device execution, not just dispatch.

    Pull one leaf back to the host — it transitively forces everything it
    depends on, on any backend.
    """
    leaves = [l for l in jax.tree_util.tree_leaves(out)
              if isinstance(l, jax.Array)]
    if leaves:
        np.asarray(jax.device_get(leaves[0]))


def run_timed(step_once: Callable[[Any], Tuple[Any, Any]], state,
              min_time: float = 2.0, warmup: int = 3
              ) -> Tuple[float, int, Any]:
    """Time `state, out = step_once(state)` by two-window subtraction.

    The host→device→host sync at a window edge has a fixed cost that a
    single window charges to the steps. Instead time a small window
    T_A (N_A steps + sync) and a large one T_B (N_B steps + sync):
    per_step = (T_B - T_A) / (N_B - N_A) cancels the fixed cost exactly.
    N_B grows (doubling) until the subtracted window covers >= min_time.

    CALLER CONTRACT: step k+1's computation must CONSUME step k's output
    (thread it through `state`), so that independent dispatches cannot
    overlap or be elided. Training steps chain their TrainState naturally; for inference/kernel timing, fold a scalar from
    the previous output back into the input (see run_infer).

    Returns (seconds_per_step, steps_timed_total, final_state).
    """
    out = None
    for _ in range(max(warmup, 1)):
        state, out = step_once(state)
    _sync(out)

    n_a = 8
    t0 = time.perf_counter()
    for _ in range(n_a):
        state, out = step_once(state)
    _sync(out)
    t_a = time.perf_counter() - t0

    # upper-bound estimate of per-step time picks the first N_B try
    est = t_a / n_a
    n_b = max(4 * n_a, int(min_time / max(est, 1e-9)))
    total_steps = n_a
    while True:
        n_b = min(n_b, 1_000_000)
        t0 = time.perf_counter()
        for _ in range(n_b):
            state, out = step_once(state)
        _sync(out)
        t_b = time.perf_counter() - t0
        total_steps += n_b
        if t_b - t_a >= min_time or n_b >= 1_000_000:
            break
        n_b *= 4
    per_step = (t_b - t_a) / (n_b - n_a)
    return max(per_step, 1e-12), total_steps, state


def bench_trainer(name: str, trainer, ts, batch, items_per_step: int,
                  unit: str, batch_size: int, min_time: float = 2.0,
                  baseline: Optional[float] = None,
                  baseline_is_ms: bool = False,
                  extra_flops: float = 0.0) -> BenchResult:
    """Benchmark one (trainer, state, batch): the common wrapper used by
    every model spec in models.py. `trainer` is core.executor.Trainer or
    parallel.trainer.MeshTrainer (same train_step contract).

    extra_flops: analytic correction added to the compiled-executable
    count for FLOPs XLA's cost analysis structurally misses — it counts a
    scan/fori_loop body ONCE regardless of trip count (see PERF_NOTES
    measurement-integrity notes), so steps that loop over matmul chunks
    (ops/fused_ce.py) pass the known per-iteration matmul FLOPs x the
    uncounted iterations here. Keep corrections analytic and
    matmul-only — never estimates of fused elementwise work."""
    rng = jax.random.key(0)

    def step_once(state):
        return trainer.train_step(state, batch, rng=rng)

    sec_per_step, steps, _ = run_timed(step_once, ts, min_time=min_time)

    flops = None
    jitted = getattr(trainer, "_train_step", None)
    if jitted is not None:
        flops = compiled_flops(jitted, ts, batch, rng)
        if flops:
            flops += extra_flops

    tflops = (flops / sec_per_step / 1e12) if flops else None
    peak = device_peak_flops()
    mfu = (flops / sec_per_step / peak) if (flops and peak) else None
    value = items_per_step / sec_per_step
    vs = None
    if baseline:
        vs = (baseline / (sec_per_step * 1e3) if baseline_is_ms
              else value / baseline)
    return BenchResult(
        model=name, unit=unit, value=value,
        ms_per_step=sec_per_step * 1e3, steps=steps,
        batch_size=batch_size,
        flops_per_step=flops, tflops_per_sec=tflops, mfu=mfu,
        device=getattr(jax.devices()[0], "device_kind",
                       jax.devices()[0].platform),
        vs_baseline=vs)
