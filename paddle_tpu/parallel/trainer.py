"""MeshTrainer: compiled SPMD training over a device mesh.

Capability-equivalent of the reference multi-device engine in one object:
- ParallelExecutor (framework/parallel_executor.cc): per-device execution +
  per-gradient collectives → ONE pjit'd step function; the SPMD partitioner
  inserts all_reduce/reduce_scatter/all_gather from shardings (replacing
  details/multi_devices_graph_pass.cc + op handles).
- BuildStrategy reduce modes (build_strategy.h:55): ALL_REDUCE = replicated
  params + psum'd grads; REDUCE = fsdp-sharded params/grads/opt-state
  (ZeRO; the modern form of the reference's param-sharded update).
- BCastParamsToDevices (parallel_executor.cc:73): `init_state` materialises
  parameters *already sharded* via jit out_shardings — no host round-trip.
- multi_batch_merge_pass (ir/multi_batch_merge_pass.h:29): gradient
  accumulation by `lax.scan` over microbatches inside the step.
- ScaleLossGradOpHandle (1/N scaling): global-mean loss under pjit gives the
  same semantics (GradientScaleStrategy.COEFF_NUM_DEVICE).

Works identically on 1 device, 8 virtual CPU devices (tests), or a pod —
the mesh is the only thing that changes.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.executor import TrainState, _stamp_step, check_nan_inf
from paddle_tpu.profiler.profiler import RecordEvent
from paddle_tpu.core.module import Module, PARAMS, STATE
from paddle_tpu.optim.optimizer import Optimizer
from paddle_tpu.parallel.sharding import ShardingRules, fsdp_rules
from paddle_tpu.parallel.strategy import DistStrategy, ReduceStrategy
from paddle_tpu.resilience.errors import BadStepBudgetExceeded
from paddle_tpu.utils.flags import FLAGS
from paddle_tpu.utils.log import resilience_event

Pytree = Any


class MeshTrainer:
    """SPMD trainer over `mesh` with declarative sharding rules.

    loss_fn has the same contract as core.executor.Trainer:
    loss_fn(module, variables, batch, rng, training) -> ((loss, aux), state').
    """

    def __init__(self, module: Module, optimizer: Optimizer,
                 loss_fn: Callable, mesh: Mesh,
                 strategy: Optional[DistStrategy] = None,
                 rules: Optional[ShardingRules] = None, seed: int = 0):
        self.module = module
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.strategy = strategy or DistStrategy()
        if rules is None:
            rules = (fsdp_rules()
                     if self.strategy.reduce_strategy is ReduceStrategy.REDUCE
                     else ShardingRules())
        self.rules = rules
        self.seed = seed
        self._train_step = None
        self._eval_step = None
        self._state_shardings = None
        self._consecutive_bad = 0  # bad-step guard budget tracking
        # training telemetry families (None until enable_metrics())
        self._m_phase = None
        self._m_step = None
        self._g_compiles = None
        self._c_steps = None

    # -- telemetry --------------------------------------------------------
    def enable_metrics(self, registry=None) -> None:
        """Register the step-phase telemetry families and start timing.

        Instrumented steps host-sync once per step (block_until_ready on
        the fetches) so the `wait` phase is the real device time rather
        than async-dispatch noise — guard mode pays that sync anyway for
        the bad-step decision, and a scrapeable step clock is the point
        of turning this on. Leave metrics off to keep fully async
        dispatch.
        """
        from paddle_tpu.obs.metrics import default_registry
        reg = registry if registry is not None else default_registry()
        self._m_phase = reg.histogram(
            "ptpu_train_phase_ms",
            "Host wall time per training step phase",
            labelnames=("phase",))
        self._m_step = reg.histogram(
            "ptpu_train_step_ms",
            "Host wall time of one train_step call, dispatch to sync")
        self._g_compiles = reg.gauge(
            "ptpu_train_compiles",
            "Compiled executables in the train-step jit cache")
        self._c_steps = reg.counter(
            "ptpu_train_steps_total", "Completed train_step calls")

    # -- sharding helpers -------------------------------------------------
    def batch_sharding(self, leaf=None) -> NamedSharding:
        """Leading-dim batch sharding over the configured batch axes."""
        axes = tuple(a for a in self.strategy.batch_axes
                     if a in self.mesh.shape)
        return NamedSharding(self.mesh, P(axes if axes else None))

    def _batch_shardings(self, batch) -> Pytree:
        def per_leaf(x):
            if getattr(x, "ndim", 0) == 0:
                return NamedSharding(self.mesh, P())
            return self.batch_sharding()
        return jax.tree.map(per_leaf, batch)

    def state_shardings(self, abstract_state: TrainState) -> TrainState:
        """Shardings for every TrainState leaf from the rule table.

        Optimizer slot trees mirror the param tree, so param-path rules
        match them too (their tree paths contain the param path) — opt
        state automatically inherits param sharding, which is what makes
        REDUCE mode a true ZeRO: params, grads AND moments sharded.
        """
        return self.rules.tree_shardings(self.mesh, abstract_state)

    # -- state ------------------------------------------------------------
    def init_state(self, *example_inputs,
                   rng: Optional[jax.Array] = None) -> TrainState:
        if rng is None:
            rng = jax.random.key(self.seed)

        def init_fn(rng, *inputs):
            variables = self.module.init(rng, *inputs)
            params = variables.get(PARAMS, {})
            return TrainState(
                params=params,
                state=variables.get(STATE, {}),
                opt_state=self.optimizer.init(params),
                step=jnp.zeros((), jnp.int32))

        abstract = jax.eval_shape(init_fn, rng, *example_inputs)
        shardings = self.state_shardings(abstract)
        self._state_shardings = shardings
        with self.mesh:
            return _stamp_step(jax.jit(init_fn, out_shardings=shardings)(
                rng, *example_inputs), 0)

    # -- step construction ------------------------------------------------
    def _loss_and_grads(self, ts: TrainState, batch, rng):
        module, loss_fn = self.module, self.loss_fn
        raw_loss_fn = loss_fn
        if self.strategy.remat:
            # ≈ memory_optimize: recompute activations in backward
            raw_loss_fn = jax.checkpoint(
                loss_fn, static_argnums=(0, 4), policy=None)

        scale = self.strategy.loss_scale

        def loss_of(params):
            variables = {PARAMS: params, STATE: ts.state}
            (loss, aux), new_state = raw_loss_fn(
                module, variables, batch, rng, True)
            scaled = loss * scale if scale else loss
            return scaled, (loss, aux, new_state)

        grad_fn = jax.value_and_grad(loss_of, has_aux=True)
        (_, (loss, aux, new_state)), grads = grad_fn(ts.params)
        if scale:
            grads = jax.tree.map(lambda g: g / scale, grads)
        return loss, aux, new_state, grads

    def _build_train_step(self):
        accum = self.strategy.gradient_accumulation_steps
        optimizer = self.optimizer
        seed = self.seed
        guard = self.strategy.bad_step_budget is not None

        def step_fn(ts: TrainState, batch, rng):
            if rng is None:
                # default rng stream from the device-resident step: no host
                # sync, reproducible across rollback/restore (see
                # core.executor.Trainer._build_train_step)
                rng = jax.random.fold_in(jax.random.key(seed ^ 0x5EED),
                                         ts.step)
            if accum <= 1:
                loss, aux, new_state, grads = self._loss_and_grads(
                    ts, batch, rng)
            else:
                # microbatch scan (multi_batch_merge capability): leading
                # batch dim reshaped to [accum, micro, ...]
                def split(x):
                    if getattr(x, "ndim", 0) == 0:
                        return x
                    b = x.shape[0]
                    return x.reshape((accum, b // accum) + x.shape[1:])
                micro = jax.tree.map(split, batch)

                def body(carry, mb_and_rng):
                    mb, r = mb_and_rng
                    loss, aux, new_state, grads = self._loss_and_grads(
                        carry["ts"], mb, r)
                    acc = jax.tree.map(jnp.add, carry["grads"], grads)
                    new_ts = TrainState(carry["ts"].params, new_state,
                                        carry["ts"].opt_state,
                                        carry["ts"].step)
                    return ({"ts": new_ts, "grads": acc}, (loss, aux))

                zero_grads = jax.tree.map(jnp.zeros_like, ts.params)
                rngs = jax.random.split(rng, accum)
                carry, (losses, auxes) = jax.lax.scan(
                    body, {"ts": ts, "grads": zero_grads}, (micro, rngs))
                grads = jax.tree.map(lambda g: g / accum, carry["grads"])
                new_state = carry["ts"].state
                loss = jnp.mean(losses)
                aux = jax.tree.map(jnp.mean, auxes)

            new_params, new_opt = optimizer.apply(
                ts.params, grads, ts.opt_state)
            new_ts = TrainState(new_params, new_state, new_opt, ts.step + 1)
            if guard:
                # Bad-step guard: one fused isfinite reduction over loss
                # + grads, then select-old on EVERY leaf (params, BN
                # state, opt moments AND step) — a non-finite step is a
                # true no-op, not a zero-grad Adam update (which would
                # still decay moments and advance bias correction). The
                # select runs in-graph, so donated input buffers are
                # never resurrected on the host side.
                finite = jnp.isfinite(loss)
                for g in jax.tree.leaves(grads):
                    finite &= jnp.isfinite(g).all()
                new_ts = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_ts, ts)
                return new_ts, {"loss": loss, "bad_step": ~finite, **aux}
            return new_ts, {"loss": loss, **aux}

        donate = (0,) if self.strategy.donate_state else ()
        return jax.jit(
            step_fn,
            out_shardings=(self._state_shardings, None),
            donate_argnums=donate)

    def _build_eval_step(self):
        module, loss_fn = self.module, self.loss_fn

        def step_fn(ts: TrainState, batch):
            variables = {PARAMS: ts.params, STATE: ts.state}
            (loss, aux), _ = loss_fn(module, variables, batch, None, False)
            return {"loss": loss, **aux}
        # in_shardings pins the state to its training sharding so an
        # fsdp-sharded TrainState is NOT silently gathered for eval;
        # fetches are replicated scalars.
        return jax.jit(step_fn,
                       in_shardings=(self._state_shardings, None))

    # -- public API -------------------------------------------------------
    def put_batch(self, batch) -> Pytree:
        """Device-put a host batch with batch-axis sharding (the feed path;
        ≈ DataFeeder splitting a batch across places)."""
        t0 = time.perf_counter()
        out = jax.tree.map(
            lambda x, s: jax.device_put(x, s), batch,
            self._batch_shardings(batch))
        if self._m_phase is not None:
            # block so the observed h2d phase is the real transfer, not
            # the async enqueue (the step blocks on the batch regardless)
            jax.block_until_ready(out)
            self._m_phase.labels(phase="h2d").observe(
                (time.perf_counter() - t0) * 1e3)
        return out

    def train_step(self, ts: TrainState, batch, rng=None):
        if self._state_shardings is None:
            raise RuntimeError("call init_state() first")
        if self._train_step is None:
            self._train_step = self._build_train_step()
        t0 = time.perf_counter()
        with RecordEvent("MeshTrainer.train_step"), self.mesh:
            new_ts, fetches = self._train_step(ts, batch, rng)
        if self._m_phase is not None:
            t1 = time.perf_counter()
            self._m_phase.labels(phase="dispatch").observe((t1 - t0) * 1e3)
            jax.block_until_ready(fetches)
            t2 = time.perf_counter()
            self._m_phase.labels(phase="wait").observe((t2 - t1) * 1e3)
            self._m_step.observe((t2 - t0) * 1e3)
            self._g_compiles.set(self._train_step._cache_size())
            self._c_steps.inc()
        hint = getattr(ts, "_step_hint", None)
        budget = self.strategy.bad_step_budget
        if budget is not None:
            # guard mode accepts one host sync per step: the skip/raise
            # decision is host control flow by design (rollback leaves
            # the compiled step untouched)
            bad = bool(jax.device_get(fetches["bad_step"]))
            fetches["bad_step"] = bad
            if bad:
                self._consecutive_bad += 1
                resilience_event(
                    "bad_step_skip", step=hint if hint is not None else -1,
                    consecutive=self._consecutive_bad, budget=budget)
                if self._consecutive_bad >= budget:
                    err = BadStepBudgetExceeded(
                        budget, hint if hint is not None else -1)
                    # the returned state is the last GOOD one (updates
                    # were skipped in-graph); hand it to the rollback
                    # path as the restore target
                    err.state = new_ts
                    raise err
            else:
                self._consecutive_bad = 0
            if hint is not None:
                _stamp_step(new_ts, hint if bad else hint + 1)
        elif hint is not None:
            _stamp_step(new_ts, hint + 1)
        if FLAGS.get("check_nan_inf"):
            check_nan_inf(fetches, "train fetches")
        return new_ts, fetches

    def reset_bad_steps(self) -> None:
        """Zero the consecutive-bad-step counter (after a rollback)."""
        self._consecutive_bad = 0

    def eval_step(self, ts: TrainState, batch):
        if self._state_shardings is None:
            raise RuntimeError("call init_state() first")
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        with self.mesh:
            return self._eval_step(ts, batch)
