"""A training loop: `MeshTrainer.train_step` on a one-device mesh, Adam,
a new seeded batch every step through `trainer.put_batch`, steps issued
back to back (as `examples/train_causal_lm.py` drives it, with the
`loss_fn` of `chip_smoke.py`: hidden states into the fused
cross-entropy).

Set-up builds one trainer and one state, drives it through its first
steps from the seed, and hands that same object to the window. Those
first steps are what `correct` compares with the reference: each step's
loss, the first gradient as the optimizer got it (Adam's first moment
after one step is (1 - beta1) times it), and the parameters' change
after the compared steps, each norm by the worst leaf.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmarks import reference, tracing, weights
from benchmarks.common import (build_model, check, check_tree, held_checks,
                               log)
from benchmarks.traffic import lm_batch

BETA1 = 0.9
# the numbers this runner compares with the reference, each needing an
# entry in the cell's limits file
HELD = ("loss_gap_max", "grad_diff_median", "grad_norm_gap_max",
        "change_norm_gap_max")


def flat(tree) -> dict:
    import jax
    return {"/".join(k.key for k in path): float(leaf)
            for path, leaf in jax.tree.flatten_with_path(
                jax.device_get(tree))[0]}


def leaf_gaps(program: dict, ref: dict, skip=()) -> dict:
    """For every leaf, the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    return {k: abs(program[k] - ref[k]) / max(ref[k], floor)
            for k in ref if k not in skip}


def compare(program: dict, ref: dict, grad_diff_norms: dict) -> dict:
    """The numbers `correct` holds, from the two sides' readings: each
    step's loss, the first gradient's norms and the norms of the
    parameters' change, by the worst leaf; and, by the median leaf, the
    norm of the two first gradients' difference
    (`grad_diff_norms`), which is of first order in rounding noise where
    a gap of norms is of second."""
    loss = [abs(a - b) / abs(b)
            for a, b in zip(program["losses"], ref["losses"])]
    grad = leaf_gaps(program["grad_norms"], ref["grad_norms"])
    # a leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: left out of the change by a rule on
    # the reference's gradient, not by name
    floor = 1e-3 * statistics.median(ref["grad_norms"].values())
    still = {k for k, g in ref["grad_norms"].items() if g < floor}
    change = leaf_gaps(program["change_norms"], ref["change_norms"], still)
    floor = statistics.median(ref["grad_norms"].values())
    diff = [d / max(ref["grad_norms"][k], floor)
            for k, d in grad_diff_norms.items()]
    return {"loss_gap_max": max(loss),
            "grad_diff_median": statistics.median(diff),
            "grad_norm_gap_max": max(grad.values()),
            "change_norm_gap_max": max(change.values()),
            "grad_leaf": max(grad, key=grad.get),
            "change_leaf": max(change, key=change.get),
            "left_out": sorted(still)}


def build_trainer(config: dict, device):
    import jax.numpy as jnp

    from paddle_tpu.ops.fused_ce import linear_cross_entropy
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import MeshConfig, MeshTrainer, make_mesh

    def loss_fn(module, variables, batch, rng, training):
        inp, tgt = batch
        hid, mut = module.apply(variables, inp, training=training,
                                rngs=rng, mutable=True, return_hidden=True)
        w, b = module.head_weights(variables)
        loss = jnp.mean(linear_cross_entropy(
            hid, w.astype(hid.dtype), tgt,
            None if b is None else b.astype(hid.dtype)))
        return (loss, {}), mut.get("state", {})

    model = build_model(config)
    trainer = MeshTrainer(
        model, Adam(config["train"]["learning_rate"], beta1=BETA1), loss_fn,
        make_mesh(MeshConfig(dp=1), devices=[device]), seed=0)
    return model, trainer


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    config, mix = ctx.config, ctx.traffic
    vocab, n_cmp = config["vocab_size"], mix["compared_steps"]
    ctx.phase("jax up")
    model, trainer = build_trainer(config, ctx.devices[0])

    def batch(step):
        return lm_batch(mix, vocab, ctx.seed, step)

    ts = trainer.init_state(jnp.asarray(batch(0)[0]))
    params = weights.make_params(config, ctx.seed)
    check_tree(model, params)
    ts = type(ts)(params=jax.device_put(params, trainer._state_shardings.params),
                  state=ts.state, opt_state=ts.opt_state, step=ts.step)
    del params
    ctx.phase("state ready")

    half = ctx.fault == "half_batch"     # tests: half of the batch left out
    frozen = ctx.fault == "state_unchanged"

    def step_once(ts, step):
        inp, tgt = batch(step)
        if half:
            inp, tgt = inp[: len(inp) // 2], tgt[: len(tgt) // 2]
        # the step donates its state: a frozen state steps on a copy
        new, out = trainer.train_step(
            jax.tree.map(jnp.copy, ts) if frozen else ts,
            trainer.put_batch((inp, tgt)), rng=jax.random.key(step))
        return (ts if frozen else new), out

    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    diff_norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))

    program = {"losses": []}
    for step in range(n_cmp):
        ts, out = step_once(ts, step)
        program["losses"].append(float(out["loss"]))
        if step == 0:
            # Adam's first moment after one step is (1 - beta1) times the
            # gradient it was given: kept on the host until the reference
            # runs, so that the window's memory is the program's alone
            m = ts.opt_state["slots"]["m"]
            program["grad_norms"] = {
                k: v / (1 - BETA1) for k, v in flat(norms(m)).items()}
            first_moment = jax.device_get(m)
    start = weights.make_params(config, ctx.seed)
    program["change_norms"] = flat(diff_norms(ts.params, start))
    del start
    log("first steps' losses:", program["losses"])
    ctx.phase("first steps done")

    # the window: the same trainer, the same state, the next steps
    ahead, losses, issued = mix["steps_ahead"], [], n_cmp
    tracer, lead = None, ctx.seconds - tracing.slice_seconds(ctx.seconds)
    t0 = ctx.open_window()
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        if ctx.trace and tracer is None and now >= lead:
            jax.block_until_ready(losses[-1:])
            tracer = ctx.tracer()
            tracer.start()
            traced_from = len(losses)
        ts, out = step_once(ts, issued)
        losses.append(out["loss"])
        issued += 1
        if len(losses) > ahead:      # at most `ahead` steps in flight
            jax.block_until_ready(losses[-1 - ahead])
    jax.block_until_ready(losses[-1])
    t1 = time.perf_counter()
    if tracer:
        tracer.stop()
    steps = len(losses)
    peak = ctx.memory_peak_bytes()
    compiles = trainer._train_step._cache_size()
    has_kernel = ctx.toy or "tpu_custom_call" in trainer._train_step.lower(
        *jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                      (ts, trainer.put_batch(batch(0)), jax.random.key(0)))
    ).as_text()
    values = np.asarray(jax.device_get(losses), np.float64)
    bad = int((~np.isfinite(values)).sum())
    tokens = steps * mix["batch"] * mix["seq"]
    log(f"window {t1 - t0:.3f}s: {steps} steps, loss {values[0]:.4f} -> "
        f"{values[-1]:.4f}")
    observed = {"steps": steps, "window_s": t1 - t0, "busy_s": None,
                "trace_window_s": None, "trace": None}
    if tracer:
        observed.update(tracer.reduce())
        observed["traced_steps"] = steps - traced_from
    del ts, trainer, model, losses
    gc.collect()

    # the reference follows the first steps, once the program's state is freed
    ref_batches = [tuple(jnp.asarray(x) for x in batch(s))
                   for s in range(n_cmp)]
    lr = config["train"]["learning_rate"]
    t_ref = time.perf_counter()
    program_grad = reference.stack_layers(
        jax.tree.map(lambda x: x / (1 - BETA1), first_moment),
        config["n_layer"])
    del first_moment
    ref = reference.train_reference(
        weights.make_params(config, ctx.seed), config, ref_batches, lr,
        grads_like=program_grad, return_grads=bool(ctx.control))
    del program_grad
    got = compare(program, ref, ref["grad_diff_norms"])
    log(f"reference followed {n_cmp} steps in "
        f"{time.perf_counter() - t_ref:.1f} s")
    log("reference losses:", ref["losses"])
    log("compared:", {k: v for k, v in got.items() if k != "left_out"},
        f"left out of the change: {len(got['left_out'])} leaves, "
        f"e.g. {got['left_out'][:2]}")
    if ctx.control:
        # the reference in the precision below ("fp8"), or with half of
        # the batch left out ("half_batch"), stands in the program's
        # place, before the same limits
        stand_in = ({"rows_used": mix["batch"] // 2}
                    if ctx.control == "half_batch"
                    else {"precision": ctx.control})
        low = reference.train_reference(
            weights.make_params(config, ctx.seed), config, ref_batches, lr,
            grads_like=ref["grads0"], **stand_in)
        log("the program read:", {k: got[k] for k in HELD})
        got = compare(low, ref, low["grad_diff_norms"])
        log(f"control {ctx.control}:",
            {k: v for k, v in got.items() if k != "left_out"}, low["losses"])
    checks = held_checks(ctx.limits, {name: got[name] for name in HELD})
    checks += [
        check("nonfinite_losses", bad, 0),
        check("train_compiles", compiles, 1, ok=compiles == 1),
        check("kernel_in_step", int(has_kernel), 1, ok=has_kernel),
    ]
    return {"attempted": steps, "failed": bad,
            "end_to_end": {"train_tok_s": tokens / (t1 - t0)},
            "observed": observed, "memory_peak_bytes": peak,
            "checks": checks}
