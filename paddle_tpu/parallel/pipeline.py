"""Pipeline parallelism: GPipe-style microbatch schedule over the "pp" axis.

The reference has no pipeline parallelism (SURVEY §2.6 "not present");
this is a TPU-native extension completing the advertised mesh axes
(parallel/mesh.py "pp"). Design follows the SPMD pipeline idiom:

- The model is S_total identical-shape stages. Per-stage parameters are
  stacked on a leading dim sharded over the pp axis (size S), so each
  device holds v = S_total/S consecutive stages ("virtual stages",
  chained inside one tick) — models deeper than the axis pipeline
  without restriction.
- Microbatches stream through a lax.scan over M + S - 1 ticks. At tick t,
  stage s computes microbatch (t - s); activations hop one stage per tick
  via a single `ppermute` over ICI. Bubble fraction is the standard
  (S - 1) / (M + S - 1).
- The microbatch buffer is SHARDED over pp in a strided layout
  (microbatch t lives on device t mod S), so resident input memory is
  O(batch/S) per device, not O(batch). Each tick, the owner of the
  needed microbatch injects it with one masked psum (activation-sized,
  the same order as the ppermute hop) — SPMD-uniform, static collectives.
- `pipeline_stream` additionally folds the loss INTO the scan: the last
  stage consumes each finished microbatch (head + loss) the tick it
  completes, so no O(batch) output buffer ever materialises — this is
  the path `PipelinedLM` trains through under MeshTrainer.
- Backward needs no hand-written schedule: `ppermute`/`psum` are linear,
  their transposes are the reverse rotation/broadcast, so jax.grad
  through the scan yields the mirrored backward pipeline automatically —
  the compiler owns the schedule, exactly the XLA-first stance of this
  framework.

All devices run the same program on identically-shaped data (masked when
idle) — SPMD-uniform, no per-stage programs to compile.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from paddle_tpu.core.module import Context, Module, PARAMS

Pytree = Any


def stack_stage_params(per_stage: Sequence[Pytree]) -> Pytree:
    """Stack a list of per-stage param pytrees on a new leading axis
    (shard it over "pp" via P("pp", ...))."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def _check_stages(stacked_params: Pytree, s: int, axis: str) -> int:
    """The stage stack must divide evenly onto the mesh axis: each device
    holds v = S_total/S_mesh consecutive stages ("virtual stages",
    chained per tick), so models deeper than the axis still pipeline.
    Returns v. A non-divisible stack would silently drop stages."""
    leaves = jax.tree.leaves(stacked_params)
    if leaves and leaves[0].shape[0] % s:
        raise ValueError(
            f"stacked stage dim {leaves[0].shape[0]} must be a multiple "
            f"of mesh '{axis}' size {s} (v consecutive stages per device)")
    return leaves[0].shape[0] // s if leaves else 1


def _chain_stages(stage_fn: Callable, params_v: Pytree, x: jax.Array):
    """Apply this device's v stacked stage slices in order (scan over the
    local virtual-stage dim — one tick's compute)."""
    def body(h, sp):
        out = stage_fn(sp, h)
        if isinstance(out, tuple):
            return out[0], out[1].astype(jnp.float32)
        return out, jnp.zeros((), jnp.float32)
    y, auxes = lax.scan(body, x, params_v)
    return y, jnp.sum(auxes)


def _microbatch(x: jax.Array, m: int) -> jax.Array:
    """[B, ...] -> [m, B/m, ...] with INTERLEAVED assignment (row b goes
    to microbatch b mod m, position b // m): a batch dp-sharded
    contiguously on dim 0 then maps to a cleanly dp-sharded
    microbatch-width dim — the naive contiguous split (microbatch
    b // (B/m)) makes XLA "involuntarily rematerialize"
    (replicate-then-repartition) the whole batch at the pjit/shard_map
    boundary. Loss math is permutation-invariant over the batch."""
    b = x.shape[0]
    return x.reshape((b // m, m) + x.shape[1:]).swapaxes(0, 1)


def _strided(xs: jax.Array, s: int) -> Tuple[jax.Array, int]:
    """[M, ...] -> ([ceil(M/s), s, ...], M): microbatch t at [t//s, t%s].

    Zero-pads M up to a multiple of s; the tick masks (`t < m`) keep the
    padding out of the math."""
    m = xs.shape[0]
    mp = -(-m // s) * s
    if mp != m:
        xs = jnp.concatenate(
            [xs, jnp.zeros((mp - m,) + xs.shape[1:], xs.dtype)])
    return xs.reshape((mp // s, s) + xs.shape[1:]), m


def _deliver(masked: jax.Array, axis: str) -> jax.Array:
    """psum that hands the owner's microbatch (everyone else passes
    zeros) to all stages, with an optimization barrier in front of it.

    The barrier is for the BACKWARD pass, where it lands between the
    transposed psum and the per-device owner mask that follows it. The
    TPU compiler otherwise hoists that psum out of the tick loop
    (while-loop all-reduce code motion), across the mask, which depends
    on the device: every device then accumulates stage 0's cotangents
    for ITS OWN ticks only and the sum after the loop hands all of them
    the same buffer — input gradients wrong for every microbatch stage 0
    does not own, with the loss exact. Seen on four v5e chips (PR 21);
    the CPU backend does not run that pass."""
    return lax.psum(lax.optimization_barrier(masked), axis)


def pipeline_apply(stage_fn: Callable[[Pytree, jax.Array], jax.Array],
                   stacked_params: Pytree, microbatches: jax.Array,
                   mesh: Mesh, axis: str = "pp"):
    """Run the stacked pipeline stages over M microbatches.

    stage_fn(params, x) -> y with y.shape == x.shape (equal-width stages —
    the usual transformer-block case). stacked_params: leading dim any
    MULTIPLE of the `axis` size (each device chains its v = S_total/S
    consecutive virtual stages per tick). microbatches: [M, mb, ...];
    resident per-device input is the strided O(M/S) shard. Returns
    [M, mb, ...] outputs (replicated — use `pipeline_stream` to avoid
    materialising them), differentiable end to end.
    """
    s = mesh.shape[axis]
    _check_stages(stacked_params, s, axis)
    if microbatches.shape[0] < 1:
        raise ValueError("need at least one microbatch")
    xs_str, m = _strided(microbatches, s)
    total = m + s - 1
    fwd_perm = [(i, (i + 1) % s) for i in range(s)]

    def local(params, xs_l):
        # params: [v, ...] this device's stage slices;
        # xs_l: [ceil(M/S), 1, mb, ...]
        xs_l = jax.tree.map(lambda x: x[:, 0], xs_l)
        stage = lax.axis_index(axis)
        zero = jnp.zeros_like(xs_l[0])

        def tick(carry, t):
            buf = carry                       # activation arriving this tick
            # the owner (t mod S) of microbatch t injects it; one
            # activation-sized psum delivers it to stage 0
            cand = xs_l[jnp.minimum(t, m - 1) // s]
            x_in = _deliver(
                jnp.where((stage == t % s) & (t < m), cand, zero), axis)
            x_t = jnp.where(stage == 0, x_in, buf)
            y, _ = _chain_stages(stage_fn, params, x_t)
            # the last stage's result for microbatch (t - (s-1)) is ready
            out_t = jnp.where(stage == s - 1, y, jnp.zeros_like(y))
            y_next = lax.ppermute(y, axis, fwd_perm)
            return y_next, out_t

        _, outs = lax.scan(tick, zero, jnp.arange(total))
        # outs[t] is valid on the last stage for t in [s-1, total);
        # every other stage contributed zeros -> one psum replicates the
        # last stage's outputs everywhere.
        outs = lax.psum(outs[s - 1:], axis)
        return outs

    in_specs = (P(axis), P(None, axis))   # params by stage; xs strided
    out_specs = P()
    return shard_map(local, mesh=mesh,
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)(stacked_params, xs_str)


def pipeline_stream(stage_fn: Callable[[Pytree, jax.Array], jax.Array],
                    consume_fn: Callable[[Pytree, jax.Array, jax.Array],
                                         jax.Array],
                    mesh: Mesh, axis: str = "pp",
                    batch_axes: Sequence[str] = (),
                    param_specs: Optional[Pytree] = None,
                    seq_axes: Sequence[str] = ()):
    """Build fn(stacked_params, aux_params, xs, ys) -> mean scalar loss.

    The full streaming pipeline: inputs arrive via the strided conveyor,
    and the tick a microbatch leaves the last stage, that stage runs
    `consume_fn(aux_params, last_stage_out, ys[j]) -> scalar` (e.g. LM
    head + cross-entropy) and accumulates — per-device live data never
    exceeds the O(batch/S) input shard plus one activation. `batch_axes`
    lists mesh axes the microbatch dim is data-parallel over (the loss is
    pmean'd across them; grads flow through the psum transposes).

    `param_specs` (a PartitionSpec pytree over stacked_params, default
    P(axis) everywhere) lets stage weights shard over FURTHER mesh axes —
    tensor parallelism inside each stage: the stage_fn then sees
    tp-sliced weight shards and is responsible for its own tp psums
    (see `lm_block(tp_axis=...)`). Activations stay replicated across
    tp, so the conveyor/loss plumbing is unchanged.

    stage_fn may return `(y, stage_aux_scalar)` instead of `y`: the
    scalar (e.g. an MoE load-balancing loss) is accumulated over every
    VALID (stage, microbatch) pair — bubble ticks masked out — averaged,
    and ADDED to the consume_fn loss.

    `seq_axes` lists mesh axes the SEQUENCE dim (xs/ys dim 3) is sharded
    over: the conveyor then streams local sequence shards, the stage_fn
    is responsible for cross-shard attention (ring over sp), and the
    loss is pmean'd across the shards.
    """
    baxes = tuple(batch_axes)
    saxes = tuple(seq_axes)

    def fn(stacked_params, aux_params, xs, ys):
        s = mesh.shape[axis]
        v = _check_stages(stacked_params, s, axis)
        xs_str, m = _strided(xs, s)
        ys_str, _ = _strided(ys, s)
        total = m + s - 1
        fwd_perm = [(i, (i + 1) % s) for i in range(s)]

        def local(params, aux, xs_l, ys_l):
            xs_l = xs_l[:, 0]
            ys_l = ys_l[:, 0]
            stage = lax.axis_index(axis)
            zero = jnp.zeros_like(xs_l[0])

            def tick(carry, t):
                buf, acc, sacc = carry
                cand = xs_l[jnp.minimum(t, m - 1) // s]
                x_in = _deliver(
                    jnp.where((stage == t % s) & (t < m), cand, zero), axis)
                x_t = jnp.where(stage == 0, x_in, buf)
                # this device's v virtual stages, chained; their summed
                # stage-aux counts only while a real microbatch is here
                # (device s holds one at tick t iff s <= t < s + m)
                y, stage_aux = _chain_stages(stage_fn, params, x_t)
                valid = (stage <= t) & (t < stage + m)
                sacc = sacc + jnp.where(valid, stage_aux, 0.0)
                # microbatch j finished on the last stage this tick; its
                # targets stream in from their strided owner the same way
                j = t - (s - 1)
                jc = jnp.clip(j, 0, m - 1)
                t_cand = ys_l[jc // s]
                tgt = _deliver(
                    jnp.where((stage == jc % s) & (j >= 0), t_cand,
                              jnp.zeros_like(t_cand)), axis)
                li = consume_fn(aux, y, tgt)
                acc = acc + jnp.where((stage == s - 1) & (j >= 0),
                                      li.astype(jnp.float32), 0.0)
                return (lax.ppermute(y, axis, fwd_perm), acc, sacc), None

            (_, acc, sacc), _ = lax.scan(
                tick, (zero, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), jnp.arange(total))
            loss = lax.psum(acc, axis) / m     # replicate across pp
            # per-stage aux: mean over the s*v*m valid (global stage,
            # microbatch) pairs (each device's sacc sums its v stages)
            loss = loss + lax.psum(sacc, axis) / (s * v * m)
            if baxes or saxes:
                # data-parallel mean; sequence shards contribute their
                # local-token means, so the sp pmean gives the global one
                loss = lax.pmean(loss, baxes + saxes)
            return loss

        def data_spec(arr):
            # trimmed to rank: low-rank targets (e.g. [M', S, mb] scalar
            # labels) simply have no sequence dim to shard
            entries = (None, axis, baxes if baxes else None,
                       saxes if saxes else None)
            return P(*entries[:arr.ndim])

        in_specs = (param_specs if param_specs is not None else P(axis),
                    P(), data_spec(xs_str), data_spec(ys_str))
        return shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(
                                 stacked_params, aux_params, xs_str, ys_str)
    return fn


def pipeline_stream_1f1b(stage_fn: Callable,
                         consume_fn: Callable,
                         mesh: Mesh, axis: str = "pp",
                         batch_axes: Sequence[str] = (),
                         param_specs: Optional[Pytree] = None):
    """1F1B-scheduled variant of `pipeline_stream`: same contract
    (fn(stacked_params, aux_params, xs, ys) -> mean scalar loss, same
    value), different activation-memory shape.

    GPipe here is jax.grad THROUGH the conveyor scan: autodiff stores
    every tick's stage residuals, so per-device activation liveness
    grows O(M) with the microbatch count — the reason 1F1B exists at
    scale. This schedule interleaves the backward into the SAME scan:

    - forward: stage s runs microbatch j at tick t = j + s (the conveyor
      unchanged — strided injection, ppermute hops);
    - the last stage consumes microbatch j the tick it finishes
      (t = j + S - 1) and immediately seeds its cotangent (1F, then 1B —
      the classic last-stage alternation);
    - backward: stage s runs the VJP for microbatch j at tick
      t = j + 2(S-1) - s; cotangents hop stage s+1 -> s via the reverse
      ppermute; parameter grads accumulate in-carry.

    Each stage keeps only a ring stash of its in-flight microbatch
    INPUTS (depth 2S-1 — the widest span, at stage 0) and recomputes the
    stage forward inside its backward tick via jax.vjp (the remat
    convention: recompute is cheaper than liveness). Peak activation
    state is therefore O(S·act) per device, independent of M, at the
    cost of one extra stage-forward per backward tick and S-1 extra
    drain ticks (total M + 2(S-1) vs M + S - 1): memory, not bubble, is
    what 1F1B buys — measured numbers in PERF_NOTES.

    The whole combined scan runs inside a custom_vjp FORWARD rule that
    returns (loss, grads): the backward rule just scales the
    precomputed grads by the incoming cotangent, so jax.grad of this
    loss never differentiates through the scan (no residual stashing)
    and MeshTrainer's value_and_grad plugs in unchanged.

    Supports tp-sharded stage weights and stage-aux scalars (MoE load
    balance). This shard_map runs with check_vma=True — unlike the
    GPipe path, the backward here calls jax.vjp INSIDE the manual
    region, and only the vma (varying-manual-axes) machinery transposes
    the stage's tp psums exactly (with check_vma=False, psum transposes
    to psum and a replicated cotangent gets multiplied by the axis
    size — measured, not theoretical). `seq_axes` (ring/ulysses inside
    stages) is a GPipe-only feature for now.
    """
    baxes = tuple(batch_axes)
    ndp = 1
    for a in baxes:
        ndp *= mesh.shape[a]

    def _combined(stacked_params, aux_params, xs, ys):
        s = mesh.shape[axis]
        v = _check_stages(stacked_params, s, axis)
        xs_str, m = _strided(xs, s)
        ys_str, _ = _strided(ys, s)
        total = m + 2 * (s - 1)
        ring = max(2 * s - 1, 1)
        fwd_perm = [(i, (i + 1) % s) for i in range(s)]
        rev_perm = [(i, (i - 1) % s) for i in range(s)]

        def local(params, aux, xs_l, ys_l):
            xs_l = xs_l[:, 0]
            ys_l = ys_l[:, 0]
            stage = lax.axis_index(axis)
            zero = jnp.zeros_like(xs_l[0])

            # the stage-aux scalar's varying-axes type depends on the
            # stage_fn (a constant zero for plain blocks, data-derived
            # for MoE); multiply by a canonically-varying one so the
            # masked cotangent below always typechecks against it
            vone = lax.pcast(jnp.float32(1.0), (axis,) + baxes,
                             to="varying")

            def vup(x):
                have = getattr(jax.typeof(x), "vma", frozenset())
                need = tuple(a for a in (axis,) + baxes if a not in have)
                return lax.pcast(x, need, to="varying") if need else x

            # pcast params/aux UP to (pp,)+baxes-varying ONCE, before the
            # scan: the in-tick jax.vjp then returns LOCAL cotangents of
            # matching vma type for every leaf — including through
            # custom_vjp ops (fused CE), whose user-written bwd cannot
            # satisfy the vma typecheck against an invariant primal (the
            # driver's clean env enforces that check;
            # jax_disable_bwd_checks=True environments merely hid it).
            # Keeping cotangents local also avoids a per-tick psum of
            # head-sized grads; `_complete` below psums once, post-scan.
            params = jax.tree.map(vup, params)
            aux = jax.tree.map(vup, aux)

            def chain(p, x):
                y, aux_s = _chain_stages(stage_fn, p, x)
                return y, aux_s * vone

            def consume_grads(y, tgt, cot):
                li, cvjp = jax.vjp(
                    lambda a, yy: consume_fn(a, yy, tgt), aux, y)
                da_t, dy = cvjp(cot.astype(li.dtype))
                return li, da_t, dy

            # Probe ONE tick's cotangent computation before the scan to
            # get correctly-TYPED zero accumulators: under check_vma=True
            # the in-region jax.vjp auto-psums cotangents of invariant
            # inputs (they come back invariant AND complete), EXCEPT
            # through custom_vjp ops (e.g. the fused-CE head grad),
            # whose user-written bwd returns local varying values. The
            # per-leaf vma therefore depends on consume_fn/stage_fn
            # internals; the probe inherits it exactly, and `_complete`
            # below psums precisely the leaves that came back local.
            x0 = jnp.where(stage == 0, lax.psum(
                jnp.where(stage == 0, xs_l[0], zero), axis), zero)
            tgt0 = lax.psum(
                jnp.where(stage == 0, ys_l[0],
                          jnp.zeros_like(ys_l[0])), axis)
            # zero-valued, but with the body cotangents' exact vma type:
            # pp-varying (stage masks) + baxes-varying (pcast)
            cot0 = lax.pcast(jnp.where(stage == s - 1, 0.0, 0.0),
                             baxes, to="varying")
            y0, _ = chain(params, x0)
            _, da0, _ = consume_grads(y0, tgt0, cot0)
            _, chain_vjp0 = jax.vjp(chain, params, x0)
            dp0, _ = chain_vjp0((jnp.zeros_like(y0), cot0))
            zeros_typed = lambda tree: jax.tree.map(
                lambda g: g * jnp.zeros((), g.dtype), tree)

            def tick(carry, t):
                (fwd_buf, bwd_buf, stash, dp_acc, da_acc, dxs_acc,
                 acc, sacc) = carry

                # ---- forward conveyor (identical to pipeline_stream) --
                cand = xs_l[jnp.minimum(t, m - 1) // s]
                x_in = lax.psum(
                    jnp.where((stage == t % s) & (t < m), cand, zero),
                    axis)
                x_t = jnp.where(stage == 0, x_in, fwd_buf)
                j_f = t - stage
                fwd_valid = (stage <= t) & (t < stage + m)
                slot_f = jnp.clip(j_f, 0, m - 1) % ring
                stash = stash.at[slot_f].set(
                    jnp.where(fwd_valid, x_t, stash[slot_f]))
                y, stage_aux = chain(params, x_t)
                sacc = sacc + jnp.where(fwd_valid, stage_aux, 0.0)

                # ---- last stage: loss value + cotangent seed ----------
                j = t - (s - 1)
                jc = jnp.clip(j, 0, m - 1)
                t_cand = ys_l[jc // s]
                tgt = _deliver(
                    jnp.where((stage == jc % s) & (j >= 0), t_cand,
                              jnp.zeros_like(t_cand)), axis)
                # unlike the gpipe scan, this one runs s-1 extra drain
                # ticks where j walks past the last microbatch: mask the
                # upper bound too or the final microbatch double-counts
                last_valid = (stage == s - 1) & (j >= 0) & (j < m)
                # d(total loss)/d(this consume) = 1/(m·ndp): the psum/m
                # over pp and the pmean over dp. pcast aligns the
                # cotangent's varying-axes type with li's (it is built
                # from pp-varying masks only; li also varies over dp)
                cot = jnp.where(last_valid, 1.0 / (m * ndp), 0.0)
                cot = lax.pcast(cot, baxes, to="varying")
                li, da_t, dy_loss = consume_grads(y, tgt, cot)
                acc = acc + jnp.where(last_valid,
                                      li.astype(jnp.float32), 0.0)
                da_acc = jax.tree.map(lambda a_, d: a_ + d, da_acc, da_t)

                # ---- backward conveyor --------------------------------
                j_b = t - 2 * (s - 1) + stage
                bwd_valid = (j_b >= 0) & (j_b < m)
                g_in = jnp.where(stage == s - 1, dy_loss, bwd_buf)
                x_saved = stash[jnp.clip(j_b, 0, m - 1) % ring]
                _, chain_vjp = jax.vjp(chain, params, x_saved)
                # stage-aux cotangent: the psum(sacc)/(s·v·m) loss term,
                # pmean'd over dp
                aux_cot = jnp.where(bwd_valid,
                                    1.0 / (s * v * m * ndp), 0.0)
                aux_cot = lax.pcast(aux_cot.astype(jnp.float32),
                                    baxes, to="varying")
                dp_t, dx_t = chain_vjp((g_in, aux_cot))
                dp_acc = jax.tree.map(lambda a_, d: a_ + d, dp_acc, dp_t)

                # input grads pop out of stage 0 -> their strided owner
                j0 = t - 2 * (s - 1)
                j0c = jnp.clip(j0, 0, m - 1)
                dx_out = lax.psum(
                    jnp.where((stage == 0) & (j0 >= 0), dx_t,
                              jnp.zeros_like(dx_t)), axis)
                own = (stage == j0c % s) & (j0 >= 0)
                dxs_acc = dxs_acc.at[j0c // s].set(
                    jnp.where(own, dx_out, dxs_acc[j0c // s]))

                fwd_next = lax.ppermute(y, axis, fwd_perm)
                bwd_next = lax.ppermute(
                    jnp.where(bwd_valid, dx_t, jnp.zeros_like(dx_t)),
                    axis, rev_perm)
                return (fwd_next, bwd_next, stash, dp_acc, da_acc,
                        dxs_acc, acc, sacc), None

            # scan carries must enter with the vma type the body
            # produces: the accumulators start as invariant zeros but
            # become (pp, dp)-varying inside — pcast the inits up
            init = (zero, zero,
                    vup(jnp.zeros((ring,) + zero.shape, zero.dtype)),
                    zeros_typed(dp0),
                    zeros_typed(da0),
                    jnp.zeros_like(xs_l),
                    vup(jnp.zeros((), jnp.float32)),
                    vup(jnp.zeros((), jnp.float32)))
            (_, _, _, dp_acc, da_acc, dxs_acc, acc, sacc), _ = lax.scan(
                tick, init, jnp.arange(total))
            loss = lax.psum(acc, axis) / m
            loss = loss + lax.psum(sacc, axis) / (s * v * m)
            if baxes:
                loss = lax.pmean(loss, baxes)

            # Complete the grads: leaves whose cotangents came back
            # invariant were ALREADY auto-psum'd by the vma transpose
            # (psum'ing again double-counts — measured); leaves still
            # varying over an axis their param is replicated on (the
            # custom_vjp escape hatch above) hold local contributions
            # and need exactly one psum over those axes. Stage params
            # are pp-sharded by design, so pp is never completed there.
            def _complete(allowed):
                def go(g):
                    vma = getattr(jax.typeof(g), "vma", frozenset())
                    ax = tuple(a for a in allowed if a in vma)
                    return lax.psum(g, ax) if ax else g
                return go

            dp_acc = jax.tree.map(_complete(baxes), dp_acc)
            da_acc = jax.tree.map(_complete((axis,) + baxes), da_acc)
            return loss, dp_acc, da_acc, dxs_acc[:, None]

        def data_spec(arr):
            entries = (None, axis, baxes if baxes else None)
            return P(*entries[:min(arr.ndim, 3)])

        xs_spec = data_spec(xs_str)
        pspec = param_specs if param_specs is not None else P(axis)
        loss, dp, da, dxs_str = shard_map(
            local, mesh=mesh,
            in_specs=(pspec, P(), xs_spec, data_spec(ys_str)),
            out_specs=(P(), pspec, P(), xs_spec),
            check_vma=True)(stacked_params, aux_params, xs_str, ys_str)
        # un-stride the input grads back to the [M, ...] layout of xs
        mp = dxs_str.shape[0] * dxs_str.shape[1]
        dxs = dxs_str.reshape((mp,) + dxs_str.shape[2:])[:xs.shape[0]]
        return loss, dp, da, dxs

    @jax.custom_vjp
    def stream(stacked_params, aux_params, xs, ys):
        return _combined(stacked_params, aux_params, xs, ys)[0]

    def stream_fwd(stacked_params, aux_params, xs, ys):
        loss, dp, da, dxs = _combined(stacked_params, aux_params, xs, ys)
        return loss, (dp, da, dxs)

    def stream_bwd(res, g):
        dp, da, dxs = res
        scale = lambda x: (x * g).astype(x.dtype)
        return (jax.tree.map(scale, dp), jax.tree.map(scale, da),
                scale(dxs), None)

    stream.defvjp(stream_fwd, stream_bwd)
    return stream


def pipeline_loss_fn(stage_fn: Callable, loss_of_outputs: Callable,
                     mesh: Mesh, axis: str = "pp",
                     num_microbatches: Optional[int] = None):
    """Build a capability fn(stacked_params, batch_x, batch_y) -> loss.

    Splits the batch into microbatches and streams them through
    `pipeline_stream` (loss computed in-scan; no replicated output
    buffer), averaging loss_of_outputs(y_pred, y_true) over microbatches.
    """
    stream = pipeline_stream(
        stage_fn, lambda _aux, pred, tgt: jnp.mean(loss_of_outputs(pred,
                                                                   tgt)),
        mesh, axis)

    def fn(stacked_params, x, y):
        mb = num_microbatches or mesh.shape[axis]
        xs = _microbatch(x, mb)
        ys = _microbatch(y, mb)
        return stream(stacked_params, (), xs, ys)
    return fn


# -- a pipelined transformer LM for the trainer stack ------------------------

def _layernorm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _maybe_psum(v, axis: Optional[str]):
    return lax.psum(v, axis) if axis is not None else v


def _lm_consume(fused_ce: bool):
    """Last-stage loss sink shared by pipelined_lm_loss and
    pipelined_moe_lm_loss: final layernorm + vocab head + mean CE over
    the microbatch. fused_ce swaps in ops.fused_ce.linear_cross_entropy
    (chunked online-softmax — the [tokens, V] logits never materialize),
    same loss to numerical noise (parity-tested both paths)."""
    def consume(aux, y_mb, tgt_mb):
        lnf_s, lnf_b, head = aux
        h = _layernorm(y_mb, lnf_s, lnf_b)
        if fused_ce:
            from paddle_tpu.ops.fused_ce import linear_cross_entropy
            return jnp.mean(linear_cross_entropy(h, head, tgt_mb))
        from paddle_tpu.ops import functional as F
        logits = h @ head
        return jnp.mean(F.softmax_with_cross_entropy(
            logits.astype(jnp.float32), tgt_mb))
    return consume


# sequence-parallel attention modes supported inside pipeline stages;
# the single source of truth for validation here and in pipelined_lm_loss
SP_MODES = ("ring", "ulysses")


def _attention(p: Pytree, x: jax.Array, n_heads: int,
               tp_axis: Optional[str] = None,
               sp_axis: Optional[str] = None, sp_size: int = 1,
               sp_mode: str = "ring") -> jax.Array:
    """Pre-LN causal self-attention sub-layer WITH residual (shared by
    lm_block and moe_lm_block — one home for the packing convention).

    qkv columns are packed HEAD-MAJOR ([head, role, head_dim]), so with
    `tp_axis` the weights arrive column-sliced to whole heads (w_qkv on
    its output dim, w_o on its input dim — Megatron column/row
    parallelism) and the sub-layer closes with one psum over tp.
    Activations are replicated across tp.

    With `sp_axis`, x is the LOCAL [mb, T/sp, D] sequence shard and the
    attention core runs sequence-parallel over that axis: sp_mode
    "ring" (K/V blocks rotate via ppermute, online-softmax merge) or
    "ulysses" (all_to_all regroups sequence↔heads, dense attention over
    the full sequence on H/sp local heads, reverse all_to_all) —
    long-context parallelism composed inside the pipeline. tp and sp
    compose (heads and sequence are orthogonal; ulysses further needs
    sp | heads-per-tp-shard)."""
    from paddle_tpu.parallel.ring import ring_attention_inner
    b, t, d = x.shape
    hd = d // n_heads

    def dense(q, k, v, t_glob):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
        mask = jnp.arange(t_glob)[None, :] <= jnp.arange(t_glob)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    h = _layernorm(x, p["ln1_s"], p["ln1_b"])
    qkv = h @ p["w_qkv"]                        # [mb,T,3D/tp] local heads
    local_heads = qkv.shape[-1] // (3 * hd)
    qkv = qkv.reshape(b, t, local_heads, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    if sp_axis is not None and sp_mode not in SP_MODES:
        raise ValueError(f"sp_mode must be one of {SP_MODES}, "
                         f"got {sp_mode!r}")
    if sp_axis is not None and sp_mode == "ring":
        o = ring_attention_inner(q, k, v, sp_axis, sp_size, causal=True)
    elif sp_axis is not None:                   # ulysses
        def a2a(z, fwd):                        # seq↔heads regroup
            return lax.all_to_all(z, sp_axis, split_axis=2 if fwd else 1,
                                  concat_axis=1 if fwd else 2, tiled=True)
        o = a2a(dense(a2a(q, True), a2a(k, True), a2a(v, True),
                      t * sp_size), False)
    else:
        o = dense(q, k, v, t)
    return x + _maybe_psum(o.reshape(b, t, local_heads * hd) @ p["w_o"],
                           tp_axis)


def lm_block(p: Pytree, x: jax.Array, n_heads: int,
             tp_axis: Optional[str] = None,
             sp_axis: Optional[str] = None, sp_size: int = 1,
             sp_mode: str = "ring") -> jax.Array:
    """One pre-LN causal transformer block (equal-width: [mb, T, D] ->
    [mb, T, D]); `p` is a per-stage slice of PipelinedLM's stacked
    params. See `_attention` for the tp packing and sp ring contracts;
    the FFN splits w1/b1 on the output dim and w2 on the input dim the
    same way (and is per-token, so sequence shards pass through)."""
    x = _attention(p, x, n_heads, tp_axis, sp_axis, sp_size, sp_mode)
    h2 = _layernorm(x, p["ln2_s"], p["ln2_b"])
    up = jax.nn.relu(h2 @ p["w1"] + p["b1"])    # [mb,T,F/tp]
    return x + _maybe_psum(up @ p["w2"], tp_axis) + p["b2"]


def moe_lm_block(p: Pytree, x: jax.Array, n_heads: int,
                 ep_axis: Optional[str] = None, k: int = 2,
                 capacity_factor: float = 2.0):
    """lm_block with the dense FFN replaced by a top-k MoE FFN (GShard-
    style MoE transformer layer). Returns (y, load_balance_scalar) —
    pipeline_stream accumulates the scalar as stage-aux. Inside the
    pipeline shard_map, expert stacks arrive pre-sliced over `ep_axis`
    and `moe_ffn_local` handles dispatch + the combining psum."""
    from paddle_tpu.parallel.moe import moe_ffn_local
    b, t, d = x.shape
    x = _attention(p, x, n_heads)
    h2 = _layernorm(x, p["ln2_s"], p["ln2_b"])
    y, aux = moe_ffn_local(
        {"gate": p["gate"], "w1": p["moe_w1"], "w2": p["moe_w2"]},
        h2.reshape(b * t, d), axis=ep_axis, k=k,
        capacity_factor=capacity_factor)
    return x + y.reshape(b, t, d), aux["load_balance"]


class PipelinedLM(Module):
    """Decoder-only LM whose transformer blocks are S pipeline stages.

    Params: embed/pos/head (+ final LN) live OUTSIDE the pipeline
    (replicated); the S blocks are stacked on a leading dim for
    P("pp", ...) sharding (`pipeline_rules`). `forward` runs the exact
    dense computation (init / eval / single-device parity);
    `pipelined_lm_loss` is the streaming pp×dp training path over the
    same parameters.
    """

    def __init__(self, vocab: int, d_model: int = 64, n_heads: int = 4,
                 d_ff: int = 128, num_stages: int = 4, max_len: int = 128,
                 dtype=jnp.float32):
        super().__init__()
        if d_model % n_heads:
            raise ValueError("n_heads must divide d_model")
        self.vocab, self.d_model, self.n_heads = vocab, d_model, n_heads
        self.d_ff, self.num_stages, self.max_len = d_ff, num_stages, max_len
        self.dtype = dtype

    def _ffn_params(self, sx: Context) -> dict:
        """Per-stage FFN params (hook: PipelinedMoELM swaps in experts)."""
        from paddle_tpu.nn import initializers as I
        d, f, s, dt = self.d_model, self.d_ff, self.num_stages, self.dtype
        return {
            "w1": sx.param("w1", (s, d, f), I.xavier(), dt),
            "b1": sx.param("b1", (s, f), I.constant(0.0), dt),
            "w2": sx.param("w2", (s, f, d), I.xavier(), dt),
            "b2": sx.param("b2", (s, d), I.constant(0.0), dt),
        }

    def _params(self, cx: Context):
        from paddle_tpu.nn import initializers as I
        v, d, s = self.vocab, self.d_model, self.num_stages
        dt = self.dtype
        emb = cx.param("embed", (v, d), I.normal(0.0, 0.02), dt)
        pos = cx.param("pos", (self.max_len, d), I.normal(0.0, 0.02), dt)
        sx = cx.scope("stages")
        stages = {
            "w_qkv": sx.param("w_qkv", (s, d, 3 * d), I.xavier(), dt),
            "w_o": sx.param("w_o", (s, d, d), I.xavier(), dt),
            "ln1_s": sx.param("ln1_s", (s, d), I.constant(1.0), dt),
            "ln1_b": sx.param("ln1_b", (s, d), I.constant(0.0), dt),
            "ln2_s": sx.param("ln2_s", (s, d), I.constant(1.0), dt),
            "ln2_b": sx.param("ln2_b", (s, d), I.constant(0.0), dt),
            **self._ffn_params(sx),
        }
        lnf_s = cx.param("lnf_s", (d,), I.constant(1.0), dt)
        lnf_b = cx.param("lnf_b", (d,), I.constant(0.0), dt)
        head = cx.param("head", (d, v), I.xavier(), dt)
        return emb, pos, stages, lnf_s, lnf_b, head

    def forward(self, cx: Context, tokens):
        emb, pos, stages, lnf_s, lnf_b, head = self._params(cx)
        x = emb[tokens] + pos[: tokens.shape[1]]

        def body(x, stage_p):
            return lm_block(stage_p, x, self.n_heads), None

        x, _ = lax.scan(body, x, stages)        # scan over the stage dim
        return _layernorm(x, lnf_s, lnf_b) @ head

    def generate(self, variables, prompt, num_steps: int,
                 rng: Optional[jax.Array] = None,
                 temperature: float = 0.0) -> jax.Array:
        """Autoregressive continuation: [B, T0] prompt -> [B, T0+steps].

        Greedy at temperature 0, else softmax sampling. Each step runs
        the full dense causal forward (static shapes, jit-able — the
        simple recompute decode; the Transformer family's KV-cache
        `decode_step` is the scale path for serving)."""
        b, t0 = prompt.shape
        if t0 < 1:
            raise ValueError("generate needs a non-empty prompt (the "
                             "first step conditions on its last token)")
        total = t0 + num_steps
        if total > self.max_len:
            raise ValueError(f"prompt {t0} + steps {num_steps} exceeds "
                             f"max_len {self.max_len}")
        tokens = jnp.zeros((b, total), jnp.int32)
        tokens = tokens.at[:, :t0].set(prompt.astype(jnp.int32))

        def body(i, tok):
            logits = self.apply(variables, tok)[:, i - 1]   # [B, V]
            if temperature > 0.0:
                nxt = jax.random.categorical(
                    jax.random.fold_in(rng, i),
                    logits.astype(jnp.float32) / temperature)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return jax.lax.dynamic_update_slice_in_dim(
                tok, nxt[:, None].astype(jnp.int32), i, axis=1)

        if temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng")
        return jax.lax.fori_loop(t0, total, body, tokens)


class PipelinedMoELM(PipelinedLM):
    """PipelinedLM with every stage's dense FFN replaced by a top-k MoE
    FFN (GShard-style MoE transformer): pp×ep×dp — pipeline stages over
    pp, each stage's expert stack sharded over ep, batch over dp. Expert
    dispatch inside a stage needs NO all_to_all (activations are
    replicated across ep; see `moe_ffn_local`). `forward` is the dense
    single-device computation over the same params (capacity math is
    per-call, so exact parity with the pipelined path holds when
    capacity_factor is ample)."""

    def __init__(self, vocab: int, d_model: int = 64, n_heads: int = 4,
                 d_ff: int = 128, num_stages: int = 4, max_len: int = 128,
                 num_experts: int = 4, top_k: int = 2,
                 capacity_factor: float = 2.0, dtype=jnp.float32):
        super().__init__(vocab, d_model, n_heads, d_ff, num_stages,
                         max_len, dtype)
        self.num_experts, self.top_k = num_experts, top_k
        self.capacity_factor = capacity_factor

    def _ffn_params(self, sx: Context) -> dict:
        from paddle_tpu.nn import initializers as I
        d, f, s = self.d_model, self.d_ff, self.num_stages
        e, dt = self.num_experts, self.dtype
        return {
            "gate": sx.param("gate", (s, d, e), I.normal(0.0, 0.02), dt),
            "moe_w1": sx.param("moe_w1", (s, e, d, f), I.xavier(), dt),
            "moe_w2": sx.param("moe_w2", (s, e, f, d), I.xavier(), dt),
        }

    def forward(self, cx: Context, tokens):
        emb, pos, stages, lnf_s, lnf_b, head = self._params(cx)
        x = emb[tokens] + pos[: tokens.shape[1]]

        def body(x, stage_p):
            y, _ = moe_lm_block(stage_p, x, self.n_heads, k=self.top_k,
                                capacity_factor=self.capacity_factor)
            return y, None

        x, _ = lax.scan(body, x, stages)
        return _layernorm(x, lnf_s, lnf_b) @ head


def _stage_specs(axis: str, tp_axis: Optional[str]):
    """PartitionSpecs for PipelinedLM's stacked stage params: dim 0 over
    the pp axis, plus Megatron column/row splits over tp when given."""
    if tp_axis is None:
        return P(axis)          # prefix: every leaf P(axis)
    return {"w_qkv": P(axis, None, tp_axis), "w_o": P(axis, tp_axis, None),
            "w1": P(axis, None, tp_axis), "b1": P(axis, tp_axis),
            "w2": P(axis, tp_axis, None), "b2": P(axis),
            "ln1_s": P(axis), "ln1_b": P(axis),
            "ln2_s": P(axis), "ln2_b": P(axis)}


def pipeline_rules(axis: str = "pp", tp_axis: Optional[str] = None):
    """Sharding rules for PipelinedLM (+ its optimizer slots): stage
    stacks over `axis`; with `tp_axis`, stage matmul weights additionally
    split Megatron-style (w_qkv/w1/b1 on the output dim, w_o/w2 on the
    input dim); embed/pos/head replicated."""
    return _rules_from_specs(axis, _stage_specs(axis, tp_axis))


def _moe_stage_specs(axis: str, ep_axis: Optional[str]):
    """PartitionSpecs for PipelinedMoELM stage params: stage dim over pp,
    expert stacks additionally over ep."""
    if ep_axis is None:
        return P(axis)
    base = {name: P(axis) for name in ("w_qkv", "w_o", "ln1_s", "ln1_b",
                                       "gate", "ln2_s", "ln2_b")}
    base["moe_w1"] = P(axis, ep_axis)
    base["moe_w2"] = P(axis, ep_axis)
    return base


def _rules_from_specs(axis: str, specs) -> "ShardingRules":
    """ShardingRules derived from a stage-spec table (single source of
    truth: the same dict drives shard_map in_specs AND TrainState
    shardings, so the two can never disagree)."""
    from paddle_tpu.parallel.sharding import ShardingRules
    if not isinstance(specs, dict):
        return ShardingRules([(r"(^|/)stages/", tuple(specs))])
    return ShardingRules(
        [(rf"(^|/)stages/{name}$", tuple(spec))
         for name, spec in specs.items()]
        + [(r"(^|/)stages/", (axis,))])


def pipeline_moe_rules(axis: str = "pp", ep_axis: Optional[str] = "ep"):
    """Sharding rules for PipelinedMoELM (+ optimizer slots): stage
    stacks over `axis`, expert stacks additionally over `ep_axis`."""
    return _rules_from_specs(axis, _moe_stage_specs(axis, ep_axis))


def pipelined_moe_lm_loss(mesh: Mesh, axis: str = "pp",
                          num_microbatches: Optional[int] = None,
                          batch_axes: Sequence[str] = ("dp",),
                          ep_axis: Optional[str] = "ep",
                          lb_weight: float = 0.01,
                          fused_ce: bool = False,
                          schedule: str = "gpipe"):
    """MeshTrainer loss_fn training PipelinedMoELM: CE streamed on the
    last stage + lb_weight × the Switch load-balance aux averaged over
    every (stage, microbatch). Expert stacks shard over `ep_axis`
    (pp×ep×dp); pair with `pipeline_moe_rules(axis, ep_axis)`.
    `fused_ce` as in pipelined_lm_loss (chunked linear+CE, no [N, V]
    logits materialization). `schedule` as in pipelined_lm_loss:
    "1f1b" runs the O(S)-activation interleaved backward — the stage-aux
    (load-balance) cotangent rides the same in-tick vjp, and the ep
    psums transpose exactly under the vma machinery (parity-tested).
    """
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"schedule must be 'gpipe' or '1f1b', "
                         f"got {schedule!r}")
    baxes = tuple(a for a in batch_axes if a in mesh.shape)
    ep = ep_axis if ep_axis is not None and mesh.shape.get(ep_axis, 1) > 1 \
        else None

    def loss_fn(module, variables, batch, rng, training):
        tok_in, tok_out = batch
        p = variables[PARAMS]
        s = mesh.shape[axis]
        m = num_microbatches or 2 * s
        b, t = tok_in.shape
        if b % m:
            raise ValueError(
                f"microbatch count {m} must divide batch size {b}")
        if ep is not None and module.num_experts % mesh.shape[ep]:
            raise ValueError(
                f"ep={mesh.shape[ep]} must divide num_experts "
                f"({module.num_experts})")

        h = p["embed"][tok_in] + p["pos"][:t]
        xs = _microbatch(h, m)
        ys = _microbatch(tok_out, m)

        def stage(sp, x):
            y, lb = moe_lm_block(sp, x, module.n_heads, ep_axis=ep,
                                 k=module.top_k,
                                 capacity_factor=module.capacity_factor)
            return y, lb_weight * lb

        builder = (pipeline_stream_1f1b if schedule == "1f1b"
                   else pipeline_stream)
        stream = builder(
            stage, _lm_consume(fused_ce), mesh, axis, batch_axes=baxes,
            param_specs=_moe_stage_specs(axis, ep))
        loss = stream(p["stages"], (p["lnf_s"], p["lnf_b"], p["head"]),
                      xs, ys)
        return (loss, {}), {}
    return loss_fn


def pipelined_lm_loss(mesh: Mesh, axis: str = "pp",
                      num_microbatches: Optional[int] = None,
                      batch_axes: Sequence[str] = ("dp",),
                      tp_axis: Optional[str] = None,
                      sp_axis: Optional[str] = None,
                      sp_mode: str = "ring",
                      fused_ce: bool = False,
                      schedule: str = "gpipe"):
    """MeshTrainer loss_fn training PipelinedLM through the pipeline.

    batch = (tokens_in [B, T], tokens_out [B, T]); num_microbatches
    (default 2·S) divides B. Embedding runs before the pipeline,
    head + cross-entropy stream inside it on the last stage (computed
    redundantly per tp member — head stays replicated).

    With `tp_axis`, stage weights shard Megatron-style inside each
    pipeline stage (pp×tp×dp 3D parallelism); pair with
    `pipeline_rules(axis, tp_axis)` so the TrainState matches. With
    `sp_axis`, the sequence dim shards over it and stages run
    sequence-parallel attention — sp_mode "ring" (K/V rotation) or
    "ulysses" (all_to_all seq<->heads; needs sp | heads-per-tp-shard) —
    pp×sp×dp long-context parallelism, composing with tp.

    `fused_ce` computes the loss via ops.fused_ce.linear_cross_entropy:
    the [mb_tokens, V] logits are never materialized (online softmax
    over vocab chunks), shrinking the last stage's peak activation from
    O(tokens·V) to O(tokens·chunk) — the knob for long sequences or
    large vocabularies; exact same loss (parity-tested).

    `schedule`: "gpipe" (jax.grad through the conveyor — activation
    residuals O(M)) or "1f1b" (`pipeline_stream_1f1b` — in-scan
    interleaved backward, O(S) activation stash; same loss and grads,
    parity-tested). 1f1b composes with tp but not (yet) sp.
    """
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"schedule must be 'gpipe' or '1f1b', "
                         f"got {schedule!r}")
    baxes = tuple(a for a in batch_axes if a in mesh.shape)
    tp = tp_axis if tp_axis is not None and mesh.shape.get(tp_axis, 1) > 1 \
        else None
    sp = sp_axis if sp_axis is not None and mesh.shape.get(sp_axis, 1) > 1 \
        else None
    sp_size = mesh.shape[sp] if sp else 1
    if sp_mode not in SP_MODES:
        raise ValueError(f"sp_mode must be one of {SP_MODES}, "
                         f"got {sp_mode!r}")
    if schedule == "1f1b" and sp is not None:
        raise ValueError("schedule='1f1b' does not compose with sp yet; "
                         "use the gpipe schedule for sequence parallelism")

    def loss_fn(module, variables, batch, rng, training):
        tok_in, tok_out = batch
        p = variables[PARAMS]
        s = mesh.shape[axis]
        m = num_microbatches or 2 * s
        b, t = tok_in.shape
        if b % m:
            raise ValueError(
                f"microbatch count {m} must divide batch size {b}")
        if tp is not None:
            nt = mesh.shape[tp]
            if module.n_heads % nt or module.d_ff % nt:
                raise ValueError(
                    f"tp={nt} must divide n_heads ({module.n_heads}) "
                    f"and d_ff ({module.d_ff})")
        if sp is not None and t % sp_size:
            raise ValueError(
                f"sp={sp_size} must divide sequence length {t}")
        if sp is not None and sp_mode == "ulysses":
            per_tp = module.n_heads // (mesh.shape[tp] if tp else 1)
            if per_tp % sp_size:
                raise ValueError(
                    f"ulysses sp={sp_size} must divide heads per tp "
                    f"shard ({per_tp})")

        h = p["embed"][tok_in] + p["pos"][:t]
        xs = _microbatch(h, m)
        ys = _microbatch(tok_out, m)

        if schedule == "1f1b":
            stream = pipeline_stream_1f1b(
                partial(lm_block, n_heads=module.n_heads, tp_axis=tp),
                _lm_consume(fused_ce), mesh, axis, batch_axes=baxes,
                param_specs=_stage_specs(axis, tp) if tp else None)
        else:
            stream = pipeline_stream(
                partial(lm_block, n_heads=module.n_heads, tp_axis=tp,
                        sp_axis=sp, sp_size=sp_size, sp_mode=sp_mode),
                _lm_consume(fused_ce), mesh, axis, batch_axes=baxes,
                param_specs=_stage_specs(axis, tp) if tp else None,
                seq_axes=(sp,) if sp else ())
        loss = stream(p["stages"], (p["lnf_s"], p["lnf_b"], p["head"]),
                      xs, ys)
        return (loss, {}), {}
    return loss_fn
