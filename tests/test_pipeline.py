"""GPipe-style pipeline over the pp axis (parallel/pipeline.py): forward
parity with sequential stage application and end-to-end differentiability
on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.pipeline import (PipelinedLM, pipeline_apply,
                                          pipeline_loss_fn, pipeline_rules,
                                          pipelined_lm_loss,
                                          stack_stage_params)

S = 4


def stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def make_params(rs, d):
    return [{"w": jnp.asarray(rs.randn(d, d) * 0.3, jnp.float32),
             "b": jnp.asarray(rs.randn(d) * 0.1, jnp.float32)}
            for _ in range(S)]


def sequential(per_stage, x):
    for p in per_stage:
        x = stage_fn(p, x)
    return x


@pytest.fixture
def mesh():
    return make_mesh(pp=S, dp=2)


def test_pipeline_matches_sequential(mesh):
    rs = np.random.RandomState(0)
    d = 16
    per_stage = make_params(rs, d)
    stacked = stack_stage_params(per_stage)
    m, mb = 6, 4
    xs = jnp.asarray(rs.randn(m, mb, d), jnp.float32)

    out = jax.jit(lambda p, x: pipeline_apply(
        stage_fn, p, x, mesh, "pp"))(stacked, xs)
    assert out.shape == (m, mb, d)
    want = jax.vmap(lambda x: sequential(per_stage, x))(xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_grads_flow_to_all_stages(mesh):
    rs = np.random.RandomState(1)
    d = 8
    stacked = stack_stage_params(make_params(rs, d))
    x = jnp.asarray(rs.randn(8, d), jnp.float32)
    y = jnp.asarray(rs.randn(8, d), jnp.float32)

    loss_fn = pipeline_loss_fn(
        stage_fn, lambda pred, t: jnp.mean((pred - t) ** 2), mesh, "pp",
        num_microbatches=4)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(stacked, x, y)
    assert np.isfinite(float(loss))
    gw = np.asarray(grads["w"])
    assert gw.shape == (S, d, d)
    # every stage received gradient signal
    for s in range(S):
        assert np.abs(gw[s]).sum() > 0, f"stage {s} got zero grad"

    # and the pipeline loss equals the sequential loss
    per_stage = [jax.tree.map(lambda p, s=s: p[s], grads) for s in range(S)]
    seq = jax.vmap(lambda xi: sequential(
        [jax.tree.map(lambda p, s=s: p[s], stacked) for s in range(S)],
        xi[None])[0])(x)
    want = float(jnp.mean((seq - y) ** 2))
    assert float(loss) == pytest.approx(want, rel=1e-5)


def test_pipeline_grad_matches_sequential_grad(mesh):
    rs = np.random.RandomState(2)
    d = 8
    per_stage = make_params(rs, d)
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rs.randn(8, d), jnp.float32)
    y = jnp.asarray(rs.randn(8, d), jnp.float32)

    loss_fn = pipeline_loss_fn(
        stage_fn, lambda pred, t: jnp.mean((pred - t) ** 2), mesh, "pp",
        num_microbatches=2)
    g_pipe = jax.jit(jax.grad(loss_fn))(stacked, x, y)

    def seq_loss(stacked_p):
        ps = [jax.tree.map(lambda q, s=s: q[s], stacked_p)
              for s in range(S)]
        pred = sequential(ps, x)
        return jnp.mean((pred - y) ** 2)

    g_seq = jax.grad(seq_loss)(stacked)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]),
                                   rtol=1e-4, atol=1e-5)


# -- PipelinedLM through the trainer stack (pp×dp) ---------------------------

def _lm_and_batch(seed=0, vocab=32, b=16, t=8, stages=S):
    model = PipelinedLM(vocab, d_model=16, n_heads=2, d_ff=32,
                        num_stages=stages, max_len=t)
    rs = np.random.RandomState(seed)
    tok = rs.randint(0, vocab, (b, t + 1)).astype(np.int32)
    return model, (tok[:, :-1], tok[:, 1:])


def _lm_trainer(model, mesh, m=2 * S):
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    return MeshTrainer(
        model, Adam(1e-2), pipelined_lm_loss(mesh, num_microbatches=m),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules())


def test_pipelined_lm_trains_on_pp_dp(mesh):
    model, batch = _lm_and_batch()
    tr = _lm_trainer(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    # per-stage params AND optimizer moments are sharded over pp
    for tree in (ts.params["stages"], ts.opt_state["slots"]["m"]["stages"]):
        for leaf in jax.tree.leaves(tree):
            assert "pp" in str(leaf.sharding.spec), leaf.sharding
    db = tr.put_batch(batch)
    first = None
    for _ in range(8):
        ts, f = tr.train_step(ts, db)
        if first is None:
            first = float(f["loss"])
    assert float(f["loss"]) < first, (first, float(f["loss"]))


def test_pipelined_lm_loss_matches_dense_forward(mesh):
    """Pipelined streaming loss == dense forward CE on the same params."""
    from paddle_tpu.ops import functional as F
    model, batch = _lm_and_batch(seed=3)
    tr = _lm_trainer(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    params0 = jax.device_get(ts.params)     # before the step donates ts
    _, f = tr.train_step(ts, tr.put_batch(batch))
    logits = model.apply({"params": params0}, jnp.asarray(batch[0]))
    want = float(jnp.mean(F.softmax_with_cross_entropy(
        logits.astype(jnp.float32), jnp.asarray(batch[1]))))
    assert float(f["loss"]) == pytest.approx(want, rel=2e-4, abs=2e-4)


def test_pipelined_lm_parity_vs_single_device(mesh):
    """pp×dp pipelined first-step loss == unsharded dense-forward loss
    computed by the plain single-device Trainer (same seed/params)."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    model, batch = _lm_and_batch(seed=4)
    tr = _lm_trainer(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    _, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)


def test_pipeline_virtual_stages_deeper_than_axis(mesh):
    """A model DEEPER than the pp axis pipelines via virtual stages
    (v = S_total/S_mesh consecutive stages chained per device per tick):
    8 stages on pp=4 must match the dense forward exactly."""
    from paddle_tpu.ops import functional as F
    model, batch = _lm_and_batch(seed=4, stages=2 * S)   # v = 2
    tr = _lm_trainer(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    params0 = jax.device_get(ts.params)
    _, f = tr.train_step(ts, tr.put_batch(batch))
    logits = model.apply({"params": params0}, jnp.asarray(batch[0]))
    want = float(jnp.mean(F.softmax_with_cross_entropy(
        logits.astype(jnp.float32), jnp.asarray(batch[1]))))
    assert float(f["loss"]) == pytest.approx(want, rel=2e-4, abs=2e-4)


def test_pipeline_single_device_runs_all_stages():
    """On a 1-device mesh every stage is a virtual stage — the pipelined
    loss must equal the dense forward (the old 1:1 restriction is gone)."""
    from paddle_tpu.ops import functional as F
    one = make_mesh(devices=jax.devices()[:1])
    model, batch = _lm_and_batch(seed=4)
    tr = _lm_trainer(model, one, m=2)
    ts = tr.init_state(jnp.asarray(batch[0]))
    params0 = jax.device_get(ts.params)
    _, f = tr.train_step(ts, tr.put_batch(batch))
    logits = model.apply({"params": params0}, jnp.asarray(batch[0]))
    want = float(jnp.mean(F.softmax_with_cross_entropy(
        logits.astype(jnp.float32), jnp.asarray(batch[1]))))
    assert float(f["loss"]) == pytest.approx(want, rel=2e-4, abs=2e-4)


def test_pipeline_rejects_non_divisible_stage_stack(mesh):
    """A stage stack that does not divide the pp axis fails loudly — at
    state creation (pjit sharding divisibility) or, for unsharded params,
    at the stream's own _check_stages."""
    from paddle_tpu.parallel.pipeline import pipeline_loss_fn
    model, batch = _lm_and_batch(seed=4, stages=3)       # 3 % 4 != 0
    tr = _lm_trainer(model, mesh)
    with pytest.raises(ValueError, match="divisible"):
        tr.init_state(jnp.asarray(batch[0]))
    # the stream-level guard (reached when params arrive unsharded)
    bad = stack_stage_params([{"w": jnp.zeros((4, 4))}] * 3)
    loss = pipeline_loss_fn(lambda p, x: x @ p["w"],
                            lambda a, b: jnp.mean((a - b) ** 2), mesh)
    with pytest.raises(ValueError, match="must be a multiple"):
        jax.jit(loss)(bad, jnp.zeros((8, 4)), jnp.zeros((8, 4)))


def test_pipelined_lm_checkpoint_roundtrip(mesh, tmp_path):
    """Save mid-training, restore onto the pp shardings, continue: the
    stitched run matches the uninterrupted one exactly."""
    from paddle_tpu.io.checkpoint import load_checkpoint, save_checkpoint
    model, batch = _lm_and_batch(seed=5)
    tr = _lm_trainer(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    db = tr.put_batch(batch)
    for _ in range(2):
        ts, _ = tr.train_step(ts, db)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, ts)
    ts, f3 = tr.train_step(ts, db)           # uninterrupted step 3

    tr2 = _lm_trainer(model, mesh)
    target = tr2.init_state(jnp.asarray(batch[0]))
    restored = load_checkpoint(path, target)
    ts2, f3b = tr2.train_step(restored, db)  # resumed step 3
    assert float(f3["loss"]) == pytest.approx(float(f3b["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(ts.params), jax.tree.leaves(ts2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_pipelined_lm_trains_with_remat(mesh):
    """strategy.remat composes with the pipeline scan: activations are
    recomputed in backward (O(1-tick) liveness at 2x forward FLOPs), the
    1F1B memory motivation served the XLA-first way. Loss must match the
    no-remat step exactly (remat changes memory, not math)."""
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    model, batch = _lm_and_batch(seed=6)
    losses = {}
    for name, remat in (("plain", False), ("remat", True)):
        tr = MeshTrainer(
            model, Adam(1e-2),
            pipelined_lm_loss(mesh, num_microbatches=2 * S), mesh,
            strategy=DistStrategy(batch_axes=("dp",), remat=remat),
            rules=pipeline_rules())
        ts = tr.init_state(jnp.asarray(batch[0]))
        ts, f = tr.train_step(ts, tr.put_batch(batch))
        losses[name] = float(f["loss"])
    assert losses["plain"] == pytest.approx(losses["remat"], rel=1e-6)


def test_pipelined_lm_3d_pp_tp_dp():
    """3D parallelism: pp=2 × tp=2 × dp=2 — Megatron tensor parallelism
    INSIDE each pipeline stage, data parallelism across the batch. The
    first-step loss must match the unsharded dense-forward Trainer, and
    stage weights + optimizer moments must be sharded over BOTH pp and
    tp."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig

    mesh3d = make_mesh(MeshConfig(pp=2, tp=2, dp=2))
    model, batch = _lm_and_batch(seed=7, stages=2)
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_lm_loss(mesh3d, num_microbatches=4, tp_axis="tp"),
        mesh3d, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules(tp_axis="tp"))
    ts = tr.init_state(jnp.asarray(batch[0]))
    for tree in (ts.params["stages"], ts.opt_state["slots"]["m"]["stages"]):
        spec = str(tree["w_qkv"].sharding.spec)
        assert "pp" in spec and "tp" in spec, spec
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    dts, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)
    # post-Adam params: backward through the tp psums x dp pmean is
    # only covered here (the n=8 dryrun lands on pp=4,tp=2,dp=1)
    for a, b in zip(jax.tree.leaves(ts.params),
                    jax.tree.leaves(dts.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


# -- PipelinedMoELM: pp×ep×dp --------------------------------------------

def test_pipelined_moe_lm_trains_pp_ep_dp():
    """GShard-style MoE transformer through the pipeline: pp=2 × ep=2 ×
    dp=2. Expert stacks (and their Adam moments) shard over BOTH pp and
    ep; training reduces the loss with the load-balance aux active."""
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig
    from paddle_tpu.parallel.pipeline import (PipelinedMoELM,
                                              pipeline_moe_rules,
                                              pipelined_moe_lm_loss)

    mesh = make_mesh(MeshConfig(pp=2, ep=2, dp=2))
    model = PipelinedMoELM(32, d_model=16, n_heads=2, d_ff=32,
                           num_stages=2, max_len=8, num_experts=4)
    rs = np.random.RandomState(8)
    tok = rs.randint(0, 32, (16, 9)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_moe_lm_loss(mesh, num_microbatches=4, lb_weight=0.01),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_moe_rules())
    ts = tr.init_state(jnp.asarray(batch[0]))
    for tree in (ts.params["stages"], ts.opt_state["slots"]["m"]["stages"]):
        spec = str(tree["moe_w1"].sharding.spec)
        assert "pp" in spec and "ep" in spec, spec
    db = tr.put_batch(batch)
    first = None
    for _ in range(10):
        ts, f = tr.train_step(ts, db)
        if first is None:
            first = float(f["loss"])
    assert float(f["loss"]) < first, (first, float(f["loss"]))


def test_pipelined_moe_lm_ce_parity_vs_dense():
    """With lb_weight=0 and ample capacity, the pp×ep streamed CE equals
    the dense single-device forward CE on the same params exactly."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig
    from paddle_tpu.parallel.pipeline import (PipelinedMoELM,
                                              pipeline_moe_rules,
                                              pipelined_moe_lm_loss)

    mesh = make_mesh(MeshConfig(pp=2, ep=4))
    model = PipelinedMoELM(32, d_model=16, n_heads=2, d_ff=32,
                           num_stages=2, max_len=8, num_experts=4,
                           capacity_factor=4.0)   # E/k: no drops possible
    rs = np.random.RandomState(9)
    tok = rs.randint(0, 32, (8, 9)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_moe_lm_loss(mesh, num_microbatches=4, lb_weight=0.0),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_moe_rules())
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    _, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)


def test_pipelined_lm_sp_ring_attention():
    """Sequence parallelism inside the pipeline: pp=2 × sp=2 × dp=2 —
    stages run ring attention over sp on sequence shards. First-step
    loss must match the unsharded dense-forward Trainer."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig

    mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
    model, batch = _lm_and_batch(seed=11, stages=2)
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_lm_loss(mesh, num_microbatches=4, sp_axis="sp"),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules())
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    dts, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)
    for a, b in zip(jax.tree.leaves(ts.params),
                    jax.tree.leaves(dts.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_pipelined_lm_4d_pp_tp_sp():
    """All structural axes at once: pp=2 × tp=2 × sp=2 — tensor-parallel
    weights AND ring attention over sequence shards inside pipeline
    stages. Loss parity vs the dense forward."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig

    mesh = make_mesh(MeshConfig(pp=2, tp=2, sp=2))
    model, batch = _lm_and_batch(seed=12, stages=2)
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_lm_loss(mesh, num_microbatches=4, tp_axis="tp",
                          sp_axis="sp"),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules(tp_axis="tp"))
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    _, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)


def test_pipeline_stream_low_rank_targets(mesh):
    """Scalar per-microbatch-row targets (rank-3 after striding) must
    still trace — the data spec trims to the argument's rank."""
    rs = np.random.RandomState(13)
    d = 8
    stacked = stack_stage_params(make_params(rs, d))
    x = jnp.asarray(rs.randn(8, d), jnp.float32)
    y = jnp.asarray(rs.randn(8), jnp.float32)         # scalar targets
    loss_fn = pipeline_loss_fn(
        stage_fn, lambda pred, t: (jnp.mean(pred, -1) - t) ** 2, mesh,
        "pp", num_microbatches=4)
    loss = jax.jit(loss_fn)(stacked, x, y)
    assert np.isfinite(float(loss))


def test_pipeline_apply_virtual_stages(mesh):
    """pipeline_apply (the output-returning path) also chains v>1
    virtual stages per device: 8 stacked stages on pp=4 must equal
    sequential application of all 8."""
    rs = np.random.RandomState(14)
    d = 8
    per_stage = [{"w": jnp.asarray(rs.randn(d, d) * 0.3, jnp.float32),
                  "b": jnp.asarray(rs.randn(d) * 0.1, jnp.float32)}
                 for _ in range(2 * S)]
    stacked = stack_stage_params(per_stage)
    xs = jnp.asarray(rs.randn(4, 3, d), jnp.float32)
    out = jax.jit(lambda p, x: pipeline_apply(
        stage_fn, p, x, mesh, "pp"))(stacked, xs)
    want = jax.vmap(lambda x: sequential(per_stage, x))(xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pipelined_lm_generate_and_export(mesh, tmp_path):
    """Train the (+1 mod V) stream on the pipeline, then (a) generate a
    continuation with the dense decode and check it follows the pattern,
    and (b) export + serve through save_inference_model/
    InferencePredictor — the new family plugs into the serving story."""
    from paddle_tpu.io.inference import (InferencePredictor,
                                         save_inference_model)
    vocab = 32
    model = PipelinedLM(vocab, d_model=32, n_heads=4, d_ff=64,
                        num_stages=S, max_len=16)
    rs = np.random.RandomState(15)
    start = rs.randint(0, vocab, (16, 1))
    seq = (start + np.arange(9)) % vocab
    batch = (seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32))
    tr = _lm_trainer(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    db = tr.put_batch(batch)
    for _ in range(60):
        ts, f = tr.train_step(ts, db)
    params = jax.device_get(ts.params)

    # (a) greedy continuation follows the +1 rule
    prompt = jnp.asarray([[3, 4, 5, 6], [20, 21, 22, 23]], jnp.int32)
    out = jax.jit(lambda v, p: model.generate(v, p, 4))(
        {"params": params}, prompt)
    np.testing.assert_array_equal(
        np.asarray(out), [[3, 4, 5, 6, 7, 8, 9, 10],
                          [20, 21, 22, 23, 24, 25, 26, 27]])
    # sampling path traces and stays in-vocab
    sampled = jax.jit(lambda v, p, r: model.generate(
        v, p, 3, rng=r, temperature=1.0))(
        {"params": params}, prompt, jax.random.key(0))
    assert int(jnp.max(sampled)) < vocab and sampled.shape == (2, 7)

    # (b) export + serve
    d = str(tmp_path / "lm")
    x = jnp.asarray(batch[0])
    save_inference_model(d, model, {"params": params}, [x],
                         input_names=["tokens"])
    served = InferencePredictor(d).run([np.asarray(x)])[0]
    want = model.apply({"params": params}, x)
    np.testing.assert_allclose(served, np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_pipelined_lm_sp_ulysses():
    """Ulysses sequence parallelism inside the pipeline (all_to_all
    seq↔heads regroup): pp=2 × sp=2 × dp=2 first-step loss must match
    the dense single-device Trainer — same bar as the ring mode."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig

    mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
    model, batch = _lm_and_batch(seed=16, stages=2)
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_lm_loss(mesh, num_microbatches=4, sp_axis="sp",
                          sp_mode="ulysses"),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules())
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    _, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)


def test_pipelined_lm_ulysses_composes_with_tp():
    """Ulysses × tensor parallelism: pp=2 × tp=2 × sp=2 with 4 heads
    (2 per tp shard, sp=2 divides them — the all_to_all regroups LOCAL
    heads). Loss parity vs the dense trainer, same bar as the ring 4D
    test; plus the divisibility guard when heads-per-tp-shard < sp."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig

    mesh = make_mesh(MeshConfig(pp=2, tp=2, sp=2))
    vocab, b, t = 32, 16, 8
    model = PipelinedLM(vocab, d_model=16, n_heads=4, d_ff=32,
                        num_stages=2, max_len=t)
    rs = np.random.RandomState(17)
    tok = rs.randint(0, vocab, (b, t + 1)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    tr = MeshTrainer(
        model, Adam(1e-2),
        pipelined_lm_loss(mesh, num_microbatches=4, tp_axis="tp",
                          sp_axis="sp", sp_mode="ulysses"),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules(tp_axis="tp"))
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    _, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)

    # 2 heads / tp=2 -> 1 local head; sp=2 cannot split it
    small = PipelinedLM(vocab, d_model=16, n_heads=2, d_ff=32,
                        num_stages=2, max_len=t)
    bad = MeshTrainer(
        small, Adam(1e-2),
        pipelined_lm_loss(mesh, num_microbatches=4, tp_axis="tp",
                          sp_axis="sp", sp_mode="ulysses"),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules(tp_axis="tp"))
    bts = bad.init_state(jnp.asarray(batch[0]))
    with pytest.raises(ValueError, match="divide heads per tp"):
        bad.train_step(bts, bad.put_batch(batch))


def test_pipelined_lm_fused_ce_matches_plain(mesh):
    """fused_ce=True (chunked linear+CE, no [N,V] logits) must produce
    the same pipelined loss as the plain head@CE path on pp×dp."""
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer

    model, batch = _lm_and_batch(seed=18)
    losses = {}
    for fused in (False, True):
        tr = MeshTrainer(
            model, Adam(1e-2),
            pipelined_lm_loss(mesh, num_microbatches=4, fused_ce=fused),
            mesh, strategy=DistStrategy(batch_axes=("dp",)),
            rules=pipeline_rules())
        ts = tr.init_state(jnp.asarray(batch[0]))
        _, f = tr.train_step(ts, tr.put_batch(batch))
        losses[fused] = float(f["loss"])
    assert losses[True] == pytest.approx(losses[False], rel=1e-5, abs=1e-5)


def test_pipelined_moe_lm_fused_ce_matches_plain():
    """Same parity bar for the MoE pipeline's streamed CE."""
    from paddle_tpu.parallel.mesh import MeshConfig
    from paddle_tpu.parallel.pipeline import (PipelinedMoELM,
                                              pipeline_moe_rules,
                                              pipelined_moe_lm_loss)
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer

    mesh = make_mesh(MeshConfig(pp=2, ep=2, dp=2))
    vocab, b, t = 32, 16, 8
    model = PipelinedMoELM(vocab, d_model=16, n_heads=2, d_ff=32,
                           num_stages=2, num_experts=4, max_len=t)
    rs = np.random.RandomState(19)
    tok = rs.randint(0, vocab, (b, t + 1)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    losses = {}
    for fused in (False, True):
        tr = MeshTrainer(
            model, Adam(1e-2),
            pipelined_moe_lm_loss(mesh, num_microbatches=4,
                                  fused_ce=fused),
            mesh, strategy=DistStrategy(batch_axes=("dp",)),
            rules=pipeline_moe_rules())
        ts = tr.init_state(jnp.asarray(batch[0]))
        _, f = tr.train_step(ts, tr.put_batch(batch))
        losses[fused] = float(f["loss"])
    assert losses[True] == pytest.approx(losses[False], rel=1e-5, abs=1e-5)


# -- 1F1B schedule -------------------------------------------------------

def _lm_trainer_1f1b(model, mesh, m=2 * S, tp_axis=None):
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    return MeshTrainer(
        model, Adam(1e-2),
        pipelined_lm_loss(mesh, num_microbatches=m, tp_axis=tp_axis,
                          schedule="1f1b"),
        mesh, strategy=DistStrategy(batch_axes=("dp",)),
        rules=pipeline_rules(tp_axis=tp_axis))


def test_1f1b_loss_and_grads_match_gpipe_and_dense(mesh):
    """The 1F1B in-scan backward must produce the SAME loss and the SAME
    post-step parameters as both the GPipe schedule (jax.grad through
    the conveyor) and the unsharded dense Trainer."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam

    model, batch = _lm_and_batch(seed=11)
    t1 = _lm_trainer_1f1b(model, mesh)
    ts1 = t1.init_state(jnp.asarray(batch[0]))
    ts1, f1 = t1.train_step(ts1, t1.put_batch(batch))

    tg = _lm_trainer(model, mesh)
    tsg = tg.init_state(jnp.asarray(batch[0]))
    tsg, fg = tg.train_step(tsg, tg.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    dts, df = dense.train_step(dts, (batch[0], batch[1]))

    assert float(f1["loss"]) == pytest.approx(float(fg["loss"]),
                                              rel=2e-5, abs=2e-5)
    assert float(f1["loss"]) == pytest.approx(float(df["loss"]),
                                              rel=2e-4, abs=2e-4)
    # post-Adam params: grads agree through every stage and the embed
    # (input-cotangent) path
    for a, b in zip(jax.tree.leaves(ts1.params),
                    jax.tree.leaves(tsg.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)
    for a, b in zip(jax.tree.leaves(ts1.params),
                    jax.tree.leaves(dts.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=2e-3)


def test_1f1b_trains(mesh):
    model, batch = _lm_and_batch(seed=12)
    tr = _lm_trainer_1f1b(model, mesh)
    ts = tr.init_state(jnp.asarray(batch[0]))
    db = tr.put_batch(batch)
    first = None
    for _ in range(8):
        ts, f = tr.train_step(ts, db)
        if first is None:
            first = float(f["loss"])
    assert float(f["loss"]) < first, (first, float(f["loss"]))


def test_1f1b_composes_with_tp():
    """pp=2 × tp=2 × dp=2 under the 1F1B schedule: the in-tick jax.vjp
    transposes the stage's tp psums; post-step params match dense."""
    from paddle_tpu.core.executor import Trainer, supervised_loss
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel.mesh import MeshConfig

    mesh3d = make_mesh(MeshConfig(pp=2, tp=2, dp=2))
    model, batch = _lm_and_batch(seed=13, stages=2)
    tr = _lm_trainer_1f1b(model, mesh3d, m=4, tp_axis="tp")
    ts = tr.init_state(jnp.asarray(batch[0]))
    ts, f = tr.train_step(ts, tr.put_batch(batch))

    dense = Trainer(model, Adam(1e-2), supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(
            lg.astype(jnp.float32), y)))
    dts = dense.init_state(jnp.asarray(batch[0]))
    dts, df = dense.train_step(dts, (batch[0], batch[1]))
    assert float(f["loss"]) == pytest.approx(float(df["loss"]),
                                             rel=2e-4, abs=2e-4)
    for a, b in zip(jax.tree.leaves(ts.params),
                    jax.tree.leaves(dts.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=2e-3)


def test_1f1b_virtual_stages_and_fused_ce(mesh):
    """8 stages on pp=4 (v=2 virtual stages per device) under 1F1B with
    the fused-CE consume: loss matches the gpipe schedule."""
    model, batch = _lm_and_batch(seed=14, stages=8)
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer

    def mk(schedule):
        return MeshTrainer(
            model, Adam(1e-2),
            pipelined_lm_loss(mesh, num_microbatches=8, fused_ce=True,
                              schedule=schedule),
            mesh, strategy=DistStrategy(batch_axes=("dp",)),
            rules=pipeline_rules())

    t1, tg = mk("1f1b"), mk("gpipe")
    ts1 = t1.init_state(jnp.asarray(batch[0]))
    ts1, f1 = t1.train_step(ts1, t1.put_batch(batch))
    tsg = tg.init_state(jnp.asarray(batch[0]))
    tsg, fg = tg.train_step(tsg, tg.put_batch(batch))
    assert float(f1["loss"]) == pytest.approx(float(fg["loss"]),
                                              rel=2e-5, abs=2e-5)
    for a, b in zip(jax.tree.leaves(ts1.params),
                    jax.tree.leaves(tsg.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=2e-3)


def test_1f1b_rejects_sp():
    mesh4 = make_mesh(pp=2, sp=2, dp=2)
    with pytest.raises(ValueError, match="1f1b"):
        pipelined_lm_loss(mesh4, sp_axis="sp", schedule="1f1b")


def test_1f1b_activation_liveness_below_gpipe(mesh):
    """The reason 1F1B exists: per-device activation liveness O(S) vs
    GPipe-through-jax.grad's O(M). XLA's compiled memory analysis at
    M=8, S=4 (d=256, T=128, batch 64): measured 194.6 MB (gpipe) vs
    24.2 MB (1f1b) temp — assert a conservative 2x so XLA version noise
    cannot flake the test; PERF_NOTES carries the exact numbers."""
    model = PipelinedLM(512, d_model=256, n_heads=8, d_ff=1024,
                        num_stages=4, max_len=128)
    rs = np.random.RandomState(0)
    tok = rs.randint(0, 512, (64, 129)).astype(np.int32)
    batch = (jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:]))
    variables = model.init(jax.random.key(0), batch[0])

    def temp_bytes(schedule):
        lf = pipelined_lm_loss(mesh, num_microbatches=8,
                               schedule=schedule)

        def f(v):
            (loss, _), _ = lf(model, v, batch, None, True)
            return loss

        comp = jax.jit(jax.value_and_grad(f)).lower(variables).compile()
        return comp.memory_analysis().temp_size_in_bytes

    assert temp_bytes("1f1b") * 2 < temp_bytes("gpipe")


def test_1f1b_moe_matches_gpipe():
    """PipelinedMoELM under the 1F1B schedule (pp=2 x ep=2 x dp=2): the
    stage-aux (load-balance) cotangent and the in-stage ep psums ride
    the in-tick vjp — loss and post-step params must match gpipe."""
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import DistStrategy, MeshTrainer
    from paddle_tpu.parallel.mesh import MeshConfig
    from paddle_tpu.parallel.pipeline import (PipelinedMoELM,
                                              pipeline_moe_rules,
                                              pipelined_moe_lm_loss)

    mesh = make_mesh(MeshConfig(pp=2, ep=2, dp=2))
    model = PipelinedMoELM(32, d_model=16, n_heads=2, d_ff=32,
                           num_stages=2, max_len=8, num_experts=4,
                           top_k=2, capacity_factor=4.0)
    rs = np.random.RandomState(21)
    tok = rs.randint(0, 32, (16, 9)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])

    def mk(schedule):
        return MeshTrainer(
            model, Adam(1e-2),
            pipelined_moe_lm_loss(mesh, num_microbatches=4,
                                  schedule=schedule),
            mesh, strategy=DistStrategy(batch_axes=("dp",)),
            rules=pipeline_moe_rules())

    t1, tg = mk("1f1b"), mk("gpipe")
    ts1 = t1.init_state(jnp.asarray(batch[0]))
    ts1, f1 = t1.train_step(ts1, t1.put_batch(batch))
    tsg = tg.init_state(jnp.asarray(batch[0]))
    tsg, fg = tg.train_step(tsg, tg.put_batch(batch))
    assert float(f1["loss"]) == pytest.approx(float(fg["loss"]),
                                              rel=2e-5, abs=2e-5)
    for a, b in zip(jax.tree.leaves(ts1.params),
                    jax.tree.leaves(tsg.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=2e-3)
