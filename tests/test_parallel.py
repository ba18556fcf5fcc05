"""Mesh/sharding/collective/MeshTrainer tests on the 8-device virtual CPU
mesh (the analog of the reference's multi-device ParallelExecutor tests,
test_parallel_executor_mnist.py, and dist tests test_dist_base.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.executor import Trainer, supervised_loss
from paddle_tpu.metrics import accuracy
from paddle_tpu.models import MLP
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import Adam, SGD
from paddle_tpu.parallel import (
    DistStrategy, MeshConfig, MeshTrainer, ReduceStrategy, ShardingRules,
    collective, make_mesh, local_mesh, shard_variables,
)
from paddle_tpu.parallel.sharding import fsdp_rules


def test_make_mesh_shapes():
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    assert mesh.shape["dp"] == 4
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(dp=3, tp=4))


def test_collectives_under_shard_map():
    mesh = local_mesh(8, axis="dp")
    x = jnp.arange(8.0)

    @collective.shard_fn(mesh, in_specs=P("dp"), out_specs=P("dp"))
    def allred(v):
        return v + 0 * collective.all_reduce(v, "dp")  # shape-preserving

    @collective.shard_fn(mesh, in_specs=P("dp"), out_specs=P())
    def total(v):
        return collective.all_reduce(jnp.sum(v), "dp")

    assert float(total(x)) == 28.0

    @collective.shard_fn(mesh, in_specs=P("dp"), out_specs=P("dp"))
    def rotate(v):
        return collective.ppermute(v, "dp", collective.ring_perm(8))

    np.testing.assert_allclose(np.asarray(rotate(x)),
                               np.roll(np.arange(8.0), 1))

    @collective.shard_fn(mesh, in_specs=P("dp"), out_specs=P("dp"))
    def bcast(v):
        return collective.broadcast(v, "dp", root=3)

    np.testing.assert_allclose(np.asarray(bcast(x)), np.full(8, 3.0))


def test_sharding_rules():
    rules = ShardingRules([(r"fc/weight$", ("tp", None))])
    tree = {"fc": {"weight": np.zeros((8, 4)), "bias": np.zeros(4)},
            "other": np.zeros((2, 2))}
    specs = rules.tree_specs(tree)
    assert specs["fc"]["weight"] == P("tp", None)
    assert specs["fc"]["bias"] == P()


def test_fsdp_rules_shard_largest_dim():
    rules = fsdp_rules(min_size=16)
    specs = rules.tree_specs({"big": np.zeros((4, 100)),
                              "small": np.zeros((2,))})
    assert specs["big"] == P(None, "fsdp")
    assert specs["small"] == P()


def _loss_fn():
    return supervised_loss(
        lambda logits, y: F.softmax_with_cross_entropy(logits, y),
        metrics={"acc": accuracy})


def _batches(n, bs=32, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(dim, classes)
    for _ in range(n):
        x = rng.randn(bs, dim).astype(np.float32)
        y = np.argmax(x @ w + 0.1 * rng.randn(bs, classes), -1)
        yield x, y.astype(np.int64)


def _train(trainer, steps=40, bs=32, seed=0):
    ts = trainer.init_state(jnp.zeros((bs, 8)))
    fetches = None
    for batch in _batches(steps, bs=bs, seed=seed):
        if hasattr(trainer, "put_batch"):
            batch = trainer.put_batch(batch)
        ts, fetches = trainer.train_step(
            ts, batch, rng=jax.random.fold_in(jax.random.key(7),
                                              int(jax.device_get(ts.step))))
    return ts, fetches


def test_mesh_trainer_dp_learns():
    mesh = local_mesh(8, axis="dp")
    trainer = MeshTrainer(MLP(hidden=(32,), num_classes=4), Adam(1e-2),
                          _loss_fn(), mesh)
    ts, fetches = _train(trainer)
    assert float(fetches["loss"]) < 1.0
    # params replicated in ALL_REDUCE mode
    w = jax.tree.leaves(ts.params)[0]
    assert w.sharding.is_fully_replicated


def test_mesh_trainer_zero_shards_params_and_moments():
    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    strategy = DistStrategy(reduce_strategy=ReduceStrategy.REDUCE)
    trainer = MeshTrainer(MLP(hidden=(128,), num_classes=4), Adam(1e-2),
                          _loss_fn(), mesh, strategy=strategy,
                          rules=fsdp_rules(min_size=128))
    ts, fetches = _train(trainer)
    assert float(fetches["loss"]) < 1.2
    big = ts.params["fcs_0"]["weight"]
    assert not big.sharding.is_fully_replicated
    # adam moments inherit the same sharding (true ZeRO)
    m = ts.opt_state["slots"]["m"]["fcs_0"]["weight"]
    assert m.sharding.spec == big.sharding.spec


def test_mesh_matches_single_device():
    """Multi-device run must match single-device numerics (the core
    correctness claim of the reference's dist tests, delta=1e-5)."""
    loss_fn = _loss_fn()
    single = Trainer(MLP(hidden=(16,), num_classes=4), SGD(0.05), loss_fn,
                     seed=0)
    ts_s = single.init_state(jnp.zeros((32, 8)))
    mesh = local_mesh(8, axis="dp")
    multi = MeshTrainer(MLP(hidden=(16,), num_classes=4), SGD(0.05),
                        loss_fn, mesh, seed=0)
    ts_m = multi.init_state(jnp.zeros((32, 8)))

    for batch in _batches(10, bs=32):
        rng = jax.random.fold_in(jax.random.key(3),
                                 int(jax.device_get(ts_s.step)))
        ts_s, f_s = single.train_step(ts_s, batch, rng=rng)
        ts_m, f_m = multi.train_step(ts_m, multi.put_batch(batch), rng=rng)
    np.testing.assert_allclose(float(f_s["loss"]), float(f_m["loss"]),
                               rtol=2e-4)
    for a, b in zip(jax.tree.leaves(ts_s.params),
                    jax.tree.leaves(ts_m.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_gradient_accumulation_matches_big_batch():
    """accum=4 over bs=32 ≈ one step at bs=32 mean-of-microbatch grads
    (multi_batch_merge capability)."""
    loss_fn = _loss_fn()
    mesh = local_mesh(8, axis="dp")
    base = MeshTrainer(MLP(hidden=(16,), num_classes=4), SGD(0.1), loss_fn,
                       mesh, seed=0)
    acc = MeshTrainer(MLP(hidden=(16,), num_classes=4), SGD(0.1), loss_fn,
                      mesh, seed=0,
                      strategy=DistStrategy(gradient_accumulation_steps=4))
    batch = next(iter(_batches(1, bs=32)))
    ts_b = base.init_state(jnp.zeros((32, 8)))
    ts_a = acc.init_state(jnp.zeros((32, 8)))
    rng = jax.random.key(11)
    ts_b, _ = base.train_step(ts_b, base.put_batch(batch), rng=rng)
    ts_a, _ = acc.train_step(ts_a, acc.put_batch(batch), rng=rng)
    for a, b in zip(jax.tree.leaves(ts_a.params),
                    jax.tree.leaves(ts_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def _tiny_transformer():
    from paddle_tpu.models.transformer import Transformer
    return Transformer(src_vocab=32, trg_vocab=32, model_dim=16, num_heads=4,
                       num_layers=2, ffn_dim=32, dropout=0.0, max_len=16)


def _seq_loss(module, variables, batch, rng, training):
    src, trg_in, trg_out = batch
    logits, mut = module.apply(variables, src, trg_in, training=training,
                               rngs=rng, mutable=True)
    loss = jnp.mean(F.softmax_with_cross_entropy(logits, trg_out))
    return (loss, {}), mut.get("state", {})


def test_transformer_tp_matches_single_device():
    """Megatron-style TP (transformer_tp_rules) end-to-end: a dp×tp mesh
    train run must match single-device numerics AND actually shard the
    attention/mlp projections over tp (≈ the reference's multi-device
    parity bar, parallel_executor_test_base.py:31)."""
    from paddle_tpu.parallel.sharding import transformer_tp_rules
    single = Trainer(_tiny_transformer(), SGD(0.05), _seq_loss, seed=0)
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    multi = MeshTrainer(_tiny_transformer(), SGD(0.05), _seq_loss, mesh,
                        seed=0, strategy=DistStrategy(batch_axes=("dp",)),
                        rules=transformer_tp_rules())
    rs = np.random.RandomState(0)
    src = rs.randint(0, 32, (8, 6)).astype(np.int32)
    trg = rs.randint(0, 32, (8, 7)).astype(np.int32)
    batch = (src, trg[:, :-1], trg[:, 1:])
    ts_s = single.init_state(jnp.asarray(src), jnp.asarray(trg[:, :-1]))
    ts_m = multi.init_state(jnp.asarray(src), jnp.asarray(trg[:, :-1]))

    qw = ts_m.params["enc_layers_0"]["attn"]["q_proj"]["weight"]
    assert qw.sharding.spec == P(None, "tp"), qw.sharding.spec
    ow = ts_m.params["enc_layers_0"]["attn"]["out_proj"]["weight"]
    assert ow.sharding.spec == P("tp", None), ow.sharding.spec

    f_s = f_m = None
    for i in range(3):
        rng = jax.random.key(100 + i)
        ts_s, f_s = single.train_step(ts_s, batch, rng=rng)
        ts_m, f_m = multi.train_step(ts_m, multi.put_batch(batch), rng=rng)
    np.testing.assert_allclose(float(f_s["loss"]), float(f_m["loss"]),
                               rtol=1e-3)
    for a, b in zip(jax.tree.leaves(ts_s.params),
                    jax.tree.leaves(ts_m.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_shard_variables_roundtrip():
    mesh = local_mesh(8, axis="dp")
    tree = {"w": np.arange(16.0).reshape(8, 2)}
    placed = shard_variables(mesh, tree,
                             ShardingRules([(r"w$", ("dp", None))]))
    assert placed["w"].sharding.spec == P("dp", None)
    np.testing.assert_allclose(np.asarray(placed["w"]), tree["w"])


def test_sharding_rules_fsdp_fallback_composes():
    """fsdp fallback is a constructor feature (not an instance patch), so
    rule tables compose and subclass/copy safely."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.sharding import (ShardingRules, fsdp_rules,
                                              transformer_tp_rules)

    rules = transformer_tp_rules()
    # explicit rule wins
    assert rules.spec_for("enc/q_proj/weight", (512, 512)) == P(None, "tp")
    # unmatched rank-2 param falls back to fsdp largest-dim
    assert rules.spec_for("misc/weight", (128, 512)) == P(None, "fsdp")
    # rank-1 (bias-like) stays replicated under min_rank=2
    assert rules.spec_for("somewhere/gamma", (512,)) == P()
    # composing: adding a rule does not disturb the fallback
    rules.add(r"special/weight$", ("sp", None))
    assert rules.spec_for("x/special/weight", (4, 4)) == P("sp", None)
    assert rules.spec_for("misc2/weight", (128, 512)) == P(None, "fsdp")
    # fsdp_rules still honours min_size
    fr = fsdp_rules(min_size=10**6)
    assert fr.spec_for("small/weight", (10, 10)) == P()
    assert fr.spec_for("big/weight", (2048, 2048)) == P("fsdp", None)


def test_eval_step_keeps_state_sharded():
    """eval_step pins in_shardings so fsdp state is not gathered."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import MLP
    from paddle_tpu.core.executor import supervised_loss
    from paddle_tpu.metrics import accuracy
    from paddle_tpu.ops import functional as F
    from paddle_tpu.optim.optimizer import SGD
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.strategy import DistStrategy, ReduceStrategy
    from paddle_tpu.parallel.trainer import MeshTrainer

    mesh = make_mesh(dp=2, fsdp=4)
    model = MLP(hidden=(64, 64), num_classes=4)
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(lg, y),
        metrics={"acc": accuracy})
    tr = MeshTrainer(model, SGD(0.1), loss_fn, mesh,
                     strategy=DistStrategy(
                         reduce_strategy=ReduceStrategy.REDUCE))
    ts = tr.init_state(jnp.zeros((8, 16)))
    rs = np.random.RandomState(0)
    batch = tr.put_batch((rs.randn(8, 16).astype(np.float32),
                          rs.randint(0, 4, 8).astype(np.int64)))
    out = tr.eval_step(ts, batch)
    assert np.isfinite(float(out["loss"]))
    # the compiled eval step's input shardings must equal the training
    # shardings (i.e. fsdp params arrive sharded, not gathered to one
    # replica): compare the compiled input shardings leaf-by-leaf
    compiled = tr._eval_step.lower(ts, batch).compile()
    got = jax.tree.leaves(compiled.input_shardings[0],
                          is_leaf=lambda s: hasattr(s, "spec"))
    fsdp_in = [g for g in got
               if any("fsdp" in str(e) for e in getattr(g, "spec", ())
                      if e is not None)]
    # the rule table sharded the big weights; the compiled step must accept
    # them fsdp-sharded (an unpinned step that gathers would show
    # replicated input shardings here)
    assert fsdp_in, [getattr(g, "spec", None) for g in got]


def test_sharded_embedding_checkpoint_guard(tmp_path):
    """Geometry stamp catches num_embeddings drift on restore."""
    import jax.numpy as jnp
    import pytest as _pytest
    from paddle_tpu.io.checkpoint import (read_metadata, save_checkpoint)
    from paddle_tpu.parallel.embedding import (
        ShardedEmbedding, checkpoint_meta, validate_checkpoint_meta)

    emb = ShardedEmbedding(1000, 16)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, {"w": jnp.zeros((4,))},
                    metadata=checkpoint_meta(emb))
    meta = read_metadata(path)
    validate_checkpoint_meta(meta, emb)              # same geometry: ok
    emb2 = ShardedEmbedding(1001, 16)
    with _pytest.raises(ValueError, match="geometry changed"):
        validate_checkpoint_meta(meta, emb2)
    validate_checkpoint_meta({}, emb2)               # unstamped: trivially ok
