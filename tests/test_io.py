"""Checkpoint + inference export tests (≈ fluid.io save/load tests,
tests/book save_inference_model round-trips)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.executor import Trainer, supervised_loss
from paddle_tpu.io import (
    CheckpointManager, InferencePredictor, latest_checkpoint, load_checkpoint,
    load_inference_model, save_checkpoint, save_inference_model)
from paddle_tpu.models import MLP
from paddle_tpu.ops import functional as F
from paddle_tpu.optim.optimizer import SGD


def _trainer():
    loss_fn = supervised_loss(
        lambda logits, y: F.softmax_with_cross_entropy(logits, y))
    return Trainer(MLP(hidden=(16,), num_classes=3), SGD(0.1), loss_fn)


def test_checkpoint_roundtrip(tmp_path):
    trainer = _trainer()
    ts = trainer.init_state(jnp.zeros((4, 6)))
    path = save_checkpoint(str(tmp_path / "ck"), ts, step=0)
    restored = load_checkpoint(path, target=ts)
    for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(restored)):
        np.testing.assert_allclose(a, b)


def test_checkpoint_shape_mismatch(tmp_path):
    save_checkpoint(str(tmp_path / "ck"), {"w": np.zeros((2, 2))})
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path / "ck"), target={"w": np.zeros((3,))})


def test_checkpoint_missing_leaf(tmp_path):
    save_checkpoint(str(tmp_path / "ck"), {"w": np.zeros(2)})
    with pytest.raises(FileNotFoundError):
        load_checkpoint(str(tmp_path / "ck"),
                        target={"w": np.zeros(2), "b": np.zeros(1)})


def test_manager_rotation_and_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    tree = {"w": np.arange(3.0)}
    for step in (1, 2, 3):
        mgr.save({"w": tree["w"] * step}, step=step)
    names = sorted(os.listdir(tmp_path))
    assert names == ["ckpt-2", "ckpt-3"]
    restored, step = mgr.restore_latest(target=tree)
    assert step == 3
    np.testing.assert_allclose(restored["w"], tree["w"] * 3)
    assert latest_checkpoint(str(tmp_path)).endswith("ckpt-3")


def test_inference_export_roundtrip(tmp_path):
    trainer = _trainer()
    ts = trainer.init_state(jnp.zeros((4, 6)))
    model_dir = str(tmp_path / "model")
    x = jnp.asarray(np.random.RandomState(0).randn(4, 6), jnp.float32)
    save_inference_model(model_dir, trainer.module, ts.variables, [x],
                         input_names=["x"])

    fn, variables, sig = load_inference_model(model_dir)
    assert sig["input_names"] == ["x"]
    expected = trainer.module.apply(ts.variables, x)
    got = fn(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)

    pred = InferencePredictor(model_dir)
    out = pred.run({"x": np.asarray(x)})
    np.testing.assert_allclose(out[0], np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """FSDP-sharded TrainState: shards written per owner, restored with
    shardings= and identical layout (SURVEY §5.4)."""
    import json
    from paddle_tpu.optim.optimizer import Adam
    from paddle_tpu.parallel import (
        DistStrategy, MeshConfig, MeshTrainer, ReduceStrategy, make_mesh)
    from paddle_tpu.parallel.sharding import fsdp_rules

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    loss_fn = supervised_loss(
        lambda logits, y: F.softmax_with_cross_entropy(logits, y))
    tr = MeshTrainer(MLP(hidden=(64,), num_classes=8), Adam(1e-3), loss_fn,
                     mesh,
                     strategy=DistStrategy(
                         reduce_strategy=ReduceStrategy.REDUCE),
                     rules=fsdp_rules(min_size=64))
    ts = tr.init_state(jnp.zeros((16, 32)))
    x = np.random.RandomState(0).randn(16, 32).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 8, 16)
    ts, _ = tr.train_step(ts, tr.put_batch((x, y)), rng=jax.random.key(0))

    # at least one leaf must actually be sharded (not fully replicated)
    assert any(
        not leaf.sharding.is_fully_replicated
        for leaf in jax.tree.leaves(ts) if isinstance(leaf, jax.Array))

    path = save_checkpoint(str(tmp_path / "ck"), ts, step=1)
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    assert manifest["version"] == 2
    assert os.path.exists(os.path.join(path, "shards-p0.npz"))

    restored = load_checkpoint(path, target=ts,
                               shardings=tr._state_shardings)
    for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        if isinstance(a, jax.Array):
            assert b.sharding.is_equivalent_to(a.sharding, a.ndim)

    # restored state must be directly usable by the compiled step
    ts2, fetches = tr.train_step(restored, tr.put_batch((x, y)),
                                 rng=jax.random.key(1))
    assert np.isfinite(float(fetches["loss"]))


def test_v1_checkpoint_read_compat(tmp_path):
    """Old single-file checkpoints (version 1) still load."""
    import json
    tree = {"w": np.arange(6.0).reshape(2, 3), "b": np.zeros(3)}
    path = str(tmp_path / "old")
    os.makedirs(path)
    leaves = []
    arrays = {}
    for i, (k, v) in enumerate(sorted(tree.items())):
        arrays[f"a{i}"] = v
        leaves.append({"key": k, "slot": f"a{i}", "shape": list(v.shape),
                       "dtype": str(v.dtype)})
    np.savez(os.path.join(path, "arrays.npz"), **arrays)
    json.dump({"version": 1, "step": 7, "metadata": {}, "leaves": leaves},
              open(os.path.join(path, "manifest.json"), "w"))
    restored = load_checkpoint(path, target=tree)
    np.testing.assert_allclose(restored["w"], tree["w"])
    np.testing.assert_allclose(restored["b"], tree["b"])


def test_async_checkpointer_parity_and_ordering(tmp_path):
    """AsyncCheckpointer: same on-disk result as the sync path; a second
    save joins the in-flight one (single-writer ordering)."""
    from paddle_tpu.io import AsyncCheckpointer
    trainer = _trainer()
    ts = trainer.init_state(jnp.zeros((4, 6)))
    ac = AsyncCheckpointer()
    ac.save(str(tmp_path / "a"), ts, step=1)
    ac.save(str(tmp_path / "b"), ts, step=2)   # joins save of "a" first
    ac.wait()
    for name, step in (("a", 1), ("b", 2)):
        restored = load_checkpoint(str(tmp_path / name), target=ts)
        for x, y in zip(jax.tree.leaves(ts), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_async_checkpoint_survives_donated_source(tmp_path):
    """The snapshot happens before save() returns: donating/overwriting
    the source arrays afterwards must not corrupt the checkpoint."""
    from paddle_tpu.io import AsyncCheckpointer
    trainer = _trainer()
    ts = trainer.init_state(jnp.zeros((4, 6)))
    want = [np.asarray(x).copy() for x in jax.tree.leaves(ts)]
    ac = AsyncCheckpointer()
    ac.save(str(tmp_path / "ck"), ts, step=0)
    # train_step donates ts: its buffers are consumed immediately
    x = jnp.asarray(np.random.RandomState(0).randn(4, 6), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 3, 4))
    trainer.train_step(ts, (x, y))
    ac.wait()
    restored2 = load_checkpoint(str(tmp_path / "ck"),
                                target=trainer.init_state(jnp.zeros((4, 6))))
    for w, g in zip(want, jax.tree.leaves(restored2)):
        np.testing.assert_array_equal(w, np.asarray(g))


def test_async_error_propagates(tmp_path):
    from paddle_tpu.io import AsyncCheckpointer
    trainer = _trainer()
    ts = trainer.init_state(jnp.zeros((4, 6)))
    ac = AsyncCheckpointer()
    bad = tmp_path / "no" / "such" / "deep" / "dir" / "ck"
    ac.save(str(bad), ts, step=0)
    with pytest.raises(RuntimeError, match="async checkpoint"):
        ac.wait()
    ac.wait()  # error is consumed; subsequent waits are clean


def test_manager_async_rotation_and_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2, async_save=True)
    trainer = _trainer()
    ts = trainer.init_state(jnp.zeros((4, 6)))
    for step in (1, 2, 3):
        mgr.save(ts, step=step)
    restored, step = mgr.restore_latest(target=ts)  # waits internally
    assert step == 3
    mgr.wait()
    names = sorted(n for n in os.listdir(tmp_path) if n.startswith("ckpt-"))
    assert names == ["ckpt-2", "ckpt-3"]  # rotation ran in the background


def test_restore_onto_sharded_target_then_step(tmp_path):
    """Restoring with only `target=` must land leaves on the target's own
    shardings: a numpy-restored fsdp state used to crash the donated
    train step with an XLA aliased-buffer size mismatch."""
    from paddle_tpu.parallel import (DistStrategy, MeshConfig, MeshTrainer,
                                     ReduceStrategy, make_mesh)
    from paddle_tpu.parallel.sharding import fsdp_rules

    mesh = make_mesh(MeshConfig(dp=4, fsdp=2))
    loss_fn = supervised_loss(
        lambda lg, y: F.softmax_with_cross_entropy(lg, y))
    tr = MeshTrainer(
        MLP(hidden=(64,), num_classes=4), SGD(0.1), loss_fn, mesh,
        strategy=DistStrategy(reduce_strategy=ReduceStrategy.REDUCE),
        rules=fsdp_rules(min_size=64))
    ts = tr.init_state(jnp.zeros((8, 6)))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(ts, step=1)
    restored, step = mgr.restore_latest(ts)
    assert step == 1
    # every restored leaf carries the target's sharding
    for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(restored)):
        assert isinstance(b, jax.Array)
        assert b.sharding == a.sharding
    x = jnp.asarray(np.random.RandomState(0).randn(8, 6), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 4, 8))
    restored, fetches = tr.train_step(restored, tr.put_batch((x, y)))
    assert np.isfinite(float(fetches["loss"]))
