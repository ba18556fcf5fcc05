"""Expert-parallel MoE FFN (parallel/moe.py): ep-sharded vs dense parity,
routing behavior, load-balancing loss, training signal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.moe import (init_moe_params, load_balancing_loss,
                                     moe_ffn, moe_ffn_a2a,
                                     moe_partition_specs)

E, D, HID = 4, 16, 32


@pytest.fixture
def params():
    return init_moe_params(jax.random.key(0), E, D, HID)


def test_moe_ep_matches_dense(params):
    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(24, D), jnp.float32)
    y_dense, aux_d = moe_ffn(params, x)
    y_ep, aux_e = jax.jit(
        lambda p, x: moe_ffn(p, x, mesh=mesh))(params, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(aux_e["expert_index"]),
                                  np.asarray(aux_d["expert_index"]))


def test_moe_routes_to_multiple_experts(params):
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(256, D), jnp.float32)
    _, aux = moe_ffn(params, x)
    used = np.unique(np.asarray(aux["expert_index"]))
    assert len(used) >= 2          # random gate spreads tokens


def test_load_balancing_loss_uniform_is_one():
    probs = jnp.full((64, E), 1.0 / E)
    idx = jnp.arange(64) % E
    loss = load_balancing_loss({"router_probs": probs, "expert_index": idx})
    assert float(loss) == pytest.approx(1.0, rel=1e-5)


def test_moe_topk_masked_matches_dense(params):
    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(24, D), jnp.float32)
    y_dense, _ = moe_ffn(params, x, k=2)
    y_ep, _ = jax.jit(lambda p, x: moe_ffn(p, x, mesh=mesh, k=2))(params, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_moe_a2a_matches_masked_with_ample_capacity(params, k):
    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(32, D), jnp.float32)
    y_masked, aux_m = jax.jit(
        lambda p, x: moe_ffn(p, x, mesh=mesh, k=k))(params, x)
    # capacity_factor=E/k: C = T/n tokens per expert = no drops possible
    y_a2a, aux_a = jax.jit(lambda p, x: moe_ffn_a2a(
        p, x, mesh=mesh, k=k, capacity_factor=E / k))(params, x)
    assert float(aux_a["dropped_fraction"]) == 0.0
    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_masked),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(aux_a["expert_index"]),
                                  np.asarray(aux_m["expert_index"]))


def test_moe_a2a_drops_past_capacity(params):
    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(5)
    # all tokens identical → all route to one expert → heavy overflow at
    # capacity_factor 1 (C = ceil(T/n · k/E · 1) << T/n)
    x = jnp.tile(jnp.asarray(rs.randn(1, D), jnp.float32), (32, 1))
    y, aux = jax.jit(lambda p, x: moe_ffn_a2a(
        p, x, mesh=mesh, k=1, capacity_factor=1.0))(params, x)
    drop = float(aux["dropped_fraction"])
    cap = int(aux["capacity"])
    assert drop > 0.5                      # most of the hot expert dropped
    # kept rows per device = capacity; dropped tokens contribute zero
    # each ep device keeps `cap` tokens for the hot expert; the rest zero
    zero_rows = np.all(np.asarray(y) == 0, axis=-1)
    assert zero_rows.sum() == 32 - cap * mesh.shape["ep"]


def test_moe_a2a_gradients_flow(params):
    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(32, D), jnp.float32)
    t = jnp.asarray(rs.randn(32, D), jnp.float32)

    def loss_fn(p):
        y, aux = moe_ffn_a2a(p, x, mesh=mesh, k=2, capacity_factor=2.0)
        return jnp.mean((y - t) ** 2) + 0.01 * load_balancing_loss(aux)

    g = jax.jit(jax.grad(loss_fn))(params)
    for name in ("gate", "w1", "w2"):
        assert float(jnp.sum(jnp.abs(g[name]))) > 0, f"no grad for {name}"


def test_moe_routing_diversifies_under_training(params):
    """The aux loss must actively rebalance a collapsed router during
    training, not just look fine at init."""
    from paddle_tpu.optim.optimizer import Adam
    rs = np.random.RandomState(7)
    # positive-mean tokens: the gate has no bias term, so a column-0
    # weight shift acts as a (positive) logit bias for every token
    x = jnp.asarray(rs.rand(256, D) + 0.5, jnp.float32)
    t = jnp.asarray(rs.randn(256, D), jnp.float32)
    # collapse the router: ~+5 logit bonus for expert 0 on every token
    p0 = dict(params)
    p0["gate"] = params["gate"].at[:, 0].add(0.3)
    _, aux0 = moe_ffn(p0, x, k=1)
    f0 = np.bincount(np.asarray(aux0["expert_index"]), minlength=E) / 256

    opt = Adam(3e-2)
    state = opt.init(p0)

    def loss_fn(p):
        y, aux = moe_ffn(p, x, k=1)
        return jnp.mean((y - t) ** 2) + 0.1 * load_balancing_loss(aux)

    @jax.jit
    def step(p, s):
        g = jax.grad(loss_fn)(p)
        return opt.apply(p, g, s)

    p = p0
    for _ in range(60):
        p, state = step(p, state)
    _, aux1 = moe_ffn(p, x, k=1)
    f1 = np.bincount(np.asarray(aux1["expert_index"]), minlength=E) / 256
    assert f0.max() > 0.9                  # started collapsed
    assert f1.max() < 0.7                  # training spread the load
    assert (f1 > 0.05).sum() >= 2          # at least two live experts


def test_moe_trains_router_and_experts(params):
    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(32, D), jnp.float32)
    t = jnp.asarray(rs.randn(32, D), jnp.float32)

    def loss_fn(p):
        y, aux = moe_ffn(p, x, mesh=mesh)
        return jnp.mean((y - t) ** 2) + 0.01 * load_balancing_loss(aux)

    g = jax.jit(jax.grad(loss_fn))(params)
    for k in ("gate", "w1", "w2"):
        assert float(jnp.sum(jnp.abs(g[k]))) > 0, f"no grad for {k}"
    specs = moe_partition_specs()
    assert str(specs["w1"]) == str(specs["w2"])


def test_moe_a2a_under_capacity_pressure(params):
    """The under-capacity regime the capacity contract exists for
    : with a skewed router at capacity_factor=1.0,
    tokens ARE dropped (reported via dropped_fraction), training still
    improves the loss, and the balancing loss drives the drop-rate down
    as the router spreads load."""
    from paddle_tpu.optim.optimizer import Adam

    mesh = make_mesh(ep=4, dp=2)
    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.rand(256, D) + 0.5, jnp.float32)
    t = jnp.asarray(rs.randn(256, D) * 0.1, jnp.float32)
    # skew the router toward expert 0 so its capacity buffer overflows
    p0 = dict(params)
    p0["gate"] = params["gate"].at[:, 0].add(0.3)
    cf = 1.0

    def fwd(p):
        return moe_ffn_a2a(p, x, mesh=mesh, k=1, capacity_factor=cf)

    _, aux0 = jax.jit(fwd)(p0)
    d0 = float(aux0["dropped_fraction"])
    assert d0 > 0.2, f"expected real capacity pressure, dropped={d0}"

    opt = Adam(3e-2)
    state = opt.init(p0)

    def loss_fn(p):
        y, aux = fwd(p)
        main = jnp.mean((y - t) ** 2)
        return main + 0.1 * load_balancing_loss(aux), (main, aux)

    @jax.jit
    def step(p, s):
        (_, (main, aux)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        p, s = opt.apply(p, g, s)
        return p, s, main, aux["dropped_fraction"]

    p = p0
    mains, drops = [], []
    for _ in range(60):
        p, state, main, dropped = step(p, state)
        mains.append(float(main))
        drops.append(float(dropped))
    assert mains[-1] < mains[0], (mains[0], mains[-1])
    # balancing loss rebalances the router => fewer tokens past capacity
    assert drops[-1] < 0.5 * d0, (d0, drops[-1])
