"""The experts' grouped-product kernel, interpreted on the CPU, against
`jax.lax.ragged_dot` and the zero mask (`grouped_product_reference`):
the one matrix (down) and the gate and up in one call, float32 and
bf16, over the row patterns a serving step makes, and the gradient the
`custom_vjp` gives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import grouped_product as gp

# rows 512 in tiles of 128 (gp.ROW_TILE): 4 row tiles, 8 groups
M, D, F, G = 512, 128, 256, 8

PATTERNS = {
    # every pair in one group
    "one_group": [0, 0, 0, 512, 0, 0, 0, 0],
    # 4 pairs a group, as a decode step: all in the first tile
    "decode": [4] * 8,
    # empty groups between full ones
    "empty_between": [100, 0, 0, 156, 0, 200, 0, 56],
    # one group's rows cross three row tiles
    "crosses_tiles": [10, 300, 20, 0, 0, 0, 0, 0],
    # a row tile shared by three groups (rows 128..255: 40 + 50 + 38)
    "three_in_a_tile": [128, 40, 50, 38, 0, 0, 0, 256],
    # the groups' sum far below the rows: the tiles past it zeros
    "few_rows": [3, 0, 5, 0, 0, 2, 0, 1],
    # no real pair at all
    "none": [0] * 8,
}


def _operands(counts, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (M, D), jnp.float32).astype(dtype)
    gate = (jax.random.normal(k2, (G, D, F), jnp.float32) / 8).astype(dtype)
    up = (jax.random.normal(k3, (G, D, F), jnp.float32) / 8).astype(dtype)
    down = (jax.random.normal(k4, (G, F, D), jnp.float32) / 8).astype(dtype)
    return x, gate, up, down, jnp.asarray(counts, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("fused", [True, False], ids=["gate_up", "down"])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_kernel_is_the_reference(pattern, fused, dtype):
    """Every row of every group as the reference gives it (float32 to
    1e-5; bf16 against the float32 reference of the same bf16 operands
    to a few of bf16's steps: the kernel rounds once, the reference at
    each product), and every row past counts.sum() exactly zero."""
    x, gate, up, down, counts = _operands(PATTERNS[pattern], dtype)
    if fused:
        got = gp.gated_grouped_product(x, gate, up, counts, use_kernel=True,
                                       interpret=True)
        want = gp.gated_grouped_product(
            *(a.astype(jnp.float32) for a in (x, gate, up)), counts,
            use_kernel=False)
        assert got.shape == (M, F)
    else:
        h = jax.random.normal(jax.random.PRNGKey(5), (M, F),
                              jnp.float32).astype(dtype)
        got = gp.grouped_product(h, down, counts, use_kernel=True,
                                 interpret=True)
        want = gp.grouped_product(h.astype(jnp.float32),
                                  down.astype(jnp.float32), counts,
                                  use_kernel=False)
        assert got.shape == (M, D)
    assert got.dtype == dtype
    total = int(counts.sum())
    got = np.asarray(got.astype(jnp.float32))
    assert not got[total:].any()
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else \
        dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(got[:total], np.asarray(want)[:total], **tol)


def test_the_gradient_is_the_reference_s():
    """Through the kernel's custom_vjp, x's and every weight's gradient
    are the reference's VJP, bit for bit (a linear loss, so the
    cotangent does not depend on the forward's rounding), for both
    calls."""
    x, gate, up, down, counts = _operands(PATTERNS["three_in_a_tile"],
                                          jnp.float32, seed=1)
    h = jax.random.normal(jax.random.PRNGKey(6), (M, F), jnp.float32)
    c_h = jax.random.normal(jax.random.PRNGKey(7), (M, F), jnp.float32)
    c_y = jax.random.normal(jax.random.PRNGKey(8), (M, D), jnp.float32)

    def grads(use_kernel):
        def gated(x, gate, up):
            return jnp.sum(c_h * gp.gated_grouped_product(
                x, gate, up, counts, use_kernel=use_kernel, interpret=True))

        def plain(h, down):
            return jnp.sum(c_y * gp.grouped_product(
                h, down, counts, use_kernel=use_kernel, interpret=True))
        return (jax.grad(gated, argnums=(0, 1, 2))(x, gate, up)
                + jax.grad(plain, argnums=(0, 1))(h, down))
    got, want = grads(True), grads(False)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("fused", [True, False], ids=["gate_up", "down"])
def test_a_narrow_weight_block_walks_the_columns(fused, monkeypatch):
    """With room for one 128-lane weight block only, the width is split
    into column blocks, each walking all the visits (the spare visits'
    zeros too): the same rows as the reference, zeros past the sum."""
    monkeypatch.setattr(gp, "WEIGHT_VMEM_BYTES", 2 * 2 * D * 128 * 4)
    x, gate, up, down, counts = _operands(PATTERNS["three_in_a_tile"][:7]
                                          + [100], jnp.float32)
    if fused:
        assert gp._tiles(M, D, F, 2, 4) == (128, 128)
        got = gp.gated_grouped_product(x, gate, up, counts, use_kernel=True,
                                       interpret=True)
        want = gp.gated_grouped_product(x, gate, up, counts,
                                        use_kernel=False)
    else:
        h = jax.random.normal(jax.random.PRNGKey(5), (M, F), jnp.float32)
        wide = jnp.concatenate([down, down], axis=2)        # [G, F, 2 D]
        assert gp._tiles(M, F, 2 * D, 1, 4) == (128, 128)
        got = gp.grouped_product(h, wide, counts, use_kernel=True,
                                 interpret=True)
        want = gp.grouped_product(h, wide, counts, use_kernel=False)
    total = int(counts.sum())
    assert not np.asarray(got)[total:].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
