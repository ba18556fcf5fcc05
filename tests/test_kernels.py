"""Pallas kernel tests (interpret mode on CPU): flash attention numerics vs
the XLA reference path — the contract that makes the TPU fast path safe."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.attention import mha, reference_attention
from paddle_tpu.kernels import flash
from paddle_tpu.kernels.flash import flash_attention


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(rng, causal):
    b, t, h, d = 2, 64, 2, 32
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    mask = None
    if causal:
        mask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
    ref = reference_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_kv_len(rng):
    b, t, h, d = 1, 32, 1, 16
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    out = flash_attention(q, k, v, kv_len=20, block_q=8, block_k=8,
                          interpret=True)
    mask = (jnp.arange(t) < 20)[None, None, None, :]
    ref = reference_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_rectangular_and_blocks(rng):
    b, tq, tk, h, d = 2, 24, 40, 2, 16
    q = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_mha_dispatch_cpu_uses_reference(rng):
    q = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
    out = mha(q, q, q, causal=True)
    assert out.shape == q.shape


def test_flash_tail_block_not_double_counted(rng):
    """t_k % block_k != 0 with no kv_len: clamped tail reads must be masked
    (ADVICE r1: kpos bound applied unconditionally)."""
    b, t, h, d = 1, 20, 1, 16  # 20 % 8 = 4 tail rows
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def _flash(q, k, v, causal, kv_len, blocks, subs):
    """flash_attention at the given blocks; with `subs`, both directions'
    strips cut to them. The strips are no argument of flash_attention, so
    that case calls the core on [BH, T, D], at lengths the blocks divide."""
    if subs is None:
        return flash_attention(q, k, v, causal=causal, kv_len=kv_len,
                               block_q=blocks[0], block_k=blocks[1],
                               interpret=True)
    b, t, h, d = q.shape

    def to_bhtd(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, -1, d)

    o = flash._flash_core(to_bhtd(q), to_bhtd(k), to_bhtd(v), None, None,
                          None, d ** -0.5, causal, kv_len, *blocks, True,
                          0.0, h, (subs, subs))
    return jnp.transpose(o.reshape(b, h, t, d), (0, 2, 1, 3))


def _dense_mask(tq, tk, causal, kv_len):
    mask = None
    if causal:
        mask = (jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None])[None,
                                                                    None]
    if kv_len is not None:
        pad = (jnp.arange(tk) < kv_len)[None, None, None, :]
        mask = pad if mask is None else mask & pad
    return mask


# b, tq, tk, h, d, (block_q, block_k), the strips' (sub_q, sub_k) or None
# for the module's own, causal, kv_len
BACKWARD_CASES = {
    # the two cases of the old test_flash_backward_matches_reference and
    # the one of test_flash_backward_kv_len
    "full": (2, 32, 32, 2, 16, (8, 8), None, False, None),
    "causal": (2, 32, 32, 2, 16, (8, 8), None, True, None),
    "kv_len": (1, 24, 24, 1, 16, (8, 8), None, False, 17),
    "tq_under_tk": (1, 24, 40, 2, 16, (8, 8), None, True, None),
    "tq_over_tk": (1, 40, 24, 2, 16, (8, 8), None, True, None),
    "no_block_divides": (1, 50, 50, 2, 16, (16, 16), None, True, None),
    # one block of 1,024 under kv_len, not a [1000, 1000] tile
    "pads_to_the_lanes": (1, 1000, 1000, 1, 16, (1024, 1024), None, True,
                          None),
    "block_under_a_strip": (1, 24, 24, 1, 16, (8, 8), (16, 16), True, None),
    "strips": (2, 64, 64, 2, 16, (32, 32), (8, 8), True, None),
    "strips_wide_grain": (1, 64, 64, 2, 16, (32, 32), (8, 16), True, None),
    "strips_tall_grain": (1, 64, 64, 2, 16, (32, 32), (16, 8), True, None),
    "strips_not_causal": (1, 64, 64, 2, 16, (32, 32), (8, 8), False, None),
    "strips_kv_len": (1, 64, 64, 2, 16, (32, 32), (8, 8), True, 41),
    "blocks_not_square": (1, 64, 64, 2, 16, (32, 16), (8, 8), True, None),
    "one_block_a_head": (1, 64, 64, 1, 16, (64, 64), (16, 16), True, None),
    # the module's own strips, two and more a block
    "own_strips": (1, 512, 512, 1, 16, (512, 512), None, True, None),
}


@pytest.mark.parametrize("path", ["one_pass", "two_kernels"])
@pytest.mark.parametrize("case", list(BACKWARD_CASES))
def test_flash_backward_matches_reference(rng, monkeypatch, case, path):
    """jax.grad through the custom_vjp against the XLA path, with the
    backward held to one of its two schedules: `_bwd_impl` (which picks
    from the shapes) replaced by the schedule's own function."""
    b, tq, tk, h, d, blocks, subs, causal, kv_len = BACKWARD_CASES[case]
    monkeypatch.setattr(flash, "_bwd_impl", getattr(flash, "_bwd_" + path))
    q = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    tgt = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32))

    def loss_flash(q, k, v):
        o = _flash(q, k, v, causal, kv_len, blocks, subs)
        return jnp.sum((o - tgt) ** 2)

    mask = _dense_mask(tq, tk, causal, kv_len)

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, mask=mask)
        return jnp.sum((o - tgt) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("subs", [(8, 8), (8, 16), (16, 8), (32, 32)])
def test_flash_forward_strips(rng, subs):
    """The forward over strips of a block: what lies wholly above is
    left out, the causal mask on the crossed ones only."""
    b, t, h, d = 1, 64, 2, 16
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    out = _flash(q, k, v, True, None, (32, 32), subs)
    ref = reference_attention(q, k, v, mask=_dense_mask(t, t, True, None))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def _plan_cover(block, sub_q, sub_k, kind, by):
    """(computed, masked): boolean [block, block] maps of a plan."""
    computed = np.zeros((block, block), bool)
    masked = np.zeros((block, block), bool)
    for rows, cols, crossed in flash._plan(block, block, sub_q, sub_k,
                                           kind, by):
        assert not computed[rows, cols].any()       # tiles do not overlap
        computed[rows, cols] = True
        if crossed is not None and by == "q":
            masked[rows, cols.start + crossed.start:
                   cols.start + crossed.stop] = True
        elif crossed is not None:
            masked[rows.start + crossed.start:
                   rows.start + crossed.stop, cols] = True
    return computed, masked


@pytest.mark.parametrize("by", ["q", "k"])
@pytest.mark.parametrize("block,sub,computed,crossed", [
    (512, (512, 512), 1, 1),    # no strips: the whole block, masked
    (512, (256, 256), 3, 2),
    (512, (128, 128), 10, 4),
    (1024, (128, 128), 36, 8),  # 56% of a head's T^2 at T = 1,024
    (64, (16, 8), None, None),  # oblong grains
    (64, (8, 16), None, None),
])
def test_flash_plan_follows_the_diagonal(block, sub, computed, crossed, by):
    """A block whose corner is on the diagonal: one tile a strip, every
    visible (query, key) pair computed, nothing wholly above the diagonal
    at the grain, and the causal mask exactly where a tile holds a pair
    that is not visible."""
    tiles = flash._plan(block, block, *sub, "diag", by)
    assert len(tiles) == block // sub[by == "k"]
    got, masked = _plan_cover(block, *sub, "diag", by)
    visible = np.tril(np.ones((block, block), bool))
    assert got[visible].all()
    assert visible[got & ~masked].all()
    if computed is not None:
        assert got.sum() == computed * sub[0] * sub[1]
        assert masked.sum() == crossed * sub[0] * sub[1]
    # at the grain: a sub-tile is computed only if it holds a visible pair
    grain = got.reshape(block // sub[0], sub[0], block // sub[1], sub[1])
    seen = visible.reshape(grain.shape).any(axis=(1, 3))
    assert (grain.any(axis=(1, 3)) == seen).all()


@pytest.mark.parametrize("by", ["q", "k"])
def test_flash_plan_off_the_diagonal(by):
    got, masked = _plan_cover(64, 16, 16, "all", by)
    assert got.all() and not masked.any()
    got, masked = _plan_cover(64, 16, 16, "any", by)
    assert got.all() and masked.all()


@pytest.mark.parametrize("t,block,q_strips,k_strips", [
    (512, 1024, (128, 128), (128, 256)),    # capped at the sequence later
    (1024, 1024, (128, 128), (128, 256)),   # the training cell: one block
    (1536, 512, (128, 128), (128, 256)),    # no padding to 2,048
    (2560, 512, (128, 128), (128, 256)),
    (16384, 1024, (128, 128), (128, 256)),
    (640, 1024, (128, 128), (128, 128)),    # 256 does not divide 640
    (1000, 1024, (128, 128), (128, 256)),   # one block of 1,024, padded
])
def test_flash_default_blocks_and_strips(t, block, q_strips, k_strips):
    assert flash._default_blocks(t, t) == (block, block)
    side = min(block, -(-t // 128) * 128)   # flash_attention's cap
    assert flash._strip_sizes(side, side, "q") == q_strips
    assert flash._strip_sizes(side, side, "k") == k_strips


@pytest.mark.parametrize("t_q,d,one_pass", [
    (1024, 64, True),       # the training cell
    (4096, 128, True),
    (16384, 64, False),     # lm_longctx, the ring path's local blocks
    (4096, 256, False),
])
def test_flash_backward_schedule_follows_the_shapes(monkeypatch, t_q, d,
                                                    one_pass):
    monkeypatch.setattr(flash, "_bwd_one_pass", lambda *a: "one_pass")
    monkeypatch.setattr(flash, "_bwd_two_kernels", lambda *a: "two")
    q = jax.ShapeDtypeStruct((4, t_q, d), jnp.bfloat16)
    got = flash._bwd_impl(q, *[None] * 16)
    assert got == ("one_pass" if one_pass else "two")


def test_mha_kv_len_reference_path(rng):
    """mha forwards kv_len to the reference path as a padding mask."""
    q = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
    out = mha(q, q, q, kv_len=5)
    mask = (jnp.arange(8) < 5)[None, None, None, :]
    ref = reference_attention(q, q, q, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_reference_attention_gqa_matches_repeat(rng):
    """Grouped-query dense path == plain path with kv heads repeated."""
    b, t, h, kvh, d = 2, 24, 4, 2, 16
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, kvh, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, kvh, d).astype(np.float32))
    mask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
    out = reference_attention(q, k, v, mask=mask)
    kr = jnp.repeat(k, h // kvh, axis=2)
    vr = jnp.repeat(v, h // kvh, axis=2)
    ref = reference_attention(q, kr, vr, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
