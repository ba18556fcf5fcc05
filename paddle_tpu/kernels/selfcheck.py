"""On-hardware flash-attention correctness gate.

CI exercises the Pallas kernels in interpret mode (CPU); the only place
they execute on a real TPU is the benchmark. A wrong-but-fast kernel
would ship silently, so the bench calls `flash_selfcheck()` on the real
device: it runs the flash path and the XLA reference path on the same
batch — forward AND backward — asserts the flash branch was actually
taken, and compares numerics.
"""

from __future__ import annotations

# graftlint: skip-file=EH001 — this module IS the assert: an on-device
# correctness gate whose whole contract is raising AssertionError (the
# bench and tests/test_flash_selfcheck.py catch it by type).

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import attention as A
from paddle_tpu.utils.flags import FLAGS


def flash_selfcheck(batch: int = 2, heads: int = 4, seq: int = 1024,
                    head_dim: int = 64, causal: bool = True,
                    dtype=jnp.bfloat16, atol: float = 5e-2) -> Dict:
    """Compare flash vs reference attention fwd+bwd on one batch.

    Returns {"flash_check": "ok", "max_err": ...} or raises AssertionError.
    Tolerance is bf16-scale: both paths use fp32 softmax/accumulation, so
    outputs agree to bf16 rounding.
    """
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(batch, seq, heads, head_dim), dtype) * 0.3
    k = jnp.asarray(rs.randn(batch, seq, heads, head_dim), dtype) * 0.3
    v = jnp.asarray(rs.randn(batch, seq, heads, head_dim), dtype) * 0.3

    # 1. the dispatch gate must choose flash for this shape on this device
    from paddle_tpu.kernels import flash as flash_mod
    taken = {"flash": False}
    orig = flash_mod.flash_attention

    def spy(*args, **kw):
        taken["flash"] = True
        return orig(*args, **kw)

    flash_mod.flash_attention, spy_token = spy, None
    try:
        def loss_flash(q, k, v):
            return jnp.sum(A.mha(q, k, v, causal=causal).astype(jnp.float32)
                           ** 2)

        f_out = A.mha(q, k, v, causal=causal)
        f_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        flash_mod.flash_attention = orig
    assert taken["flash"], (
        "flash_selfcheck: dispatch gate did NOT take the flash path "
        f"(platform={jax.devices()[0].platform}, "
        f"flag={FLAGS.get('flash_attention')})")

    # 2. reference path on the same batch
    def loss_ref(q, k, v):
        return jnp.sum(A.reference_attention(
            q, k, v, mask=_causal_mask(seq) if causal else None)
            .astype(jnp.float32) ** 2)

    r_out = A.reference_attention(
        q, k, v, mask=_causal_mask(seq) if causal else None)
    r_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)

    max_rel = 0.0
    for a, b in zip((f_out, *f_grads), (r_out, *r_grads)):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        max_rel = max(max_rel, float(jnp.max(jnp.abs(a - b))) / scale)
    assert max_rel < atol, (
        f"flash_selfcheck: flash vs reference mismatch: max relative "
        f"error {max_rel:.4f} (tol {atol})")

    # 3. segment-id (packed-batch) masking on hardware: block-sparse
    # skipping must not change values vs the dense masked reference
    segs = np.zeros((batch, seq), np.int32)
    segs[:, seq // 3:] = 1
    segs[:, 2 * seq // 3:] = 2
    segs_j = jnp.asarray(segs)
    s_out = A.mha(q, k, v, causal=causal, segment_ids=segs_j)
    smask = (segs_j[:, None, :, None] == segs_j[:, None, None, :])
    if causal:
        smask = jnp.logical_and(smask, _causal_mask(seq))
    s_ref = A.reference_attention(q, k, v, mask=smask)
    seg_err = float(jnp.max(jnp.abs(s_out.astype(jnp.float32)
                                    - s_ref.astype(jnp.float32)))) / (
        float(jnp.max(jnp.abs(s_ref.astype(jnp.float32)))) + 1e-6)
    assert seg_err < atol, (
        f"flash_selfcheck: segment-id path mismatch: {seg_err:.4f}")

    # 4. in-kernel dropout: deterministic per key, ~rate zeros, and the
    # no-dropout average is recovered in expectation (loose bound)
    key = jax.random.PRNGKey(3)
    d1 = A.mha(q, k, v, causal=causal, dropout_rate=0.5,
               dropout_rng=key)
    d2 = A.mha(q, k, v, causal=causal, dropout_rate=0.5,
               dropout_rng=key)
    drop_det = float(jnp.max(jnp.abs(d1.astype(jnp.float32)
                                     - d2.astype(jnp.float32))))
    assert drop_det == 0.0, (
        f"flash_selfcheck: dropout not deterministic per key: {drop_det}")
    assert not np.allclose(np.asarray(d1, np.float32),
                           np.asarray(f_out, np.float32)), (
        "flash_selfcheck: dropout_rate=0.5 did not change the output "
        "(in-kernel dropout is not being applied)")

    return {"flash_check": "ok", "flash_max_rel_err": round(max_rel, 5),
            "flash_seg_rel_err": round(seg_err, 5),
            "flash_platform": jax.devices()[0].platform}


def _causal_mask(t: int):
    return (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
