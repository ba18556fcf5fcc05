"""The main path's kernels compile for the chip — checked here, where
there is no chip, by the TPU compiler that is installed with JAX
(compiled for a DESCRIBED v5e; nothing runs, so this says nothing about
results or times). Interpret-mode tests cannot see what this does: the
ragged kernels passed all of them while the chip's compiler refused
their bf16 MHA shape.

This is the only test file that describes a chip. Only one process at a
time may load the TPU's library, and each xdist worker imports every
test file: so the topology is described inside a fixture (never at
import), compiles run in this process, and these tests stay in one
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    """A described four-chip v5e host; the persistent compilation cache
    is off while this module compiles (an executable built for a
    described chip can be written to it but never read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_with_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,kv_heads,head_dim,mixed", [
    (16, 16, 64, False),     # GPT-2 medium: bf16 MHA at head_dim 64
    (16, 16, 64, True),
    (4, 4, 64, False),       # its tp=4 per-shard slice
    (4, 4, 64, True),
    (32, 4, 128, False),     # GQA
], ids=["mha16x64-fp", "mha16x64-int8_mixed", "tp4_slice-fp",
        "tp4_slice-int8_mixed", "gqa32_4x128-fp"])
def test_ragged_kernel_compiles_for_v5e(one_chip, heads, kv_heads, head_dim,
                                        mixed):
    from paddle_tpu.kernels.paged_attention import ragged_paged_attention
    # the engine's default step: 512-token chunk budget + 8 decode rows,
    # tile_q 8, block 16, max_len 1024
    t, tq, bs, nb, mb, rows, nq = 576, 8, 16, 2048, 64, 9, 256

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = s((nb, bs, kv_heads, head_dim), jnp.bfloat16)
    args = [s((t, heads, head_dim), jnp.bfloat16), pool, pool,
            s((rows, mb), jnp.int32), s((rows,), jnp.int32),
            s((rows,), jnp.int32), s((t // tq,), jnp.int32),
            s((t // tq,), jnp.int32)]
    if mixed:
        qpool = s((nq, bs, kv_heads, head_dim), jnp.int8)
        args += [qpool, qpool, s((nq,), jnp.float32), s((nq,), jnp.float32)]

    def fn(q, kp, vp, bt, cl, qs, tr, to, kq=None, vq=None, ks=None,
           vs=None):
        return ragged_paged_attention(
            q, kp, vp, bt, cl, qs, tr, to, use_kernel=True, interpret=False,
            kq_pool=kq, vq_pool=vq, k_scales=ks, v_scales=vs)
    assert _compiles_with_kernel(fn, *args)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_kernel_compiles_for_v5e(one_chip, grad):
    from paddle_tpu.kernels.flash import flash_attention
    q = jax.ShapeDtypeStruct((1, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    assert _compiles_with_kernel(fn, q, q, q)


def test_gpipe_backward_keeps_its_psum_in_the_tick_loop(topo):
    """On four real chips the TPU compiler hoisted the transposed psum of
    the pipeline's input conveyor out of the tick loop, across the
    device-dependent owner mask: loss exact, input gradients wrong, and
    nothing on the CPU shows it. `pipeline._deliver` holds it in place
    with an optimization barrier; the compiled backward must still have
    an all-reduce inside the loop body."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    from paddle_tpu.parallel.pipeline import pipeline_stream

    s, m, mb, d = 4, 8, 2, 16
    mesh = make_mesh(MeshConfig(pp=s), devices=topo.devices)
    loss = pipeline_stream(
        lambda p, x: jnp.tanh(x @ p["w"]),
        lambda aux, y, tgt: jnp.mean((y * aux - tgt) ** 2), mesh)
    rep = NamedSharding(mesh, P())

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, np.float32, sharding=rep)
    text = jax.jit(jax.grad(loss, argnums=2)).lower(
        {"w": arg(s, d, d)}, arg(), arg(m, mb, d), arg(m, mb, d)
    ).compile().as_text()
    in_loop = [ln for ln in text.splitlines() if "all-reduce(" in ln
               and "transpose(jvp())/shard_map/while/body" in ln]
    assert in_loop, "the backward psum left the tick loop"

