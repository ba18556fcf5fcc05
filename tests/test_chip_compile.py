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


def _pool_rows(nb, bs, kv_heads, head_dim, dtype, sharding):
    """One layer's pool as the cache lays it out, as a shape."""
    from paddle_tpu.kernels.paged_attention import head_lanes
    return jax.ShapeDtypeStruct((nb, bs, kv_heads * head_lanes(head_dim)),
                                dtype, sharding=sharding)


@pytest.mark.parametrize("heads,kv_heads,head_dim,mixed", [
    (16, 16, 64, False),     # GPT-2 medium: bf16 MHA at head_dim 64
    (16, 16, 64, True),
    (4, 4, 64, False),       # its tp=4 per-shard slice
    (4, 4, 64, True),
    (32, 4, 128, False),     # GQA
    (20, 20, 64, False),     # GPT-2 large: rows of 2,560 lanes
    (20, 20, 64, True),
], ids=["mha16x64-fp", "mha16x64-int8_mixed", "tp4_slice-fp",
        "tp4_slice-int8_mixed", "gqa32_4x128-fp", "mha20x64-fp",
        "mha20x64-int8_mixed"])
def test_ragged_kernel_compiles_for_v5e(one_chip, heads, kv_heads, head_dim,
                                        mixed):
    """The ragged kernel at the span its pool's shape gives (8 blocks a
    cell at 16 and 20 heads, 32 on a tp=4 slice, 4 at GQA's 1,024
    lanes): every block of the span an operand of its own."""
    from paddle_tpu.kernels.paged_attention import (head_lanes,
                                                    ragged_paged_attention,
                                                    ragged_span)
    # the engine's default step: 512-token chunk budget + 8 decode rows,
    # tile_q 8, block 16, max_len 1024
    t, tq, bs, nb, mb, rows, nq = 576, 8, 16, 2048, 64, 9, 256
    assert ragged_span(bs, kv_heads * head_lanes(head_dim), 2, mb) == {
        16: 8, 20: 8, 4: 32, 32: 16}[heads]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = [s((t, heads, head_dim), jnp.bfloat16),
            _pool_rows(nb, bs, kv_heads, head_dim, jnp.bfloat16, one_chip),
            s((rows, mb), jnp.int32), s((rows,), jnp.int32),
            s((rows,), jnp.int32), s((t // tq,), jnp.int32),
            s((t // tq,), jnp.int32)]
    if mixed:
        args += [_pool_rows(nq, bs, kv_heads, head_dim, jnp.int8, one_chip),
                 s((nq,), jnp.float32), s((nq,), jnp.float32)]

    def fn(q, kv, bt, cl, qs, tr, to, kvq=None, ks=None, vs=None):
        return ragged_paged_attention(
            q, kv, bt, cl, qs, tr, to, use_kernel=True, interpret=False,
            groups=heads // kv_heads, kvq_pool=kvq, k_scales=ks,
            v_scales=vs)
    assert _compiles_with_kernel(fn, *args)


def _pool_sized_copies(text: str, pool) -> list:
    """chip_smoke.py's reading of a compiled program (it prints the same
    count on the real chip): the `copy` instructions whose result has
    a pool's element count, each a whole-pool relayout."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke
    return chip_smoke.pool_sized_copies(text, pool.size)


def _holds_the_picks(compiled, batch, vocab, dtype, sharding=None):
    """The step's first output is (logits, log-sum-exp, best id, its
    logit) at [batch, spec_len 1] rows: the logits in the head's own
    dtype, the three numbers a row in 4 bytes each (under tensor
    parallelism every one of them whole on every chip)."""
    picks, *_ = compiled.out_info
    assert [(x.shape, x.dtype) for x in picks] == [
        ((batch, 1, vocab), dtype), ((batch, 1), jnp.float32),
        ((batch, 1), jnp.int32), ((batch, 1), jnp.float32)]
    if sharding is not None:
        assert all(x.sharding.is_equivalent_to(sharding, x.ndim)
                   for x in picks)


@pytest.mark.parametrize("heads,kv_heads,head_dim,blocks,batch,compress", [
    (16, 16, 64, 3072, 32, 0),    # gpt2m-chat's pool
    (20, 20, 64, 1280, 16, 0),    # gpt2l-docs's pool
    (4, 4, 64, 3072, 32, 0),      # medium's tp=4 per-chip slice
    (5, 5, 64, 1280, 16, 0),      # large's: 5 heads x 128 lanes a chip
    (32, 4, 128, 2048, 8, 0),     # GQA, 256 lanes a head
    (16, 16, 64, 3072, 32, 256),  # with the int8 tier's pools beside
], ids=["gpt2m_chat", "gpt2l_docs", "gpt2m_tp4_slice", "gpt2l_tp4_slice",
        "gqa32_4x128", "gpt2m_chat-int8_mixed"])
def test_engine_step_updates_the_pool_in_place(one_chip, heads, kv_heads,
                                               head_dim, blocks, batch,
                                               compress):
    """The engine's real step, compiled for the described chip at the
    serving cells' pool sizes (two layers, so it compiles in seconds):
    the pools are aliased to the step's outputs and no whole-pool
    relayout is in the program. A [blocks, 16, heads, 64] pool cost two
    transposes through a padded temporary per pool per step (96 copies
    in gpt2m-chat's 24 layers, 144 in gpt2l-docs's 36), donated or
    not; this is the guard against their return."""
    from unittest import mock

    from paddle_tpu.engine.engine import ServeEngine
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.transformer import CausalLM

    model = CausalLM(vocab=512, model_dim=heads * head_dim, num_heads=heads,
                     num_kv_heads=kv_heads, num_layers=2,
                     ffn_dim=2 * heads * head_dim, dropout=0.0, max_len=1024,
                     dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    # the engine itself lives on the CPU with a few blocks: the step's
    # program depends on the pools' sizes only through its operands
    eng = ServeEngine(model,
                      jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                                   shapes),
                      max_batch_size=batch, block_size=16, num_blocks=8,
                      max_prefill_tokens=512, tile_q=8,
                      kv_compress_blocks=compress)

    def on_chip(x, lead=None):
        return jax.ShapeDtypeStruct(
            ((lead or x.shape[0]),) + x.shape[1:], x.dtype,
            sharding=one_chip)
    t, nt, b = eng.flat_tokens, eng.num_tiles, eng.max_batch_size

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    pools = [on_chip(x, blocks) for x in eng.cache.pools]
    # this process sees the CPU and the dispatcher would take its XLA
    # tier: steered here, in the test, to the tier the chip takes
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = eng._step_fn.lower(
            jax.tree.map(on_chip, eng.variables), i32(t), i32(t), pools,
            jax.tree.map(on_chip, eng.cache.qpools),
            jax.tree.map(on_chip, eng.cache.qscales),
            i32(b + 1, eng.max_blocks_per_seq), i32(b + 1), i32(b + 1),
            i32(nt), i32(nt), i32(t), i32(b, eng.spec_len)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _pool_sized_copies(text, pools[0]) == []
    _holds_the_picks(compiled, batch, 512, jnp.bfloat16)
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


@pytest.mark.parametrize("heads,blocks,batch,mode", [
    (16, 3072, 32, "fp"),       # medium over four chips: 4 heads a chip
    (20, 1280, 16, "int8"),     # large: 5 heads x 128 lanes a chip
], ids=["gpt2m-fp_allreduce", "gpt2l-int8_allreduce"])
def test_tp4_step_updates_its_pool_shards_in_place(topo, heads, blocks,
                                                   batch, mode):
    """The same guard for the tensor-parallel step: the engine's own
    `compile_steps` over a mesh of the four described chips. Each chip's
    shard of every pool (its heads' lanes) is aliased, and no chip
    relays its shard out."""
    from unittest import mock

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.transformer import CausalLM
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    from paddle_tpu.parallel.serve_collective import ServeTP

    mesh = make_mesh(MeshConfig(tp=4), devices=topo.devices)
    model = CausalLM(vocab=512, model_dim=heads * 64, num_heads=heads,
                     num_layers=2, ffn_dim=128 * heads, dropout=0.0,
                     max_len=1024, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    step, _ = compile_steps(model, shapes, False, ServeTP(mesh, 4, mode=mode),
                            ["paged"] * 2)
    t = 512 + batch * 8

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    pools = [_pool_rows(blocks, 16, heads, 64, jnp.bfloat16,
                        NamedSharding(mesh, P(None, None, "tp")))] * 2
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            shapes, i32(t), i32(t), pools, [], [], i32(batch + 1, 64),
            i32(batch + 1), i32(batch + 1), i32(t // 8), i32(t // 8), i32(t),
            i32(batch, 1)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    _holds_the_picks(compiled, batch, 512, jnp.bfloat16,
                     NamedSharding(mesh, P()))
    shard = jax.ShapeDtypeStruct((blocks, 16, pools[0].shape[2] // 4),
                                 jnp.bfloat16)
    assert _pool_sized_copies(text, shard) == []
    assert _pool_sized_copies(text, pools[0]) == []
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * shard.size * 2)


def test_latent_ragged_kernel_compiles_for_v5e(one_chip):
    """The latent row at `glm47f-docs8k`'s shapes alone (the whole step
    is below): 20 heads over one 640-lane row a token, blocks of 128,
    a table of 72, so four blocks and 512 keys a cell."""
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    ragged_span)
    t, tq, heads, bs, nb, mb, rows = 1152, 8, 20, 128, 2048, 72, 17
    assert ragged_span(bs, 640, 2, mb) == 4

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(*args):
        return ragged_paged_attention(
            *args, use_kernel=True, interpret=False, groups=heads,
            value_lanes=(0, 512), scale=0.1)
    text = jax.jit(fn).lower(
        s((t, heads, 576), jnp.bfloat16), s((nb, bs, 640), jnp.bfloat16),
        s((rows, mb)), s((rows,)), s((rows,)), s((t // tq,)), s((t // tq,))
    ).compile().as_text()
    assert "ragged_latent_attention" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("rows,d,f,experts", [
    (2048, 2048, 1792, 32),     # lfm2-reason: 512 flat rows x 4
    (4608, 2048, 1536, 64),     # glm47f-docs8k: 1,152 flat rows x 4
], ids=["lfm2_reason", "glm47f_docs8k"])
def test_grouped_product_compiles_for_v5e(one_chip, rows, d, f, experts):
    """The experts' two grouped-product calls at the expert cells'
    shapes, under the `moe_experts` scope as `RoutedExperts` makes
    them: each call's instruction is named and lies under the scope
    (`benchmarks/scope_reduce.py`, which `moe_roofline_pct` reads), and
    the program holds no grouped product of the compiler's own."""
    import sys
    from unittest import mock

    from paddle_tpu.kernels import grouped_product, paged_attention
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.scope_reduce import scopes_of

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(x, gate, up, down, counts):
        with jax.named_scope("moe_experts"):
            h = grouped_product.gated_grouped_product(x, gate, up, counts)
            return grouped_product.grouped_product(h, down, counts)
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        text = jax.jit(fn).lower(
            s((rows, d)), s((experts, d, f)), s((experts, d, f)),
            s((experts, f, d)), s((experts,), jnp.int32)).compile().as_text()
    calls = [ln.split("=")[0].split()[-1].lstrip("%")
             for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert sorted(c.split(".")[0] for c in calls) == [
        "grouped_gate_up", "grouped_product"]
    under = scopes_of(text, ["moe_experts"])
    assert all(under.get(c) == "moe_experts" for c in calls), calls
    assert "ragged-dot" not in text


@pytest.mark.parametrize("shape,grad,kernels", [
    ((1, 1024, 16, 64), False, ("flash_fwd",)),
    ((1, 1024, 16, 64), True, ("flash_fwd", "flash_bwd")),
    # gpt2m-train-1k's own shape: the backward is the one pass
    ((8, 1024, 16, 64), True, ("flash_fwd", "flash_bwd")),
    # the longest head the one pass takes (ONE_PASS_VMEM_BYTES)
    ((1, 4096, 8, 64), True, ("flash_fwd", "flash_bwd")),
    # a length no block divides: one 1,024 block, padded, in strips
    ((1, 1000, 8, 64), True, ("flash_fwd", "flash_bwd")),
    # lm_longctx's: a head's dq does not fit in VMEM, so two kernels
    ((1, 16384, 8, 64), True, ("flash_fwd", "flash_dq", "flash_dkv")),
], ids=["fwd", "grad", "grad-train_cell", "grad-4k", "grad-1000",
        "grad-16k"])
def test_flash_kernel_compiles_for_v5e(one_chip, shape, grad, kernels):
    """The flash kernels at real widths, and the rule that picks the
    backward's schedule from the shapes alone: the compiled text names
    the Pallas calls that ran."""
    from paddle_tpu.kernels.flash import flash_attention
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    text = jax.jit(fn).lower(q, q, q).compile().as_text()
    assert "tpu_custom_call" in text
    for name in ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkv"):
        assert (name in text) == (name in kernels), name


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



def _experts_are_the_kernel(text, compiled, cfg):
    """The expert layers' products are the two grouped-product calls of
    `kernels/grouped_product.py`, not the compiler's grouped product,
    and the step's temporaries hold no buffer of an expert matrix's
    size: no expert's weights are copied (concatenated, cast or laid
    out anew) on their way to the kernel."""
    assert "grouped_gate_up" in text and "grouped_product" in text
    assert "ragged-dot" not in text
    args = cfg["constructor_args"]
    matrix = (cfg[args["num_experts"]] * cfg[args["model_dim"]]
              * cfg[args["expert_dim"]] * 2)
    assert compiled.memory_analysis().temp_size_in_bytes < matrix


def test_latent_expert_step_at_its_cell_sizes(one_chip):
    """The engine's step over a latent pool with routed experts,
    compiled for the described chip at `glm47f-docs8k`'s own sizes (all
    seven layers, 64 experts, the whole vocabulary, 2,048 blocks of 128
    latent rows; shapes only): the latent ragged kernel is accepted,
    the pools are aliased to the step's output, no whole-pool relayout
    is in the program, and everything the step holds fits the chip with
    room for the allocator (under 15 GB of 16)."""
    import json
    from unittest import mock

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.latent_moe import LatentMoELM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        cfg = json.load(f)
    model = LatentMoELM(
        dtype=jnp.bfloat16,
        **{k: cfg[v] for k, v in cfg["constructor_args"].items()})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    s = cfg["serve"]
    tq, b = s["tile_q"], s["max_batch_size"]
    # ServeEngine's sizing of its flat step, without an engine: one
    # would hold 9 GB of weights
    t = -(-s["max_prefill_tokens"] // tq) * tq + b * tq
    mb = -(-s["max_seq_len"] // s["block_size"])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    row = model.blocks[0].attn.latent_row
    pool = jax.ShapeDtypeStruct(
        (s["num_blocks"], s["block_size"],
         paged_attention.latent_lanes(row[0])),
        jnp.bfloat16, sharding=one_chip)
    assert pool.shape[-1] == 640
    pools = [pool] * len(model.blocks)
    step, _ = compile_steps(model, shapes, False, None,
                            ["paged"] * len(pools))
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            jax.tree.map(on_chip, shapes), i32(t), i32(t), pools, [], [],
            i32(b + 1, mb), i32(b + 1), i32(b + 1), i32(t // tq),
            i32(t // tq), i32(t), i32(b, 1)).compile()
    text = compiled.as_text()
    assert "ragged_latent_attention" in text and "tpu_custom_call" in text
    _experts_are_the_kernel(text, compiled, cfg)
    assert _pool_sized_copies(text, pool) == []
    # an untied float32 head: 9.9 MB of logits that stay put
    _holds_the_picks(compiled, b, cfg["vocab_size"], jnp.float32)
    mem = compiled.memory_analysis()
    pool_bytes = len(pools) * pool.size * pool.dtype.itemsize
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"glm47f-docs8k step: {total} bytes compiled, "
          f"{mem.temp_size_in_bytes} of them temporaries")
    assert 0.25 * 16e9 < total < 15e9, total


def test_scan_kernel_compiles_for_v5e(one_chip):
    """The ragged selective scan at `phi4mf-reason`'s step: 512 flat
    positions in 64 tiles, 5,120 channels of 16 states, 33 slots, the
    state donated and aliased to the kernel's output."""
    from paddle_tpu.kernels.selective_scan import ragged_selective_scan

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    t, dn, n, nt, slots = 512, 5120, 16, 64, 33
    args = [s((t, dn), jnp.bfloat16), s((t, dn), jnp.float32),
            s((n, dn), jnp.float32), s((t, n), jnp.float32),
            s((t, n), jnp.float32), s((dn,), jnp.float32),
            s((slots, n, dn), jnp.float32), s((nt,), jnp.int32),
            s((nt,), jnp.int32), s((nt,), jnp.int32)]

    def fn(*a):
        return ragged_selective_scan(*a, use_kernel=True, interpret=False)
    compiled = jax.jit(fn, donate_argnums=(6,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged_selective_scan" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        slots * n * dn * 4


@pytest.mark.parametrize("window,blocks", [(512, 225), (None, 1024)],
                         ids=["window_ring", "whole_context"])
def test_pair_row_ragged_kernel_compiles_for_v5e(one_chip, window, blocks):
    """The ragged kernel as a differential-attention layer calls it: 40
    query heads over 10 key pairs of 128 + 128 (rows of 2,560 lanes,
    groups 4, one block of 128 rows a cell), with the 512 window over a
    ring pool and without over the paged one."""
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    ragged_span)
    t, tq, bs, mb, rows = 512, 8, 128, 27, 33
    assert ragged_span(bs, 2560, 2, mb) == 1

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = [s((t, 40, 128), jnp.bfloat16),
            _pool_rows(blocks, bs, 10, 128, jnp.bfloat16, one_chip),
            s((rows, mb), jnp.int32), s((rows,), jnp.int32),
            s((rows,), jnp.int32), s((t // tq,), jnp.int32),
            s((t // tq,), jnp.int32)]

    def fn(q, kv, bt, cl, qs, tr, to):
        return ragged_paged_attention(
            q, kv, bt, cl, qs, tr, to, use_kernel=True, interpret=False,
            groups=4, scale=0.125, window=window,
            name="ragged_diff_attention")
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "ragged_diff_attention" in text


def test_hybrid_step_at_its_cell_sizes(one_chip):
    """The engine's step over the three kinds of cache, compiled for the
    described chip at `phi4mf-reason`'s own sizes (all 32 layers, the
    whole vocabulary, 1,024 paged blocks, 32 rings of 7 blocks in each
    of eight window pools, 33 slots of state; shapes only): both
    kernels are accepted, every pool and state array is aliased to the
    step's output, no copy of a pool's or a state's size is in the
    program, the three scopes are in its text, and everything the step
    holds fits the chip (about 9.8 GB of 16)."""
    import json
    from unittest import mock

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.engine.paged_cache import CacheLayout
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.hybrid_lm import HybridLM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        cfg = json.load(f)
    model = HybridLM(
        dtype=jnp.bfloat16,
        **{k: cfg[v] for k, v in cfg["constructor_args"].items()})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    s = cfg["serve"]
    tq, b = s["tile_q"], s["max_batch_size"]
    t = -(-s["max_prefill_tokens"] // tq) * tq + b * tq
    mb = -(-s["max_seq_len"] // s["block_size"])
    layout = CacheLayout(model.cache_layout, s["block_size"], b,
                         s["max_prefill_tokens"])
    assert layout.ring_blocks == 7
    heads, head_dim = model.kv_row
    arrays = layout.arrays(
        (s["num_blocks"], s["block_size"],
         heads * paged_attention.head_lanes(head_dim)), jnp.bfloat16)
    kinds = [kind for kind, _, _ in arrays]
    assert (kinds.count("paged"), kinds.count("window"),
            kinds.count("state")) == (1, 8, 18)
    pools = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for _, shape, dtype in arrays]

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step, _ = compile_steps(model, shapes, False, None, kinds)
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            jax.tree.map(on_chip, shapes), i32(t), i32(t), pools, [], [],
            i32(b + 1, mb), i32(b + 1), i32(b + 1), i32(t // tq),
            i32(t // tq), i32(t), i32(b, 1)).compile()
    text = compiled.as_text()
    for name in ("tpu_custom_call", "ragged_selective_scan",
                 "ragged_diff_attention", "ssm_scan", "gated_memory",
                 "diff_attention"):
        assert name in text, name
    for size in sorted({p.size for p in pools[:-1]}):
        assert _pool_sized_copies(text, jax.ShapeDtypeStruct(
            (size,), jnp.int8)) == [], size
    # the tied head's logits are float32 here: 25.6 MB that stay put
    _holds_the_picks(compiled, b, cfg["vocab_size"], jnp.float32)
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= held > 1.9e9
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0.25 * 16e9 < total < 15e9, total


def test_lightning_kernel_compiles_for_v5e(one_chip):
    """Ragged lightning attention at `sala-docs32k`'s step: 512 flat
    positions in 64 tiles, 32 heads of 128 side by side in the lanes, 33
    slots of [32, 128, 128] float32, the state donated and aliased to
    the kernel's output."""
    from paddle_tpu.kernels.lightning_attention import \
        ragged_lightning_attention

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    t, h, d, nt, slots = 512, 32, 128, 64, 33
    args = [s((t, h, d), jnp.float32)] * 3 + [
        s((h,), jnp.float32), s((slots, h, d, d), jnp.float32)] \
        + [s((nt,), jnp.int32)] * 4

    def fn(*a):
        return ragged_lightning_attention(*a, use_kernel=True,
                                          interpret=False)
    compiled = jax.jit(fn, donate_argnums=(4,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged_lightning_attention" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        slots * h * d * d * 4


def test_block_mask_ragged_kernel_compiles_for_v5e(one_chip):
    """The ragged kernel as a block-sparse layer calls it, a kv head a
    call: 16 query heads over one kv head of 128 (rows of 256 lanes,
    blocks of 64, eight a cell), a table of 520 entries a row in SMEM
    and a block mask a query."""
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    ragged_span)
    t, tq, bs, mb, rows, nb = 512, 8, 64, 520, 33, 6144
    assert ragged_span(bs, 256, 2, mb) == 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = [s((t, 16, 128), jnp.bfloat16),
            _pool_rows(nb, bs, 1, 128, jnp.bfloat16, one_chip),
            s((rows, mb), jnp.int32), s((rows,), jnp.int32),
            s((rows,), jnp.int32), s((t // tq,), jnp.int32),
            s((t // tq,), jnp.int32), s((t, mb), jnp.bool_)]

    def fn(q, kv, bt, cl, qs, tr, to, mask):
        return ragged_paged_attention(
            q, kv, bt, cl, qs, tr, to, use_kernel=True, interpret=False,
            groups=16, block_mask=mask, name="ragged_sparse_attention")
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "ragged_sparse_attention" in text


def test_sparse_linear_step_keeps_pools_and_state_in_place(one_chip):
    """The engine's step over paged pools a kv head, index pools and
    lightning state, compiled for the described chip at
    `sala-docs32k`'s widths and pool sizes with two layers (one of each
    kind; shapes only): both kernels are in it, every pool and state
    array is aliased to the step's output, and no copy of a pool's, an
    index pool's or a state's size is in the program (a gather of part
    of a row once laid each paged pool out anew, 1.6 GB a step)."""
    import json
    from unittest import mock

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.engine.paged_cache import CacheLayout
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.sparse_linear_lm import SparseLinearLM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, mixer_types=["lightning-attn", "minicpm4"],
               num_hidden_layers=2, vocab_size=4096)
    model = SparseLinearLM(
        dtype=jnp.bfloat16,
        **{k: cfg[v] for k, v in cfg["constructor_args"].items()})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    s = cfg["serve"]
    tq, b, bs = s["tile_q"], s["max_batch_size"], s["block_size"]
    t = -(-s["max_prefill_tokens"] // tq) * tq + b * tq
    mb = -(-s["max_seq_len"] // bs)
    layout = CacheLayout(model.cache_layout, bs, b, s["max_prefill_tokens"])
    described = layout.arrays(
        (s["num_blocks"], bs, paged_attention.head_lanes(cfg["head_dim"])),
        jnp.bfloat16)
    kinds = [kind for kind, _, _ in described]
    assert kinds == ["state", "paged", "paged", "index", "rows"]
    pools = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for _, shape, dtype in described]

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step, _ = compile_steps(model, shapes, False, None, kinds)
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            jax.tree.map(on_chip, shapes), i32(t), i32(t), pools, [], [],
            i32(b + 1, mb), i32(b + 1), i32(b + 1), i32(t // tq),
            i32(t // tq), i32(t), i32(b, 1)).compile()
    text = compiled.as_text()
    assert "ragged_sparse_attention" in text
    assert "ragged_lightning_attention" in text
    for pool in pools[:-1]:
        assert _pool_sized_copies(text, pool) == [], pool.shape
    held = sum(p.size * p.dtype.itemsize for p in pools[:-1])
    assert compiled.memory_analysis().alias_size_in_bytes >= held


def test_ssd_kernel_compiles_for_v5e(one_chip):
    """The ragged SSD scan at `falconh1-reason`'s step: 512 flat
    positions in 64 tiles, 32 heads of 128 in two groups of keys and
    queries of 256, 33 slots of [32, 256, 128] float32, two grid cells a
    tile (a group's 16 heads each), the state donated and aliased."""
    from paddle_tpu.kernels.lightning_attention import ragged_ssd

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    t, h, p, g, n, nt, slots = 512, 32, 128, 2, 256, 64, 33
    args = [s((t, h, p), jnp.float32), s((t, h), jnp.float32),
            s((h,), jnp.float32), s((t, g, n), jnp.float32),
            s((t, g, n), jnp.float32), s((h,), jnp.float32),
            s((slots, h, n, p), jnp.float32)] + [s((nt,), jnp.int32)] * 4

    def fn(*a):
        return ragged_ssd(*a, use_kernel=True, interpret=False)
    compiled = jax.jit(fn, donate_argnums=(6,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged_ssd" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        slots * h * n * p * 4


def test_parallel_hybrid_step_at_its_cell_sizes(one_chip):
    """The engine's step over layers that each keep a paged pool AND
    state slots, compiled for the described chip at `falconh1-reason`'s
    own sizes (all 6 layers, the whole vocabulary of 261,120, 1,024
    blocks of 128, 33 slots; shapes only): the ragged paged kernel and
    the SSD kernel are in it with both named scopes, every pool and
    state array is aliased to the step's output, no copy of a pool's or
    a state's size is in the program, and everything the step holds
    fits the chip."""
    import json
    from unittest import mock

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.engine.paged_cache import CacheLayout
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.parallel_hybrid_lm import ParallelHybridLM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        cfg = json.load(f)
    model = ParallelHybridLM(
        dtype=jnp.bfloat16,
        **{k: cfg[v] for k, v in cfg["constructor_args"].items()})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    s = cfg["serve"]
    tq, b = s["tile_q"], s["max_batch_size"]
    t = -(-s["max_prefill_tokens"] // tq) * tq + b * tq
    mb = -(-s["max_seq_len"] // s["block_size"])
    layout = CacheLayout(model.cache_layout, s["block_size"], b,
                         s["max_prefill_tokens"])
    heads, head_dim = model.kv_row
    arrays = layout.arrays(
        (s["num_blocks"], s["block_size"],
         heads * paged_attention.head_lanes(head_dim)), jnp.bfloat16)
    kinds = [kind for kind, _, _ in arrays]
    assert kinds == ["paged", "state", "state"] * 6 + ["rows"]
    pools = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for _, shape, dtype in arrays]
    assert [p.shape for p in pools[:3]] == [
        (1024, 128, 1024), (33, 32, 256, 128), (33, 3 * 5120)]

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step, _ = compile_steps(model, shapes, False, None, kinds)
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            jax.tree.map(on_chip, shapes), i32(t), i32(t), pools, [], [],
            i32(b + 1, mb), i32(b + 1), i32(b + 1), i32(t // tq),
            i32(t // tq), i32(t), i32(b, 1)).compile()
    text = compiled.as_text()
    for name in ("tpu_custom_call", "ragged_ssd", "ragged_paged_attention",
                 "ssd_scan", "parallel_mixer"):
        assert name in text, name
    for size in sorted({p.size for p in pools[:-1]}):
        assert _pool_sized_copies(text, jax.ShapeDtypeStruct(
            (size,), jnp.int8)) == [], size
    _holds_the_picks(compiled, b, cfg["vocab_size"], jnp.float32)
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= held > 2.4e9
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"falconh1-reason step: {total} bytes compiled, "
          f"{mem.temp_size_in_bytes} of them temporaries")
    assert 0.25 * 16e9 < total < 15.5e9, total


def test_conv_moe_step_at_its_cell_sizes(one_chip):
    """The engine's step over attention layers that keep a paged pool
    and conv layers that keep a state slot, with routed experts in 12 of
    its 13 layers, compiled for the described chip at `lfm2-reason`'s
    own sizes (the whole vocabulary of 65,536, 32 experts of 1,792,
    1,024 blocks of 128, 33 slots; shapes only): the ragged paged kernel
    and both named scopes are in it, every pool and state array is
    aliased to the step's output, no copy of a pool's or a state's size
    is in the program, the tokens per expert come out [12, 32], and
    everything the step holds fits the chip."""
    import json
    from unittest import mock

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.engine.paged_cache import CacheLayout
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.conv_moe_lm import ConvMoELM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    model = ConvMoELM(
        dtype=jnp.bfloat16,
        **{k: cfg[v] for k, v in cfg["constructor_args"].items()})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    s = cfg["serve"]
    tq, b = s["tile_q"], s["max_batch_size"]
    t = -(-s["max_prefill_tokens"] // tq) * tq + b * tq
    mb = -(-s["max_seq_len"] // s["block_size"])
    layout = CacheLayout(model.cache_layout, s["block_size"], b,
                         s["max_prefill_tokens"])
    heads, head_dim = model.kv_row
    arrays = layout.arrays(
        (s["num_blocks"], s["block_size"],
         heads * paged_attention.head_lanes(head_dim)), jnp.bfloat16)
    kinds = [kind for kind, _, _ in arrays]
    assert kinds == ["state", "paged"] + ["state"] * 3 + ["paged"] + [
        "state"] * 3 + ["paged"] + ["state"] * 3 + ["rows"]
    pools = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for _, shape, dtype in arrays]
    assert [p.shape for p in pools[:2]] == [(33, 2 * 2048),
                                            (1024, 128, 1024)]

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step, _ = compile_steps(model, shapes, False, None, kinds)
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            jax.tree.map(on_chip, shapes), i32(t), i32(t), pools, [], [],
            i32(b + 1, mb), i32(b + 1), i32(b + 1), i32(t // tq),
            i32(t // tq), i32(t), i32(b, 1)).compile()
    text = compiled.as_text()
    for name in ("tpu_custom_call", "ragged_paged_attention", "short_conv",
                 "moe_experts"):
        assert name in text, name
    _experts_are_the_kernel(text, compiled, cfg)
    for size in sorted({p.size for p in pools[:-1]}):
        assert _pool_sized_copies(text, jax.ShapeDtypeStruct(
            (size,), jnp.int8)) == [], size
    _holds_the_picks(compiled, b, cfg["vocab_size"], jnp.float32)
    *_, per_expert = compiled.out_info
    assert (per_expert.shape, per_expert.dtype) == ((12, 32), jnp.int32)
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= held > 0.8e9
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"lfm2-reason step: {total} bytes compiled, "
          f"{mem.temp_size_in_bytes} of them temporaries")
    assert 0.25 * 16e9 < total < 15.5e9, total


def test_window_moe_step_at_its_cell_sizes(one_chip):
    """The engine's step over window layers that keep a ring a slot and
    a full layer that keeps a paged pool, with one chip's share of an
    expert-parallel expert layer in four of its five layers, compiled
    for the described chip at `kexaone-reason`'s own sizes (64 query
    heads over 8 of 128, 16 held experts of 2,048 behind a router over
    128, the 19,200-id slice, 1,024 blocks of 128, rings of 4 blocks in
    33 slots; shapes only): both named ragged calls and the expert
    scope are in it, every pool and ring is aliased to the step's
    output, no copy of a pool's or a ring's size is in the program, the
    tokens per held expert come out [4, 16 + 1] (the last column the
    pairs sent away), and everything the step holds fits the chip."""
    import json
    from unittest import mock

    from paddle_tpu.engine.engine import compile_steps
    from paddle_tpu.engine.paged_cache import CacheLayout
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.models.window_moe_lm import WindowMoELM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    model = WindowMoELM(
        dtype=jnp.bfloat16,
        **{k: cfg[v] for k, v in cfg["constructor_args"].items()})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    s = cfg["serve"]
    tq, b = s["tile_q"], s["max_batch_size"]
    t = -(-s["max_prefill_tokens"] // tq) * tq + b * tq
    mb = -(-s["max_seq_len"] // s["block_size"])
    layout = CacheLayout(model.cache_layout, s["block_size"], b,
                         s["max_prefill_tokens"])
    assert layout.ring_blocks == 4
    heads, head_dim = model.kv_row
    arrays = layout.arrays(
        (s["num_blocks"], s["block_size"],
         heads * paged_attention.head_lanes(head_dim)), jnp.bfloat16)
    kinds = [kind for kind, _, _ in arrays]
    assert kinds == ["window"] * 3 + ["paged", "window", "rows"]
    pools = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for _, shape, dtype in arrays]
    assert [p.shape for p in pools[2:4]] == [(1 + 32 * 4, 128, 2048),
                                             (1024, 128, 2048)]

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step, _ = compile_steps(model, shapes, False, None, kinds)
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        compiled = step.lower(
            jax.tree.map(on_chip, shapes), i32(t), i32(t), pools, [], [],
            i32(b + 1, mb), i32(b + 1), i32(b + 1), i32(t // tq),
            i32(t // tq), i32(t), i32(b, 1)).compile()
    text = compiled.as_text()
    for name in ("tpu_custom_call", "ragged_gqa_window", "ragged_gqa_full",
                 "moe_experts"):
        assert name in text, name
    _experts_are_the_kernel(text, compiled, cfg)
    for size in sorted({p.size for p in pools[:-1]}):
        assert _pool_sized_copies(text, jax.ShapeDtypeStruct(
            (size,), jnp.int8)) == [], size
    _holds_the_picks(compiled, b, cfg["vocab_size"], jnp.float32)
    *_, per_expert = compiled.out_info
    assert (per_expert.shape, per_expert.dtype) == ((4, 17), jnp.int32)
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= held > 0.8e9
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"kexaone-reason step: {total} bytes compiled, "
          f"{mem.temp_size_in_bytes} of them temporaries")
    assert 0.25 * 16e9 < total < 15.5e9, total
