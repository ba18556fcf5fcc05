"""Tensor-parallel serving tests (parallel/serve_collective.py +
engine tp_size): the quantized decode collective against exact psum,
wire-byte accounting, pool_shape/divisibility validation at
construction, tp=1 ≡ legacy identity, tp=2 CPU-mesh parity (fp mode
byte-identical token streams; int8 within quantization tolerance and
always complete), speculative decoding / COW forks / host-tier revival
each unchanged under tp=2, the one-compile invariant with the sharded
step, and the graftlint gate on every file this feature touches.

conftest forces 8 virtual CPU devices, so a tp=2 mesh is always
available under the suite.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.engine.paged_cache import PagedKVCache
from paddle_tpu.models.transformer import CausalLM
from paddle_tpu.obs.metrics import MetricsRegistry
from paddle_tpu.parallel import MeshConfig, make_mesh
from paddle_tpu.parallel import serve_collective as sc

pytestmark = [
    pytest.mark.serve,
    pytest.mark.skipif(jax.device_count() < 2,
                       reason="tp tests need >= 2 devices"),
]

VOCAB = 61


@pytest.fixture(scope="module")
def model_and_vars():
    # GQA on purpose: 4 query heads over 2 kv heads, so tp=2 exercises
    # the shard-local grouping (1 kv head + 2 q heads per chip).
    model = CausalLM(vocab=VOCAB, model_dim=32, num_heads=4,
                     num_layers=2, ffn_dim=64, dropout=0.0, max_len=64,
                     num_kv_heads=2)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables, mode=None, **kw):
    """Build a ServeEngine with the allreduce mode pinned for the
    duration of construction (the engine reads PTPU_SERVE_ALLREDUCE
    host-side exactly once, at construction)."""
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_prefill_tokens", 32)
    kw.setdefault("tile_q", 4)
    kw.setdefault("registry", MetricsRegistry())
    prev = os.environ.get("PTPU_SERVE_ALLREDUCE")
    if mode is not None:
        os.environ["PTPU_SERVE_ALLREDUCE"] = mode
    try:
        return ServeEngine(model, variables, **kw)
    finally:
        if mode is not None:
            if prev is None:
                os.environ.pop("PTPU_SERVE_ALLREDUCE", None)
            else:
                os.environ["PTPU_SERVE_ALLREDUCE"] = prev


PROMPTS = [[7, 3, 7, 3, 11, 2], [1, 2, 3, 1, 2, 3, 1, 2],
           [5, 9, 2, 8], [4, 4, 4, 4, 4, 4, 4]]


# -- collective-level -------------------------------------------------------

class TestServeCollective:
    def test_resolve_mode(self, monkeypatch):
        monkeypatch.delenv("PTPU_SERVE_ALLREDUCE", raising=False)
        assert sc.resolve_mode() == "int8"
        monkeypatch.setenv("PTPU_SERVE_ALLREDUCE", "fp")
        assert sc.resolve_mode() == "fp"
        monkeypatch.setenv("PTPU_SERVE_ALLREDUCE", "bf8")
        with pytest.raises(ValueError):
            sc.resolve_mode()

    def test_int8_allreduce_close_to_psum(self):
        """The quantized collective is psum within per-chunk int8
        quantization error: |err| <= tp * chunk_absmax / 127 per
        element (each shard rounds once)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(2, 8, 320), jnp.float32)

        def body(mode):
            def f(x_):
                return sc.serve_all_reduce(x_, "tp", mode=mode, chunk=64)
            return shard_map(f, mesh=mesh, in_specs=(P("tp",),),
                             out_specs=P("tp",), check_vma=False)(x)

        exact = np.asarray(body("fp"))
        quant = np.asarray(body("int8"))
        np.testing.assert_allclose(exact, np.asarray(x).sum(0)[None]
                                   .repeat(2, 0), rtol=1e-6, atol=1e-6)
        # per-element bound from the per-chunk scale
        bound = 2.0 * np.abs(np.asarray(x)).max() / 127.0 + 1e-6
        assert np.max(np.abs(quant - exact)) <= bound

    def test_int8_allreduce_handles_ragged_and_zero_chunks(self):
        """Lengths not divisible by the chunk pad internally; an
        all-zero chunk must not divide by zero (scale floor)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
        x = np.zeros((2, 3, 37), np.float32)
        x[:, 0, :5] = [[1.0, -2.0, 0.5, 3.0, -0.25]] * 2

        def f(x_):
            return sc.quantized_all_reduce(x_, "tp", chunk=16)

        out = shard_map(f, mesh=mesh, in_specs=(P("tp",),),
                        out_specs=P("tp",), check_vma=False)(
                            jnp.asarray(x))
        out = np.asarray(out)
        assert np.all(np.isfinite(out))
        np.testing.assert_allclose(out[0], x.sum(0), atol=0.05)

    def test_wire_bytes_accounting(self):
        D = 512
        assert sc.allreduce_wire_bytes(D, "fp", 1) == 0
        assert sc.allreduce_wire_bytes(D, "int8", 1) == 0
        # fp ring: 2 * (tp-1)/tp * 4B * D
        assert sc.allreduce_wire_bytes(D, "fp", 2) == 2 * (1 / 2) * 4 * D
        # int8 all-gather: (tp-1) * (D payload + fp32 scale per chunk)
        assert sc.allreduce_wire_bytes(D, "int8", 2, chunk=256) == \
            1 * (D + 4 * D / 256)
        assert sc.allreduce_wire_bytes(D, "int8", 2) < \
            sc.allreduce_wire_bytes(D, "fp", 2)


# -- cache-level ------------------------------------------------------------

class TestPoolSharding:
    def test_pool_shape_divides_kv_heads(self):
        c = PagedKVCache(num_blocks=8, block_size=4, num_layers=1,
                         num_kv_heads=4, head_dim=8)
        # rows of kv_heads x head_lanes(8) = 128 lanes; tp divides the heads
        assert c.pool_shape() == (8, 4, 4 * 128)
        assert c.pool_shape(2) == (8, 4, 2 * 128)
        assert c.pools[0].shape == c.pool_shape()
        with pytest.raises(ValueError):
            c.pool_shape(3)
        with pytest.raises(ValueError):
            c.pool_shape(0)

    def test_ctor_rejects_indivisible_tp(self):
        with pytest.raises(ValueError, match="tp_size"):
            PagedKVCache(num_blocks=8, block_size=4, num_layers=1,
                         num_kv_heads=4, head_dim=8, tp_size=3)
        with pytest.raises(ValueError, match="tp_size"):
            PagedKVCache(num_blocks=8, block_size=4, num_layers=1,
                         num_kv_heads=4, head_dim=8, tp_size=0)

    def test_engine_rejects_indivisible_heads(self, model_and_vars):
        model, variables = model_and_vars
        with pytest.raises(ValueError):
            _engine(model, variables, tp_size=3)       # 4 heads % 3
        with pytest.raises(ValueError):
            _engine(model, variables,
                    tp_size=jax.device_count() * 2)    # too few devices


# -- engine-level parity ----------------------------------------------------

class TestTPParity:
    def test_tp1_is_legacy(self, model_and_vars):
        """tp_size=1 takes the exact single-device jit path: identical
        tokens to an engine built without the knob, no mesh attached."""
        model, variables = model_and_vars
        base = _engine(model, variables)
        tp1 = _engine(model, variables, tp_size=1)
        assert tp1._serve_tp is None and tp1._mesh is None
        assert tp1.generate(PROMPTS, max_new_tokens=10) == \
            base.generate(PROMPTS, max_new_tokens=10)

    def test_tp2_fp_token_identical(self, model_and_vars):
        """fp-mode tp=2 must reproduce the tp=1 token streams exactly:
        the logits differ in ulps but greedy argmax integer streams are
        the gate. The per-chip KV pool halves and the whole drain stays
        on the ONE sharded compiled step."""
        model, variables = model_and_vars
        ref = _engine(model, variables, mode="fp")
        eng = _engine(model, variables, mode="fp", tp_size=2)
        want = ref.generate(PROMPTS, max_new_tokens=12)
        got = eng.generate(PROMPTS, max_new_tokens=12)
        assert got == want
        assert eng._step_fn._cache_size() == 1
        assert eng.cache.per_chip_pool_bytes() * 2 == \
            ref.cache.per_chip_pool_bytes()
        assert eng.obs.get("ptpu_serve_tp_size").value == 2.0
        assert ref.obs.get("ptpu_serve_tp_size").value == 1.0
        eng.cache.assert_quiesced()

    def test_tp2_int8_completes_with_probe_observed(self, model_and_vars):
        """int8 mode: token streams may drift within quantization noise
        on a tiny model, so the gates are completion (every request
        emits the full budget or EOS), one compile, and the allreduce
        microprobe landing in the mode-labelled histogram."""
        model, variables = model_and_vars
        ref = _engine(model, variables, mode="fp")
        eng = _engine(model, variables, mode="int8", tp_size=2)
        want = ref.generate(PROMPTS, max_new_tokens=10)
        got = eng.generate(PROMPTS, max_new_tokens=10)
        assert [len(t) for t in got] == [len(t) for t in want]
        assert eng._step_fn._cache_size() == 1
        hist = eng.obs.get("ptpu_serve_allreduce_ms").children()
        assert ("int8",) in hist and hist[("int8",)].count >= 1
        # the frontend's warmup baseline reset must not wipe the
        # static-config series (a /metrics scrape after warmup still
        # shows the degree and the construction microprobe)
        eng.reset_stats()
        assert eng.obs.get("ptpu_serve_tp_size").value == 2.0
        hist = eng.obs.get("ptpu_serve_allreduce_ms").children()
        assert hist[("int8",)].count == 1
        eng.cache.assert_quiesced()


# -- engine features ride unchanged under tp=2 ------------------------------

class TestTPFeatureParity:
    def test_spec_decode_unchanged(self, model_and_vars):
        """Speculative decode under tp=2/fp equals the spec-off tp=2
        run token for token (lossless verification is orthogonal to
        the sharding)."""
        model, variables = model_and_vars
        prompts = [[1, 2, 3, 4, 5, 1, 2, 3, 4, 5, 1, 2, 3]]
        base = _engine(model, variables, mode="fp", tp_size=2)
        spec = _engine(model, variables, mode="fp", tp_size=2, spec_k=3)
        want = base.generate(prompts, max_new_tokens=14)
        got = spec.generate(prompts, max_new_tokens=14)
        assert got == want
        assert spec.obs.get("ptpu_spec_drafted_tokens_total").value > 0
        assert spec._step_fn._cache_size() == 1
        spec.cache.assert_quiesced()

    def test_cow_fork_unchanged(self, model_and_vars):
        """n=2 parallel sampling (COW fork through the sharded
        _copy_blocks jit) under tp=2/fp equals the tp=1 group run per
        candidate."""
        model, variables = model_and_vars
        prompt = [1, 2, 3, 1, 2, 3, 1, 2]
        ref = _engine(model, variables, mode="fp")
        rb = ref.add_request(list(prompt), max_new_tokens=12, n=2)
        res_ref = ref.run()
        eng = _engine(model, variables, mode="fp", tp_size=2)
        re_ = eng.add_request(list(prompt), max_new_tokens=12, n=2)
        res_tp = eng.run()
        assert res_tp[re_.req_id] == res_ref[rb.req_id]
        assert res_tp[re_.forks[0].req_id] == res_ref[rb.forks[0].req_id]
        assert eng._step_fn._cache_size() == 1
        eng.cache.assert_quiesced()

    def test_host_tier_revival_unchanged(self, model_and_vars):
        """A tight sharded pool preempts, demotes to the host tier and
        revives by DMA back into the SHARDED device pools; output must
        equal the roomy tp=2 run token for token."""
        model, variables = model_and_vars
        tails = [[21, 22, 23, 24], [31, 32, 33, 34], [41, 42, 43, 44]]
        prompts = [[7, 3, 7, 3] + t for t in tails]
        roomy = _engine(model, variables, mode="fp", tp_size=2,
                        max_batch_size=3)
        want = roomy.generate(prompts, max_new_tokens=12)
        tight = _engine(model, variables, mode="fp", tp_size=2,
                        max_batch_size=3, num_blocks=9,
                        host_tier_bytes=1 << 20)
        got = tight.generate(prompts, max_new_tokens=12)
        assert got == want
        assert sum(r.preemptions for r in tight.finished.values()) > 0
        demoted = tight.obs.get("ptpu_kv_tier_demoted_blocks_total")
        assert demoted.labels(reason="preempt").value > 0
        assert tight._step_fn._cache_size() == 1
        tight.cache.assert_quiesced()


# -- lint gate --------------------------------------------------------------

def test_tp_files_add_no_lint_findings():
    """graftlint over the whole tree (the telemetry pass needs the
    full registration universe), filtered to the files this feature
    touches: zero findings beyond the checked-in baseline — no new
    baseline entries rode in with tensor-parallel serving."""
    from paddle_tpu.analysis import (apply_baseline, load_baseline,
                                     run_analysis)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    touched = {
        "paddle_tpu/parallel/serve_collective.py",
        "paddle_tpu/parallel/sharding.py",
        "paddle_tpu/engine/engine.py",
        "paddle_tpu/engine/paged_cache.py",
        "paddle_tpu/kernels/paged_attention.py",
        "paddle_tpu/models/transformer.py",
        "paddle_tpu/serve/replica.py",
        "paddle_tpu/serve/frontend.py",
        "tools/paged_roofline.py",
        "tools/serve_bench.py",
        "OBSERVABILITY.md"}
    findings = run_analysis(
        [os.path.join(repo, "paddle_tpu"), os.path.join(repo, "tools")],
        repo)
    new, _suppressed, _stale = apply_baseline(
        findings, load_baseline(os.path.join(repo,
                                             "analysis_baseline.txt")))
    new = [f for f in new if f.file.replace(os.sep, "/") in touched]
    assert not new, "new graftlint findings:\n" + "\n".join(
        f.render() for f in new)
