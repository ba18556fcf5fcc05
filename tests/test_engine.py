"""Online inference engine tests (engine/): allocator bookkeeping,
continuous-batching == sequential decode identity, preemption-recompute
correctness, streaming callbacks, serve events, saved-model round-trip.

The load-bearing assertion is EXACT token identity, not closeness: the
engine always runs its compiled steps at fixed padded shapes (decode at
[max_batch_size], prefill at bucketed T), and rows of a batch are
computed independently, so a request's tokens cannot depend on what
else rode in the batch. `test_batched_equals_sequential` is that
guarantee; `test_engine_matches_model_generate` pins the engine to the
repo's reference decode path.
"""

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.engine import (CacheExhausted, PagedKVCache, Request,
                               Scheduler, ServeEngine)
from paddle_tpu.models.transformer import CausalLM
from paddle_tpu.obs.metrics import MetricsRegistry

# the package re-exports a function named `profiler` over the submodule
prof = importlib.import_module("paddle_tpu.profiler.profiler")

pytestmark = pytest.mark.serve

VOCAB = 61


@pytest.fixture(scope="module")
def model_and_vars():
    model = CausalLM(vocab=VOCAB, model_dim=16, num_heads=4, num_layers=2,
                     ffn_dim=32, dropout=0.0, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    return ServeEngine(model, variables, **kw)


PROMPTS = [[5, 9, 2], [7, 1, 1, 3, 8], [4], [11, 12, 13, 14, 15, 16, 17]]


# -- allocator ------------------------------------------------------------

class TestPagedKVCache:
    def test_alloc_free_roundtrip(self):
        c = PagedKVCache(num_layers=1, num_blocks=9, block_size=4,
                         num_kv_heads=2, head_dim=8)
        assert c.free_blocks == 8          # block 0 reserved
        c.alloc_sequence(1, [1] * 5)       # 2 blocks
        c.alloc_sequence(2, [2] * 4)       # exact boundary: 1 block
        assert c.used_blocks == 3
        assert c.blocks_for(5) == 2 and c.blocks_for(4) == 1
        assert c.free_sequence(1) == 2
        assert c.free_sequence(2) == 1
        assert c.free_blocks == 8

    def test_append_crosses_block_boundary(self):
        c = PagedKVCache(num_layers=1, num_blocks=4, block_size=4,
                         num_kv_heads=2, head_dim=8)
        c.alloc_sequence(7, [1, 2, 3, 4])
        assert c.used_blocks == 1
        slot = c.append_token(7)           # position 4 -> new block
        assert c.used_blocks == 2
        assert slot == c.slot_of(7, 4)
        assert slot % 4 == 0               # first slot of the new block
        # append before advance is idempotent (same reservation)
        assert c.append_token(7) == slot
        c.advance(7, 9)
        assert c.seq_len(7) == 5

    def test_exhaustion_raises_without_partial_alloc(self):
        c = PagedKVCache(num_layers=1, num_blocks=3, block_size=4,
                         num_kv_heads=2, head_dim=8)
        c.alloc_sequence(1, [1] * 4)
        with pytest.raises(CacheExhausted):
            c.alloc_sequence(2, [2] * 12)  # needs 3, only 1 free
        assert c.free_blocks == 1          # nothing leaked
        assert c.can_allocate(4) and not c.can_allocate(5)

    def test_block_zero_never_allocated(self):
        c = PagedKVCache(num_layers=1, num_blocks=5, block_size=2,
                         num_kv_heads=1, head_dim=4)
        c.alloc_sequence(1, list(range(8)))   # all 4 allocatable blocks
        assert 0 not in c.block_table(1)
        assert c.padded_table(1, 6)[-2:] == [0, 0]   # padding IS block 0


# -- scheduler ------------------------------------------------------------

class TestScheduler:
    def test_fifo_admission_under_budget(self):
        c = PagedKVCache(num_layers=1, num_blocks=64, block_size=4,
                         num_kv_heads=2, head_dim=8)
        s = Scheduler(c, max_batch_size=2, max_prefill_tokens=8)
        for p in ([1, 2, 3], [4, 5], [6]):
            s.add(Request(prompt=list(p)))
        rows = s.next_batch()
        assert all(not w.decode for w in rows)
        assert [w.length for w in rows] == [3, 2]        # batch cap hit
        assert [w.start for w in rows] == [0, 0]
        assert s.queue_depth == 1
        rows2 = s.next_batch()
        assert all(w.decode for w in rows2)              # admission full
        assert [w.length for w in rows2] == [1, 1]

    def test_long_prompt_prefills_in_chunks(self):
        """A prompt over the per-step budget admits anyway and is cut
        into budget-bounded chunks at successive offsets."""
        c = PagedKVCache(num_layers=1, num_blocks=64, block_size=4,
                         num_kv_heads=2, head_dim=8)
        s = Scheduler(c, max_batch_size=2, max_prefill_tokens=8)
        s.add(Request(prompt=list(range(20))))
        seen = []
        for _ in range(3):
            rows = s.next_batch()
            assert len(rows) == 1 and not rows[0].decode
            seen.append((rows[0].start, rows[0].length))
        assert seen == [(0, 8), (8, 8), (16, 4)]
        assert not s.running[0].prefilling

    def test_unschedulable_head_fails_loud(self):
        """A head request that can NEVER fit the pool (even alone) must
        raise, not strand silently. (Over the prefill budget is no
        longer fatal — chunked prefill covers it.)"""
        c = PagedKVCache(num_layers=1, num_blocks=4, block_size=4,
                         num_kv_heads=2, head_dim=8)
        s = Scheduler(c, max_batch_size=2, max_prefill_tokens=8)
        s.add(Request(prompt=list(range(16))))   # 4 blocks > 3 usable
        with pytest.raises(CacheExhausted, match="never"):
            s.next_batch()

    def test_preempt_requeues_front_with_folded_prompt(self):
        c = PagedKVCache(num_layers=1, num_blocks=64, block_size=4,
                         num_kv_heads=2, head_dim=8)
        s = Scheduler(c, max_batch_size=2)
        r = Request(prompt=[1, 2])
        s.add(r)
        s.next_batch()
        r.generated = [9, 8]
        s.preempt(r)
        assert r.prompt == [1, 2, 9, 8] and r.generated == []
        assert r.preempt_carry == 2 and r.preemptions == 1
        assert s.waiting[0] is r and not s.running
        assert c.free_blocks == 63

    def test_victim_is_most_deadline_slack(self):
        """Preemption lands on the running request with the MOST
        deadline slack; without deadlines it degrades to the original
        rule (last admitted wins ties at +inf)."""
        c = PagedKVCache(num_layers=1, num_blocks=64, block_size=4,
                         num_kv_heads=2, head_dim=8)
        s = Scheduler(c, max_batch_size=4)
        tight = Request(prompt=[1], deadline=10.0)
        loose = Request(prompt=[2], deadline=99.0)
        none_ = Request(prompt=[3])                 # inf: most slack
        s.running = [tight, loose, none_]
        assert s._pick_victim(tight) is none_
        s.running = [tight, loose]
        assert s._pick_victim(tight) is loose
        assert s._pick_victim(loose) is tight       # never the keeper
        # all-default deadlines: last admitted, as before
        a, b = Request(prompt=[4]), Request(prompt=[5])
        s.running = [a, b]
        assert s._pick_victim(None) is b

    def test_cancel_running_and_waiting(self):
        c = PagedKVCache(num_layers=1, num_blocks=64, block_size=4,
                         num_kv_heads=2, head_dim=8)
        s = Scheduler(c, max_batch_size=1)
        running = Request(prompt=[1, 2, 3])
        queued = Request(prompt=[4, 5])
        s.add(running)
        s.add(queued)
        s.next_batch()                              # admits only `running`
        held = c.used_blocks
        assert held > 0 and s.queue_depth == 1
        assert s.cancel(queued)                     # no KV held
        assert s.queue_depth == 0 and c.used_blocks == held
        assert s.cancel(running)                    # frees its blocks
        assert c.used_blocks == 0 and not s.running
        assert running.finish_reason == "cancelled"
        assert not s.cancel(running)                # already gone


# -- engine ---------------------------------------------------------------

def _sequential(model, variables, prompts, n, **req_kw):
    out = []
    for p in prompts:
        eng = _engine(model, variables)
        out.append(eng.generate([p], max_new_tokens=n, **req_kw)[0])
    return out


def test_prefill_budget_validated_at_construction(model_and_vars):
    """max_prefill_tokens is checked against the model's usable context
    at construction: nonsense rejects, oversize clamps (and shrinks the
    compiled step) instead of silently padding dead tiles."""
    model, variables = model_and_vars
    with pytest.raises(ValueError, match="max_prefill_tokens"):
        _engine(model, variables, max_prefill_tokens=0)
    big = _engine(model, variables, max_prefill_tokens=10_000)
    assert big.scheduler.max_prefill_tokens == big.max_seq_len
    assert big.flat_tokens == _engine(model, variables).flat_tokens


def test_one_compile_for_mixed_traffic(model_and_vars):
    """THE one-compilation claim, asserted mechanically: a serve run
    mixing long chunked prefills, short prompts and decode — including
    steps where chunk rows and decode rows share the launch — triggers
    exactly ONE compilation of the step callable. (The old two-path
    engine compiled the decode step plus one prefill step per pow2
    bucket: O(log chunk_budget) compiles.)"""
    model, variables = model_and_vars
    eng = _engine(model, variables, max_prefill_tokens=8)
    eng.add_request([3, 1, 4], max_new_tokens=2)     # warmup
    eng.run()
    assert eng._step_fn._cache_size() == 1
    eng.add_request(list(range(1, 30)), max_new_tokens=4)   # 4 chunks
    eng.add_request([5, 9], max_new_tokens=6)               # decode rider
    eng.add_request(list(range(30, 43)), max_new_tokens=3)  # mid-size
    eng.run()
    assert eng._step_fn._cache_size() == 1           # zero recompiles
    assert eng._copy_blocks._cache_size() <= 1


def test_batched_equals_sequential(model_and_vars, capsys):
    """THE continuous-batching guarantee: same tokens whether a request
    shares the batch or runs alone."""
    model, variables = model_and_vars
    eng = _engine(model, variables)
    batched = eng.generate(PROMPTS, max_new_tokens=8)
    assert batched == _sequential(model, variables, PROMPTS, 8)


def test_engine_matches_model_generate(model_and_vars):
    """Paged + continuous batching vs the dense-cache fori_loop decoder."""
    model, variables = model_and_vars
    eng = _engine(model, variables)
    got = eng.generate(PROMPTS, max_new_tokens=8)
    for p, g in zip(PROMPTS, got):
        want = model.generate(variables, jnp.asarray([p], jnp.int32), 8)
        assert g == np.asarray(want)[0, len(p):].tolist()


def test_sampled_decode_batch_invariant(model_and_vars):
    """Stochastic sampling keys off (seed, position), so it too must be
    batching-invariant."""
    model, variables = model_and_vars
    kw = dict(temperature=0.8, top_k=8, seed=123)
    eng = _engine(model, variables)
    batched = eng.generate(PROMPTS[:3], max_new_tokens=6, **kw)
    assert batched == _sequential(model, variables, PROMPTS[:3], 6, **kw)
    assert len(set(map(tuple, batched))) > 1   # actually sampling


def test_preemption_recompute_exact(model_and_vars):
    """A pool too small for all requests forces eviction; recompute must
    reproduce the exact same tokens as an unconstrained run."""
    model, variables = model_and_vars
    prompts = [[5, 9, 2, 4], [7, 1, 1, 3], [4, 4, 2, 9]]
    roomy = _engine(model, variables, max_batch_size=3)
    want = roomy.generate(prompts, max_new_tokens=12)

    tight = _engine(model, variables, max_batch_size=3, num_blocks=9)
    got = tight.generate(prompts, max_new_tokens=12)
    assert sum(r.preemptions for r in tight.finished.values()) > 0
    assert got == want
    assert tight.cache.used_blocks == 0       # everything returned


def test_streaming_callbacks_in_order(model_and_vars):
    model, variables = model_and_vars
    eng = _engine(model, variables)
    streams = {}
    reqs = []
    for p in PROMPTS[:2]:
        stream = []
        reqs.append(eng.add_request(
            p, max_new_tokens=5,
            callback=(lambda s: s.append)(stream)))
        streams[reqs[-1].req_id] = stream
    done = eng.run()
    for r in reqs:
        assert streams[r.req_id] == done[r.req_id]    # streamed == final
        assert len(streams[r.req_id]) == 5


def test_serve_events_emitted(model_and_vars, capsys):
    model, variables = model_and_vars
    eng = _engine(model, variables)
    eng.generate(PROMPTS[:2], max_new_tokens=4)
    events = [json.loads(line) for line in
              capsys.readouterr().out.strip().splitlines()
              if line.startswith('{"evt"')]
    kinds = {e["evt"] for e in events}
    assert {"serve_admit", "serve_prefill", "serve_decode",
            "serve_done"} <= kinds
    done = [e for e in events if e["evt"] == "serve_done"]
    assert len(done) == 2
    for e in done:
        assert e["tokens"] == 4 and e["ttft_ms"] >= 0
    decode = [e for e in events if e["evt"] == "serve_decode"]
    assert all(0 <= e["occupancy"] <= 1 for e in decode)


def test_oversize_prompt_rejected_at_intake(model_and_vars):
    model, variables = model_and_vars
    roomy = _engine(model, variables)
    with pytest.raises(ValueError, match="no room"):
        roomy.add_request([1] * 64)          # max_seq_len is 64
    tiny = _engine(model, variables, num_blocks=4)
    with pytest.raises(ValueError, match="num_blocks"):
        tiny.add_request(list(range(12)))    # 13 slots -> 4 blocks > 3
    # over the per-STEP chunk budget is no longer a rejection: long
    # prompts admit and prefill across chunked steps
    chunky = _engine(model, variables, max_prefill_tokens=8)
    req = chunky.add_request(list(range(10)), max_new_tokens=2)
    chunky.run()
    assert req.num_generated == 2


def test_eos_stops_early(model_and_vars):
    model, variables = model_and_vars
    eng = _engine(model, variables)
    free = eng.generate([[5, 9, 2]], max_new_tokens=8)[0]
    # eos = a token whose FIRST occurrence is mid-stream, so the stop
    # both triggers and truncates
    eos = next(t for t in free if t != free[0])
    cut = free.index(eos)
    eng2 = _engine(model, variables)
    req = eng2.add_request([5, 9, 2], max_new_tokens=8, eos_id=eos)
    eng2.run()
    assert req.generated == free[:cut + 1]
    assert req.finish_reason == "eos"


def test_engine_cancel_midflight(model_and_vars):
    """engine.cancel() between steps: blocks freed, counted under
    requests{reason="cancelled"}, survivors decode identically."""
    model, variables = model_and_vars
    eng = _engine(model, variables)
    # reference from the same engine: prefix sharing is exact, so the
    # later run reproduces it token-for-token (and saves a compile)
    reference = eng.generate([PROMPTS[0]], max_new_tokens=8)[0]
    keep = eng.add_request(list(PROMPTS[0]), max_new_tokens=8)
    drop = eng.add_request(list(PROMPTS[1]), max_new_tokens=8)
    eng.step()                                   # both admitted + planned
    assert eng.cancel(drop)
    assert not eng.cancel(drop)                  # idempotent: already out
    eng.run()
    assert keep.generated == reference           # batch-mate unaffected
    assert drop.finish_reason == "cancelled"
    assert eng.obs.get("ptpu_serve_requests_total").labels(
        reason="cancelled").value == 1.0
    assert eng.cache.occupancy() == 0.0
    eng.cache.assert_quiesced()


def test_sched_gauges_fresh_between_steps(model_and_vars):
    """Queue-depth/running gauges must update on admit/enqueue/finish,
    not only inside step(): a router scrapes BETWEEN steps."""
    model, variables = model_and_vars
    eng = _engine(model, variables, max_batch_size=2)
    depth = eng.obs.get("ptpu_sched_queue_depth")
    running = eng.obs.get("ptpu_sched_running")
    reqs = [eng.add_request(list(p), max_new_tokens=2) for p in PROMPTS[:3]]
    assert depth.value == 3.0                    # enqueue, before any step
    eng.step()                                   # admits 2 (batch cap)
    assert depth.value == 1.0 and running.value == 2.0
    cancelled = eng.cancel(reqs[2])              # still waiting
    assert cancelled and depth.value == 0.0      # gauge moved, no step ran
    eng.run()
    assert running.value == 0.0 and depth.value == 0.0


def test_deadline_ms_sets_absolute_deadline(model_and_vars):
    model, variables = model_and_vars
    eng = _engine(model, variables)
    r_inf = eng.add_request([1, 2], max_new_tokens=1)
    r_tight = eng.add_request([3, 4], max_new_tokens=1, deadline_ms=250.0)
    assert r_inf.deadline == float("inf")
    assert r_tight.deadline == pytest.approx(
        r_tight.enqueue_time + 0.25)
    # no eng.run(): the deadline is a pure add_request property, and
    # skipping the drain skips a step compile (victim selection under
    # deadlines is covered by the scheduler tests above)


def test_from_saved_model_roundtrip(model_and_vars, tmp_path):
    """Export with the manifest `serve` block, rebuild blind from disk,
    and decode identically to the in-memory engine."""
    from paddle_tpu.testing import export_causal_lm
    path, model, variables = export_causal_lm(str(tmp_path / "m"))
    eng = ServeEngine.from_saved_model(path, max_batch_size=2,
                                       block_size=4, num_blocks=32)
    want = _engine(model, variables, max_batch_size=2,
                   block_size=4, num_blocks=32).generate(
        [[3, 1, 4], [1, 5, 9, 2]], max_new_tokens=6)
    got = eng.generate([[3, 1, 4], [1, 5, 9, 2]], max_new_tokens=6)
    assert got == want


def test_from_saved_model_keeps_compute_dtype(tmp_path):
    """A bf16 export comes back bf16 — model and KV pool — and a
    manifest written before the `dtype` field loads as float32."""
    from paddle_tpu.testing import export_causal_lm
    path, _, _ = export_causal_lm(str(tmp_path / "m"), dtype=jnp.bfloat16)
    kw = dict(max_batch_size=2, block_size=4, num_blocks=32)
    eng = ServeEngine.from_saved_model(path, **kw)
    assert eng.model.dtype == jnp.bfloat16
    assert eng.cache.pools[0].dtype == jnp.bfloat16
    # the checkpoint's host arrays were placed on the device once
    assert all(isinstance(x, jax.Array)
               for x in jax.tree.leaves(eng.variables))
    got = eng.generate([[3, 1, 4]], max_new_tokens=4)
    assert len(got[0]) == 4
    sig_path = tmp_path / "m" / "signature.json"
    sig = json.loads(sig_path.read_text())
    assert sig["serve"].pop("dtype") == "bfloat16"
    sig_path.write_text(json.dumps(sig))
    old = ServeEngine.from_saved_model(path, **kw)
    assert old.model.dtype == jnp.float32
    assert old.cache.pools[0].dtype == jnp.float32


def test_old_manifest_without_serve_block(model_and_vars, tmp_path):
    """Pre-serve manifests stay loadable by the predictor, and the engine
    fails with a clear message instead of a KeyError."""
    from paddle_tpu.io.inference import (InferencePredictor,
                                         save_inference_model)
    model, variables = model_and_vars
    x = jnp.zeros((1, 4), jnp.int32)
    path = str(tmp_path / "old")
    save_inference_model(path, model, variables, [x],
                         input_names=["tokens"])        # no serve_meta
    out = InferencePredictor(path).run([np.zeros((1, 4), np.int32)])
    assert out[0].shape == (1, 4, VOCAB)
    with pytest.raises(ValueError, match="serve"):
        ServeEngine.from_saved_model(path)


# -- the pool's row (kernels/paged_attention.py) and the pool's donation --

# (kv heads, head_dim): a head padded up to 128 lanes, one that fills
# them exactly (the GPT-2 cells' shape), one padded up to 256
LAYOUT_SHAPES = [(4, 8), (2, 64), (3, 96)]


@pytest.mark.parametrize("hkv,hd", LAYOUT_SHAPES)
def test_pool_rows_hold_k_and_v_side_by_side(hkv, hd):
    from paddle_tpu.kernels.paged_attention import (head_lanes, pack_kv,
                                                    unpack_kv, write_kv)
    rng = np.random.default_rng(hkv * hd)
    k = rng.standard_normal((5, hkv, hd)).astype(np.float32)
    v = rng.standard_normal((5, hkv, hd)).astype(np.float32)
    lanes = head_lanes(hd)
    assert lanes % 128 == 0 and 2 * hd <= lanes < 2 * hd + 128
    rows = pack_kv(k, v)
    assert rows.shape == (5, hkv * lanes)
    heads = rows.reshape(5, hkv, lanes)
    assert (heads[..., :hd] == k).all() and (heads[..., hd:2 * hd] == v).all()
    assert not heads[..., 2 * hd:].any()
    back = unpack_kv(rows, hd)
    assert (back[0] == k).all() and (back[1] == v).all()
    # the step's write: whole rows land at their flat slots, nothing else
    cache = PagedKVCache(num_layers=1, num_blocks=4, block_size=4,
                         num_kv_heads=hkv, head_dim=hd)
    assert cache.pools[0].shape == cache.pool_shape() == (4, 4, hkv * lanes)
    slots = jnp.asarray([6, 3, 15, 8, 9], jnp.int32)
    pool = np.asarray(write_kv(cache.pools[0], slots, jnp.asarray(k),
                               jnp.asarray(v))).reshape(16, -1)
    assert (pool[np.asarray(slots)] == rows).all()
    assert not np.delete(pool, np.asarray(slots), axis=0).any()


def _engine_with_a_known_block(hkv, hd, **kw):
    """A small engine whose block 3 holds seeded k/v in every layer,
    written through the cache's own write; returns (engine, per-layer
    (k, v) of that block)."""
    from paddle_tpu.kernels.paged_attention import write_kv
    model = CausalLM(vocab=VOCAB, model_dim=hkv * hd, num_heads=hkv,
                     num_layers=2, ffn_dim=32, dropout=0.0, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = _engine(model, variables, num_blocks=16, **kw)
    rng = np.random.default_rng(7)
    want = []
    for li, pool in enumerate(eng.cache.pools):
        k = rng.standard_normal((4, hkv, hd)).astype(np.float32)
        v = rng.standard_normal((4, hkv, hd)).astype(np.float32)
        eng.cache.pools[li] = write_kv(pool, jnp.arange(12, 16), k, v)
        want.append((k, v))
    return eng, want


def _assert_block(eng, block, want):
    for (k, v), (wk, wv) in zip(eng.cache.read_block(block), want):
        assert k.shape == wk.shape and (k == wk).all() and (v == wv).all()


KEY = (1, 2, 3, 4)


def _round_trip_cow(hkv, hd):
    eng, want = _engine_with_a_known_block(hkv, hd)
    consumed = list(eng.cache.pools)
    eng.cache._pending_copies.append((3, 5))
    assert eng._flush_cow() == 1
    assert all(p.is_deleted() for p in consumed)    # donated to the copy
    _assert_block(eng, 5, want)
    _assert_block(eng, 3, want)


def _round_trip_host_tier(hkv, hd):
    eng, want = _engine_with_a_known_block(hkv, hd, host_tier_bytes=1 << 22)
    assert eng.cache._demote_block(3, KEY, "evict")
    eng.cache._pending_host_loads.append((6, eng.host_tier.get(KEY)))
    assert eng._flush_tier_loads() == 1
    _assert_block(eng, 6, want)


def _round_trip_compress_promote(hkv, hd):
    from paddle_tpu.quant.int8_compute import (dequantize_block,
                                               quantize_block)
    eng, want = _engine_with_a_known_block(hkv, hd, kv_compress_blocks=4)
    eng.cache._pending_compress.append((3, 1))
    assert eng._flush_compress() == 1
    eng.cache._pending_promotes.append((7, 1))
    assert eng._flush_promote() == 1
    spilled = eng.cache._slot_qlayers(1)
    for (k, v), (wk, wv), (kq, ks, vq, vs) in zip(
            eng.cache.read_block(7), want, spilled):
        for got, src, q8, scale in ((k, wk, kq, ks), (v, wv, vq, vs)):
            # k and v each under its own per-block scale, one quant step
            q, s = quantize_block(jnp.asarray(src))
            assert (np.asarray(q) == q8).all() and float(s) == scale
            assert (got == np.asarray(
                dequantize_block(q, s, jnp.float32))).all()
            assert np.abs(got - src).max() <= scale / 127 + 1e-7


def _round_trip_kvxfer(hkv, hd):
    from paddle_tpu.engine import prefix_digest
    from paddle_tpu.serve.kvxfer import decode_entry, encode_tier_blob
    src, want = _engine_with_a_known_block(hkv, hd, host_tier_bytes=1 << 22)
    dst, _ = _engine_with_a_known_block(hkv, hd, host_tier_bytes=1 << 22)
    assert src.cache._demote_block(3, KEY, "finish")
    payload = encode_tier_blob(src.host_tier, prefix_digest(KEY))
    key, blobs, nbytes = decode_entry(payload, int8=False)
    assert key == KEY and dst.host_tier.insert_encoded(key, blobs, nbytes)
    dst.cache._pending_host_loads.append((9, dst.host_tier.get(KEY)))
    assert dst._flush_tier_loads() == 1
    _assert_block(dst, 9, want)


@pytest.mark.parametrize("hkv,hd", LAYOUT_SHAPES[:2])
@pytest.mark.parametrize("path", [
    _round_trip_cow, _round_trip_host_tier, _round_trip_compress_promote,
    _round_trip_kvxfer], ids=lambda f: f.__name__[12:])
def test_block_round_trip(path, hkv, hd):
    """One block's k/v through every way a block leaves and re-enters
    the pool: each goes through the cache's view of a block, so none
    knows the pool's layout."""
    path(hkv, hd)


def _dense_logprob(model, variables, prompt, generated):
    seq = jnp.asarray([prompt + generated], jnp.int32)
    logp = jax.nn.log_softmax(
        model.apply(variables, seq, training=False)[0].astype(jnp.float32))
    return float(sum(logp[len(prompt) - 1 + i, t]
                     for i, t in enumerate(generated)))


@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_step_consumes_the_pools_and_serves_the_same(model_and_vars, tier,
                                                     monkeypatch):
    """Every step takes the pools donated: the handles it was given are
    deleted, the engine holds the ones it returned, and no path (flushes,
    demotion, revival, snapshots) reads a consumed one. Tokens equal
    model.generate's and the log-probabilities the dense forward's: the
    numbers the split [blocks, bs, heads, hd] pools gave (CHANGES.md,
    PR 26, has the parent's side by side)."""
    monkeypatch.setenv("PTPU_PAGED_KERNEL", tier)
    model, variables = model_and_vars
    eng = _engine(model, variables, num_blocks=12, host_tier_bytes=1 << 22,
                  kv_compress_blocks=6)
    reqs = [eng.add_request(p, max_new_tokens=5) for p in PROMPTS]
    while eng.scheduler.has_work():
        before, launched = list(eng.cache.pools), eng._launched
        assert eng.step()
        # (a call that only collects the last step launches nothing)
        assert all(p.is_deleted() for p in before) \
            or eng._launched == launched
        assert not any(p.is_deleted() for p in eng.cache.pools)
        eng.debug_state(), eng.kv_prefix_directory()
        assert eng.cache.per_chip_pool_bytes() > 0
    for r, p in zip(reqs, PROMPTS):
        want = np.asarray(model.generate(
            variables, jnp.asarray([p], jnp.int32), num_steps=5))[0, -5:]
        assert r.generated == want.tolist()
        assert r.logprob_sum == pytest.approx(
            _dense_logprob(model, variables, p, r.generated), abs=1e-4)
    # churn the 11-block pool so that blocks demote, compress and revive
    # between donated steps
    for i in range(6):
        eng.generate([[20 + i] * 9 + [1, 2]], max_new_tokens=3)
    again = eng.generate([PROMPTS[3]], max_new_tokens=5)[0]
    assert again == reqs[3].generated
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_pick_scores_a_greedy_token_against_the_steps_log_sum_exp(
        temperature):
    """`_sample` leaves a greedy token's log-probability to `_pick`,
    which takes it from the row's log-sum-exp as the step computes it
    (float32): the plain softmax's, within float32 rounding of a
    float64 pass over the row. A sampled token is scored by `_sample`
    itself, under its temperature."""
    from paddle_tpu.engine.engine import _pick, _sample
    row = (np.random.default_rng(3).standard_normal(50_000)
           * 2.5).astype(np.float32)
    lse = np.asarray(jax.nn.logsumexp(jnp.asarray(row)))
    req = Request(prompt=[1], max_new_tokens=1, temperature=temperature,
                  seed=11)
    best, top = int(row.argmax()), row.max()
    tok, lp = _pick(row, best, top, lse, req, 4)
    z = row.astype(np.float64) / (temperature or 1.0)
    want = z - z.max() - np.log(np.exp(z - z.max()).sum())
    assert lp == pytest.approx(want[tok], abs=2e-6)
    if temperature:
        assert _sample(row, req, 4) == (tok, lp)
    else:
        assert (tok, _sample(row, req, 4)) == (best, (tok, None))
        # without the row, the step's own pick: the same pair, bit for bit
        assert _pick(None, best, top, lse, req, 4) == (tok, lp)


def test_failed_donated_step_leaves_a_serving_engine(model_and_vars):
    """A step that raises after it consumed the donated pools: the
    engine rebuilds them, sends what was running back to the queue, and
    lets the error through; the next calls serve every request, the
    interrupted ones included, with the tokens an undisturbed engine
    gives."""
    model, variables = model_and_vars
    want = _engine(model, variables).generate(PROMPTS, max_new_tokens=6)
    eng = _engine(model, variables)
    reqs = [eng.add_request(p, max_new_tokens=6) for p in PROMPTS]
    for _ in range(3):
        eng.step()
    assert any(r.generated for r in reqs)
    real = eng._step_fn

    def falls_over(*operands):
        real(*operands)                 # the pools are consumed ...
        raise RuntimeError("the device fell over")     # ... then this
    eng._step_fn = falls_over
    with pytest.raises(RuntimeError, match="fell over"):
        eng.step()
    eng._step_fn = real
    assert not any(p.is_deleted() for p in eng.cache.pools)
    assert not eng.scheduler.running and eng.scheduler.queue_depth == 4
    assert eng.cache.used_blocks == 0 and not eng.cache.prefix_rows()
    eng.run()
    assert [eng._generated_of(r) for r in reqs] == want
    late = eng.generate([[9, 9, 8]], max_new_tokens=4)
    assert late == _engine(model, variables).generate([[9, 9, 8]],
                                                      max_new_tokens=4)
    eng.cache.assert_quiesced()


# -- a second step in flight ------------------------------------------------
#
# ENGINE.md "A second step in flight": `step()` launches step N+1 before it
# collects step N wherever N's rows are greedy. The loop held synchronous
# (`_runs_ahead` says no: the same launch and collect, back to back) is what
# every property is held against, over each kind of cache the engine keeps.

LAYOUTS = ("dense", "latent", "hybrid", "snapshot")
LONG = list(range(1, 27))       # three 8-token snapshot boundaries and a tail
MIXED = [LONG + [30, 31], [5, 9, 2, 7, 1, 3], [4, 4, 8], LONG + [40]]


@functools.lru_cache(maxsize=None)
def _layout(kind):
    """(model, variables, engine options) of a toy of each cache kind:
    dense paged, latent paged, state slots with a window ring, and
    block-sparse + lightning layers served with state snapshots."""
    if kind == "dense":
        model = CausalLM(vocab=VOCAB, model_dim=16, num_heads=4,
                         num_layers=2, ffn_dim=32, dropout=0.0, max_len=64)
    elif kind == "latent":
        from paddle_tpu.models.latent_moe import LatentMoELM
        model = LatentMoELM(
            vocab=VOCAB, model_dim=16, num_heads=2, num_layers=3, q_rank=8,
            kv_rank=8, nope_dim=4, rope_dim=4, v_dim=4, dense_dim=32,
            expert_dim=8, num_experts=8, top_k=2, max_len=64)
    elif kind == "hybrid":
        from paddle_tpu.models.hybrid_lm import HybridLM
        model = HybridLM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            ffn_dim=32, window=8, d_inner=32, d_state=4, d_conv=4, dt_rank=2,
            layer_kinds=["mamba", "window", "mamba", "full", "gmu", "cross"],
            max_len=64)
    else:
        from paddle_tpu.models.sparse_linear_lm import SparseLinearLM
        model = SparseLinearLM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            head_dim=4, ffn_dim=32, la_heads=2, la_head_dim=8, max_len=64,
            mixer_types=["lightning-attn", "minicpm4"], snapshot_tokens=8,
            snapshot_slots=4, sparse=dict(
                dense_len=16, kernel=4, stride=2, block=4, init_blocks=1,
                local=8, topk=1))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables, {"max_prefill_tokens": 8}


def _pair(kind, **kw):
    """Two engines of one kind: the loop as it runs, and held
    synchronous."""
    model, variables, options = _layout(kind)
    ahead = _engine(model, variables, registry=MetricsRegistry(),
                    **{**options, **kw})
    held = _engine(model, variables, registry=MetricsRegistry(),
                   **{**options, **kw})
    held._runs_ahead = lambda flight: False
    return ahead, held


def _count(eng, name):
    return eng.obs.get(name).value


def _served(eng, requests):
    """Serve `requests` (add_request's keywords) to the end; what each
    got: tokens, reason, the float sum, and the callback's calls."""
    calls = [[] for _ in requests]
    reqs = [eng.add_request(callback=calls[i].append, **kw)
            for i, kw in enumerate(requests)]
    eng.run()
    assert all(c == ServeEngine._generated_of(r)
               for c, r in zip(calls, reqs))
    return [(ServeEngine._generated_of(r), r.finish_reason, r.logprob_sum,
             len(c)) for r, c in zip(reqs, calls)]


@pytest.mark.parametrize("kind", LAYOUTS)
def test_a_second_step_in_flight_serves_the_synchronous_tokens(kind):
    """Same prompts, same weights: tokens, finish reasons, `logprob_sum`
    and the number of callbacks are those of the loop held synchronous,
    request by request. No request computes a row past its count
    (`discarded_rows` 0, and the generated tokens are the tokens asked
    for), nearly every step was launched behind an uncollected one, and
    the step compiled once on both sides."""
    ahead, held = _pair(kind)
    requests = [{"prompt": p, "max_new_tokens": n}
                for p, n in zip(MIXED, (6, 9, 1, 5))]
    got, want = _served(ahead, requests), _served(held, requests)
    assert got == want
    assert [g[1] for g in got] == ["length"] * 4
    asked = sum(r["max_new_tokens"] for r in requests)
    for eng in (ahead, held):
        assert eng.obs.get("ptpu_serve_tokens_total").labels(
            kind="generated").value == asked
        assert _count(eng, "ptpu_engine_rows_discarded_total") == 0
        assert eng._step_fn._cache_size() == 1
        assert eng._flight is None and not eng.scheduler.has_work()
        eng.cache.assert_quiesced()
    assert _count(held, "ptpu_engine_steps_overlapped_total") == 0
    steps = _count(ahead, "ptpu_engine_steps_total")
    assert steps == ahead.steps == held.steps
    # all but the first launch of the burst
    assert _count(ahead, "ptpu_engine_steps_overlapped_total") == steps - 1


@pytest.mark.parametrize("kind", ["dense", "hybrid"])
def test_no_row_is_planned_past_the_sequence_ceiling(kind):
    """A request that ends by `max_seq_len` is foreseen by the plan made
    while its last token is in flight, by the bound `_emit_token` ends it
    with (`Scheduler.out_of_room`): no row is computed and thrown away,
    and the tokens are the synchronous loop's."""
    ahead, held = _pair(kind, max_seq_len=24)
    requests = [{"prompt": MIXED[1], "max_new_tokens": 40},
                {"prompt": MIXED[2], "max_new_tokens": 12}]
    got, want = _served(ahead, requests), _served(held, requests)
    assert got == want
    # 6 prompt tokens + 17 generated stand at the ceiling of 24 - 1
    assert [(len(g[0]), g[1]) for g in got] == [(17, "length"),
                                                (12, "length")]
    for eng in (ahead, held):
        assert _count(eng, "ptpu_engine_rows_discarded_total") == 0
        assert eng.obs.get("ptpu_serve_tokens_total").labels(
            kind="generated").value == 29
        eng.cache.assert_quiesced()
    steps = _count(ahead, "ptpu_engine_steps_total")
    assert steps == held.steps
    assert _count(ahead, "ptpu_engine_steps_overlapped_total") == steps - 1


def _free_run(kind, prompt, n):
    model, variables, options = _layout(kind)
    return _engine(model, variables, **options).generate(
        [prompt], max_new_tokens=n)[0]


@pytest.mark.parametrize("how", ["eos", "cancel", "cancel_group"])
@pytest.mark.parametrize("kind", LAYOUTS)
def test_a_request_that_ends_with_a_row_in_flight(kind, how):
    """An end-of-sequence token, and a cancel from outside, while the
    request has a row in the step in flight: that row is thrown away
    when its step is collected. Nothing is emitted after the end, the
    neighbour's tokens are the synchronous loop's, and every block and
    slot comes back."""
    prompt, other = MIXED[1], MIXED[0]
    free = _free_run(kind, prompt, 12)
    ahead, held = _pair(kind)
    if how == "eos":
        cut = free.index(free[2])   # its first occurrence ends the request
        requests = [{"prompt": prompt, "max_new_tokens": 12,
                     "eos_id": free[cut]},
                    {"prompt": other, "max_new_tokens": 8}]
        got, want = _served(ahead, requests), _served(held, requests)
        assert got == want
        assert got[0][:2] == (free[:cut + 1], "eos") and got[0][3] == cut + 1
    else:
        outputs = []
        for eng in (ahead, held):
            seen = []
            req = eng.add_request(prompt, max_new_tokens=12,
                                  callback=seen.append)
            keep = eng.add_request(other, max_new_tokens=8)
            for _ in range(4):
                assert eng.step()
            assert (eng._flight is not None) == (eng is ahead)
            had = list(seen)
            if how == "cancel":
                assert eng.cancel(req)
            else:
                assert eng.cancel_group(req) == 1
            eng.run()
            assert seen == had == req.generated
            assert req.finish_reason == "cancelled"
            outputs.append((had, keep.generated, keep.finish_reason))
        assert outputs[0] == outputs[1]
    # the row that was out when the request ended, and no other
    assert _count(ahead, "ptpu_engine_rows_discarded_total") == 1
    assert _count(held, "ptpu_engine_rows_discarded_total") == 0
    for eng in (ahead, held):
        assert eng._step_fn._cache_size() == 1
        assert eng.cache.occupancy() == 0.0
        eng.cache.assert_quiesced()


@pytest.mark.parametrize("tier", ["promote", "host"])
def test_an_end_of_sequence_behind_a_pending_tier_flush(model_and_vars, tier):
    """A promote lane or a host-tier load staged by the plan of step N+1
    has step N collected before the flush, after the plan was made. A
    request that ends by `eos_id` in that collect has given its table
    back: its row of the plan is dropped, the neighbours' tokens are the
    synchronous loop's, and every block comes back."""
    model, variables = model_and_vars
    options = ({"num_blocks": 16, "kv_compress_blocks": 24,
                "kv_promote_hits": 1} if tier == "promote"
               else {"num_blocks": 12, "host_tier_bytes": 1 << 20})
    system = [7, 3, 7, 3, 11, 2, 5, 9, 1, 1, 4, 8]
    prompt = PROMPTS[1]
    free = _engine(model, variables).generate([prompt], max_new_tokens=12)[0]
    cut = free.index(free[2])
    outputs = []
    for hold in (False, True):
        eng = _engine(model, variables, registry=MetricsRegistry(), **options)
        if hold:
            eng._runs_ahead = lambda flight: False
        # the system prompt's blocks leave the pool for the lower rung
        eng.generate([system + [6, 2]], max_new_tokens=6)
        eng.generate([[50] * 8], max_new_tokens=8)
        for i in range(3):
            eng.generate([[30 + i] * 16], max_new_tokens=12)
        assert tuple(system[:4]) not in eng.cache._index
        seen = []
        ends = eng.add_request(prompt, max_new_tokens=12, eos_id=free[cut],
                               callback=seen.append)
        for _ in range(cut):
            assert eng.step()
        # the pick that ends it is on the device, not yet on the host
        assert seen == free[:cut] and (eng._flight is None) == hold
        flushes = []
        flush = eng._flush_promote if tier == "promote" \
            else eng._flush_tier_loads
        staged = lambda: flushes.append(flush()) or flushes[-1]
        if tier == "promote":
            eng._flush_promote = staged
        else:
            eng._flush_tier_loads = staged
        # its admission stages the lanes, in the plan of the next step
        late = eng.add_request(system + [6, 2], max_new_tokens=6)
        eng.run()
        assert max(flushes) > 0
        assert seen == free[:cut + 1] and ends.finish_reason == "eos"
        assert late.finish_reason == "length" and late.cached_tokens > 0
        outputs.append((seen, late.generated))
        assert _count(eng, "ptpu_engine_rows_discarded_total") == 0
        assert eng._step_fn._cache_size() == 1
        eng.cache.assert_quiesced()
    assert outputs[0] == outputs[1]


def _overlapped(eng):
    """`overlapped` of the ring's `engine.step` spans, by step."""
    return {e["args"]["step"]: e["args"]["overlapped"]
            for e in prof.get_events() if e["name"] == "engine.step"}


@pytest.mark.parametrize("what", ["preempt", "draft", "fork", "temperature"])
def test_a_step_the_host_has_to_see_is_collected_first(model_and_vars, what):
    """A plan that must preempt, a drafted row, a request that forks and
    a row at a temperature: the step is collected before the next is
    planned, and the outputs are the synchronous loop's."""
    model, variables = model_and_vars
    options, requests = {}, [{"prompt": p, "max_new_tokens": 10}
                             for p in PROMPTS[:3]]
    if what == "preempt":
        options = {"max_batch_size": 3, "num_blocks": 9}
        requests = [{"prompt": p, "max_new_tokens": 12}
                    for p in ([5, 9, 2, 4], [7, 1, 1, 3], [4, 4, 2, 9])]
    elif what == "draft":
        options = {"spec_k": 3}
        requests.append({"prompt": [1, 2, 3, 4, 5] * 2 + [1, 2],
                         "max_new_tokens": 10})
    elif what == "fork":
        requests.append({"prompt": PROMPTS[3], "max_new_tokens": 6, "n": 3})
    else:
        requests.append({"prompt": PROMPTS[3], "max_new_tokens": 6,
                         "temperature": 0.7, "top_k": 20, "seed": 11})
    ahead = _engine(model, variables, registry=MetricsRegistry(), **options)
    held = _engine(model, variables, registry=MetricsRegistry(), **options)
    held._runs_ahead = lambda flight: False
    whole = []
    preempt = ahead.scheduler.preempt
    ahead.scheduler.preempt = lambda req: (
        whole.append(ahead._flight is None and not req.in_flight),
        preempt(req))
    prof.reset_profiler()
    got = _served(ahead, requests)
    flags = _overlapped(ahead)
    assert got == _served(held, requests)
    if what == "fork":
        forks = [ServeEngine._generated_of(f) for eng in (ahead, held)
                 for r in eng.finished.values() for f in r.forks]
        assert len(forks) == 4 and forks[:2] == forks[2:]
    steps = _count(ahead, "ptpu_engine_steps_total")
    ran_ahead = _count(ahead, "ptpu_engine_steps_overlapped_total")
    assert ran_ahead == sum(flags.values())
    if what == "preempt":
        # every victim's `prompt + generated` was whole
        assert whole and all(whole)
        assert sum(r.preemptions for r in ahead.finished.values()) > 0
        assert 0 < ran_ahead < steps
    elif what == "draft":
        assert ran_ahead == 0 and ahead._m_spec_accepted.value > 0
    elif what == "fork":
        # the step behind the prompt's final chunk waited for the fork
        first = min(r.req_id for r in ahead.finished.values()
                    if r.n_candidates > 1)
        forked = next(e["args"]["first_token_step"]
                      for e in prof.get_events() if e["name"] == "request"
                      and e["args"]["req"] == first)
        assert flags[forked + 1] == 0 and 0 < ran_ahead < steps
    else:
        # the steps in which the sampled request drew a token, and the
        # ones behind them: none ran ahead; the rest did
        downloads = _count(ahead, "ptpu_engine_logit_downloads_total")
        assert downloads == 6 and ran_ahead == steps - downloads - 1
    assert ahead._step_fn._cache_size() == held._step_fn._cache_size() == 1
    ahead.cache.assert_quiesced()


def test_the_step_compiles_once_whatever_the_order(model_and_vars):
    """`_step_fn` sees one kind of `tokens` operand: after the warm-up,
    a synchronous step, steps launched behind one in flight and a drain
    its cache holds one entry, and the merge beside it one."""
    model, variables = model_and_vars
    eng = _engine(model, variables, max_prefill_tokens=8)
    eng.generate([[3, 1, 4]], max_new_tokens=2)     # the warm-up
    sizes = [(eng._step_fn._cache_size(), eng._merge._cache_size())]
    for p in PROMPTS:
        eng.add_request(p, max_new_tokens=6)
    ahead = eng._runs_ahead
    eng._runs_ahead = lambda flight: False
    assert eng.step() and eng._flight is None       # synchronous
    sizes.append((eng._step_fn._cache_size(), eng._merge._cache_size()))
    eng._runs_ahead = ahead
    assert eng.step() and eng._flight is not None   # a second one out
    assert eng.step() and eng._flight.overlapped
    sizes.append((eng._step_fn._cache_size(), eng._merge._cache_size()))
    eng.run()                                       # the drain
    sizes.append((eng._step_fn._cache_size(), eng._merge._cache_size()))
    assert sizes == [(1, 1)] * 4
    assert eng._copy_blocks._cache_size() <= 1


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_a_block_of_generated_tokens_is_indexed_once_its_values_are_known(
        kind):
    """A sequence's length runs one token ahead of its values while a
    step is in flight: a block filled by generated tokens enters the
    prefix index only when its last value has landed, under the key of
    the tokens it holds, and a later prompt hits it as it does after the
    synchronous loop."""
    ahead, held = _pair(kind)
    prompt = [5, 9, 2]
    hits = []
    for eng in (ahead, held):
        req = eng.add_request(prompt, max_new_tokens=11)
        while eng.step():
            known = req.prompt + req.generated
            for key, block in eng.cache._index.items():
                # never a key over a token the host has not seen
                assert len(key) % 4 == 0 and list(key) == known[:len(key)]
        text = prompt + req.generated
        # positions 0..12 are in the pool: three full blocks of 4
        assert sorted(len(k) for k in eng.cache._index) == [4, 8, 12]
        again = eng.add_request(text[:13] + [7], max_new_tokens=3)
        eng.run()
        hits.append((again.cached_tokens, again.generated))
    assert hits[0] == hits[1] and hits[0][0] == 12
    assert _count(ahead, "ptpu_engine_steps_overlapped_total") > 0


@pytest.mark.parametrize("kind", ["hybrid", "snapshot"])
def test_a_snapshot_holds_its_boundary_though_the_next_step_was_out(kind):
    """The chunk that ends on a snapshot boundary is followed on the
    device's queue by the copy of its slot's state, before the next
    step, launched ahead of the collect, moves the slot on: the
    snapshot places hold what the synchronous loop's hold, and a prompt
    that restores one is served the same tokens."""
    model, variables, options = _layout(kind)
    if kind == "hybrid":
        options = dict(options, enable_prefix_cache=True, snapshot_tokens=8,
                       snapshot_slots=4)
    engines = [_engine(model, variables, registry=MetricsRegistry(),
                       **options) for _ in range(2)]
    ahead, held = engines
    held._runs_ahead = lambda flight: False
    kept = []
    for eng in engines:
        first = eng.generate([MIXED[0]], max_new_tokens=4)
        assert eng.cache.snapshots_held == 3        # at 8, 16 and 24
        places = {len(k): v[0] for k, v in eng.cache._snap_index.items()}
        state = [np.asarray(snap[places[n]]) for n in (8, 16, 24)
                 for snap, at in zip(eng.cache.snaps, eng.cache.snap_places)
                 if eng.cache.kinds[at] != "window"]
        req = eng.add_request(MIXED[3], max_new_tokens=6)
        eng.run()
        assert req.cached_tokens == 24
        kept.append((first, state, req.generated))
        eng.cache.assert_quiesced()
    assert kept[0][0] == kept[1][0] and kept[0][2] == kept[1][2]
    assert len(kept[0][1]) >= 3
    for a, b in zip(kept[0][1], kept[1][1]):
        np.testing.assert_array_equal(a, b)
        assert np.abs(a).max() > 0
    # the boundary chunks' successors were out before their collect
    assert _count(ahead, "ptpu_engine_steps_overlapped_total") >= 3
    assert _count(held, "ptpu_engine_steps_overlapped_total") == 0
    # and the hit is what a cold engine serves
    cold = _engine(model, variables, **dict(options, enable_prefix_cache=False)
                   ).generate([MIXED[3]], max_new_tokens=6)[0]
    assert kept[0][2] == cold
