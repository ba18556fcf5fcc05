"""The decoder of five layer kinds (`models/hybrid_lm.py`) against the
benchmark's plain reference (`benchmarks/reference_phi4flash.py`), at
the toy sizes of `benchmarks/configs/phi-4-mini-flash.json` on seeded
weights: the published form, then chunked prefill and decode through
the engine's three kinds of cache, the two kernels this block brought,
and every refusal of the slot kinds.

Tolerances. Everything here is float32 on one backend, and the two
sides differ in formulation, not in precision: the reference computes
a pair's two softmax maps apart and the model one map a query head over
the pair's key of twice the width; the reference scans a sequence, the
engine a step's tiles from a slot. Logits have unit scale, so 2e-4 is
a dozen float32 roundings through ten layers and a hundredth of what a
dropped term (a bias, the D skip, the second map) moves them by.
"""

import importlib
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_phi4flash as reference
from benchmarks import weights_phi4flash as weights
from benchmarks.common import build_model
from paddle_tpu.engine import engine as engine_mod
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.engine.paged_cache import CacheExhausted, CacheLayout
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.obs.metrics import MetricsRegistry

prof = importlib.import_module("paddle_tpu.profiler.profiler")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_007
TOL = 2e-4


def _toy(layers=None) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["toy"]}
    if layers is not None:
        cfg["layer_kinds"] = cfg["layer_kinds"][:layers]
        cfg["num_hidden_layers"] = layers
    return cfg


@pytest.fixture(scope="module")
def toy():
    cfg = _toy()
    model = build_model(cfg)
    return cfg, model, {"params": weights.make_params(cfg, SEED)}


def _tokens(cfg, rng, *lens):
    return [rng.integers(0, cfg["vocab_size"], n).tolist() for n in lens]


# -- the published form ------------------------------------------------------

@pytest.mark.parametrize("layers,kind", [
    (1, "mamba"), (2, "window"), (6, "full"), (7, "gmu"), (8, "cross"),
    (10, "whole")])
def test_forward_agrees_with_the_reference(layers, kind):
    """The stack cut after its first layer of each kind, then whole:
    24 tokens are three windows long."""
    cfg = _toy(layers)
    assert kind == "whole" or cfg["layer_kinds"][-1] == kind
    model = build_model(cfg)
    params = weights.make_params(cfg, SEED)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    got = model.apply({"params": params}, tokens)
    rows = jnp.tile(jnp.arange(24)[None], (2, 1))
    want = reference.logits_at(cfg, SEED, tokens, rows)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_dropped_term_fails_the_tolerance(toy):
    """The comparison would see a layer computed without one of its
    terms: the reference with the scan's D skip zeroed, or with every
    attention bias zeroed, moves the logits by far more than TOL."""
    cfg, model, variables = toy
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg["vocab_size"], (1, 24)), jnp.int32)
    rows = jnp.arange(24)[None]
    whole = reference.logits_at(cfg, SEED, tokens, rows)

    class Without:
        embed, norm_f = staticmethod(weights.embed), staticmethod(
            weights.norm_f)

        def __init__(self, zeroed):
            self.zeroed = zeroed

        def layer(self, cfg, seed, i):
            p = weights.layer(cfg, seed, i)
            mixer = p["mixer"]
            for path in self.zeroed:
                node = mixer
                for key in path[:-1]:
                    node = node.get(key, {})
                if path[-1] in node:
                    node[path[-1]] = jnp.zeros_like(node[path[-1]])
            return p

    for zeroed in ([("D",)], [("qkv", "bias"), ("q", "bias"), ("o", "bias")]):
        cut = reference.logits_at(cfg, SEED, tokens, rows,
                                  weights=Without(zeroed))
        assert float(jnp.abs(cut - whole).max()) > 50 * TOL, zeroed


# -- through the engine's three kinds of cache ------------------------------

class Spy:
    """Every logits row the engine samples from, by request and
    position."""

    def __init__(self):
        self.rows = {}
        self._sample = engine_mod._sample

    def __call__(self, logits, req, pos):
        self.rows[(req.req_id, pos)] = np.array(logits, np.float32)
        return self._sample(logits, req, pos)


def _engine(model, variables, **kw):
    kw = {"max_batch_size": 3, "block_size": 4, "num_blocks": 96,
          "max_prefill_tokens": 16, "tile_q": 8, "max_seq_len": 120,
          "registry": MetricsRegistry(), **kw}
    return ServeEngine(model, variables, **kw)


def _reference_rows(cfg, prompt, generated):
    """The reference's logits at the positions the engine sampled from:
    the prompt's last, then each generated token's but the last."""
    seq = prompt + generated
    width = -(-len(seq) // 128) * 128
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(generated))
    return np.asarray(reference.logits_at(
        cfg, SEED, jnp.asarray(tokens), jnp.asarray(rows[None])))[0]


def _served_against_reference(cfg, eng, prompts, new_tokens):
    spy = Spy()
    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        reqs = [eng.add_request(p, max_new_tokens=new_tokens)
                for p in prompts]
        eng.run()
    for req, prompt in zip(reqs, prompts):
        out = ServeEngine._generated_of(req)
        assert len(out) == new_tokens
        want = _reference_rows(cfg, prompt, out)
        got = np.stack([spy.rows[(req.req_id, len(prompt) + j)]
                        for j in range(new_tokens)])
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert out == want.argmax(-1).tolist()
    return reqs


@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        toy, tier, monkeypatch):
    """Prompts of 23 (ends mid-block, block 4), 37 (three chunks of 16:
    every chunk boundary inside the 8-token window) and 5, decoded to
    contexts up to 49, six windows long, three at a time: prefill and
    decode through the paged pool, the window rings and the state slots
    against the reference's full forward pass, by logits; the two
    kernels interpreted, then their XLA references."""
    monkeypatch.setenv("PTPU_PAGED_KERNEL", tier)
    cfg, model, variables = toy
    eng = _engine(model, variables)
    prompts = _tokens(cfg, np.random.default_rng(3), 23, 37, 5)
    _served_against_reference(cfg, eng, prompts, 12)
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
    assert eng.cache.slots_in_use == 0


def test_a_slot_handed_on_reads_zeros(toy):
    """One slot: the second sequence is admitted into the slot the
    first left its state, tail and ring in, and is served as if alone."""
    cfg, model, variables = toy
    eng = _engine(model, variables, max_batch_size=1)
    prompts = _tokens(cfg, np.random.default_rng(4), 30, 11)
    reqs = _served_against_reference(cfg, eng, prompts, 6)
    assert [r.preemptions for r in reqs] == [0, 0]
    state = eng.cache.pools[eng.cache.kinds.index("state")]
    assert float(jnp.abs(state[1]).max()) > 0      # the slot was used
    assert float(jnp.abs(state[0]).max()) == 0     # the null slot never


def test_preemption_drops_the_state_and_recomputes_it(toy):
    """A sequence preempted in mid-decode loses its slot; readmitted,
    it re-prefills prompt + generated from position 0 into whatever
    slot is free and goes on with the tokens and logits of the
    undisturbed run."""
    cfg, model, variables = toy
    prompt = _tokens(cfg, np.random.default_rng(5), 19)[0]
    eng = _engine(model, variables)
    spy = Spy()
    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        req = eng.add_request(prompt, max_new_tokens=14)
        while req.num_generated < 6:
            eng.step()
        slot = eng.cache.slot(req.req_id)
        eng.scheduler.preempt(req)
        assert eng.cache.slots_in_use == 0 and slot == 1
        eng.run()
    out = ServeEngine._generated_of(req)
    assert req.preemptions == 1 and len(out) == 14
    want = _reference_rows(cfg, prompt, out)
    assert out == want.argmax(-1).tolist()
    got = np.stack([spy.rows[(req.req_id, len(prompt) + j)]
                    for j in range(14)])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    eng.cache.assert_quiesced()


def test_window_blocks_stay_under_the_bound_and_are_counted(toy):
    """The ring of a slot is ceil((window - 1 + chunk) / block) + 1
    blocks whatever the context: a sequence grown to 100 tokens holds
    at most that many at every step, `window_blocks_released` counts
    the logical blocks that fell behind, and the span field and the
    counter agree."""
    cfg, model, variables = toy
    eng = _engine(model, variables, max_batch_size=1)
    lay = eng.cache.layout
    assert lay.ring_blocks == -(-(8 - 1 + 16) // 4) + 1 == 7
    window_pool = eng.cache.pools[eng.cache.kinds.index("window")]
    assert window_pool.shape[0] == 1 + 1 * 7       # whatever the context
    prompt = _tokens(cfg, np.random.default_rng(6), 40)[0]
    prof.reset_profiler()
    req = eng.add_request(prompt, max_new_tokens=60)
    held = []
    while eng.step():
        if req.req_id in eng.cache._slot:
            pos = eng.cache.seq_len(req.req_id)
            held.append(eng.cache.ring_blocks_held(req.req_id, pos))
    assert max(held) <= lay.ring_blocks and max(held) >= 3
    # the last step's next query stood at 99: blocks wholly below 92
    released = (99 - 7) // 4
    assert eng.cache.window_blocks_released == released
    steps = [e for e in prof.get_events() if e["name"] == "engine.step"]
    assert sum(s["args"]["window_blocks_released"] for s in steps) == \
        released == eng.obs.get(
            "ptpu_kv_window_blocks_released_total").value
    paged_blocks = eng.cache.blocks_for(100)
    assert paged_blocks == 25 > lay.ring_blocks


def test_the_step_carries_the_three_scopes(toy):
    cfg, model, variables = toy
    eng = _engine(model, variables)
    t, nt, b = eng.flat_tokens, eng.num_tiles, eng.max_batch_size

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    text = eng._step_fn.lower(
        eng.variables, i32(t), i32(t), eng.cache.pools, [], [],
        i32(b + 1, eng.max_blocks_per_seq), i32(b + 1), i32(b + 1), i32(nt),
        i32(nt), i32(t), i32(b, 1)).as_text(debug_info=True)
    for scope in ("ssm_scan", "gated_memory", "diff_attention"):
        assert scope in text, scope


# -- the layout and the manager ---------------------------------------------

def test_the_model_declares_its_layout(toy):
    cfg, model, _ = toy
    kinds = [layer["kind"] for layer in model.cache_layout]
    assert kinds == ["state", "window", "state", "window", "state", "paged",
                     "none", "reads", "none", "reads"]
    assert model.cache_layout[7]["layer"] == 5
    assert model.cache_layout[1]["window"] == 8
    names = [(n, tuple(s), str(d))
             for n, s, d in model.cache_layout[0]["arrays"]]
    assert names == [("ssm", (4, 64), "float32"),
                     ("conv", (3 * 64,), "float32")]
    lay = CacheLayout(model.cache_layout, block_size=4, slots=3,
                      chunk_tokens=16)
    arrays = lay.arrays((96, 4, 128), jnp.float32)
    assert [k for k, _, _ in arrays] == (
        ["state", "state", "window"] * 2 + ["state", "state", "paged",
                                            "rows"])
    assert arrays[2][1] == (1 + 3 * 7, 4, 128)
    assert arrays[-1][1] == (4, 8)
    assert lay.ring(2) == list(range(8, 15))


def test_a_layout_reads_only_paged_layers():
    with pytest.raises(ValueError, match="keeps no paged pool"):
        CacheLayout([{"kind": "none"}, {"kind": "reads", "layer": 0}], 4, 2,
                    8)
    with pytest.raises(ValueError, match="unknown cache kind"):
        CacheLayout([{"kind": "ring"}], 4, 2, 8)


def test_admission_counts_slots(toy):
    """Two slots: the third request waits for one though paged blocks
    abound, and a direct allocation without a slot raises."""
    cfg, model, variables = toy
    eng = _engine(model, variables, max_batch_size=2)
    for p in _tokens(cfg, np.random.default_rng(7), 9, 9, 9):
        eng.add_request(p, max_new_tokens=4)
    eng.step()
    assert len(eng.scheduler.running) == 2 and eng.scheduler.queue_depth == 1
    assert eng.cache.slots_in_use == 2
    assert not eng.cache.can_allocate([1, 2, 3])
    with pytest.raises(CacheExhausted, match="state slot"):
        eng.cache.alloc_sequence(10**9, [1, 2, 3])
    eng.run()
    eng.cache.assert_quiesced()


def test_block_copies_leave_slots_alone(toy):
    """The fixed-width block copy moves blocks of the paged pools only:
    state, rings and the rows table come back as they went in."""
    cfg, model, variables = toy
    eng = _engine(model, variables)
    pools = [jnp.full(p.shape, i + 1, p.dtype)
             for i, p in enumerate(eng.cache.pools)]
    src = jnp.asarray([3] + [0] * 7, jnp.int32)
    dst = jnp.asarray([5] + [0] * 7, jnp.int32)
    want = [np.asarray(p) for p in pools]
    out = eng._copy_blocks(pools, src, dst)
    assert len(out) == len(want) == len(eng.cache.kinds)
    for kind, a, b in zip(eng.cache.kinds, out, want):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=kind)


@pytest.mark.parametrize("kwargs,words", [
    ({"spec_k": 2}, "spec_k=2 over"),
    ({"host_tier_bytes": 1 << 20}, "host_tier_bytes=1048576 over"),
    ({"kv_compress_blocks": 8}, "kv_compress_blocks=8 over"),
    ({"tp_size": 2}, "tp_size=2 over"),
    ({"demote_finished": True}, "kvxfer"),
], ids=["speculation", "host_tier", "int8_tier", "tp", "kvxfer"])
def test_what_slots_cannot_do_refuses_at_construction(toy, kwargs, words):
    cfg, model, variables = toy
    with pytest.raises(ValueError, match=words) as e:
        _engine(model, variables, **kwargs)
    assert "recurrent state or a window ring" in str(e.value)
    assert "serve it with" in str(e.value)


@pytest.mark.parametrize("kwargs,words", [
    ({"spec_k": 2}, "spec_k=2 over"),
    ({"host_tier_bytes": 1 << 20}, "host_tier_bytes=1048576 over"),
    ({"kv_compress_blocks": 8}, "kv_compress_blocks=8 over"),
], ids=["speculation", "host_tier", "int8_tier"])
def test_the_prefix_cache_leaves_the_other_refusals_standing(toy, kwargs,
                                                              words):
    cfg, model, variables = toy
    with pytest.raises(ValueError, match=words):
        _engine(model, variables, enable_prefix_cache=True, **kwargs)


# -- prefix reuse over the scan's state, the tail and the rings --------------

def _rows_of(eng, prompts, new_tokens):
    spy = Spy()
    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        reqs = [eng.add_request(p, max_new_tokens=new_tokens)
                for p in prompts]
        eng.run()
    return reqs, [np.stack([spy.rows[(r.req_id, len(p) + j)]
                            for j in range(new_tokens)])
                  for r, p in zip(reqs, prompts)]


def test_the_prefix_cache_works_over_state_by_snapshots(toy):
    """`enable_prefix_cache=True` no longer refuses: every 16 tokens of
    a prompt (four blocks of 4) the slot's scan state, convolution tail
    and window rings are snapshot. A second prompt that shares 43 tokens
    of the first's 50 hits 32 deep, its slot restored, and is served
    bit for bit as by an engine without the cache; a third that shares
    all of them hits 48 deep. The unset default stays off."""
    cfg, model, variables = toy
    first = _tokens(cfg, np.random.default_rng(21), 50)[0]
    second = first[:43] + _tokens(cfg, np.random.default_rng(22), 7)[0]
    third = first + [3, 1, 4]
    eng = _engine(model, variables, enable_prefix_cache=True,
                  snapshot_tokens=16, snapshot_slots=4)
    assert eng.cache.snapshot_every == 16
    assert len(eng.cache.snaps) == eng.cache.kinds.count("window") \
        + eng.cache.kinds.count("state")
    _rows_of(eng, [first], 3)
    assert eng.cache.snapshots_held == 3           # at 16, 32 and 48
    reqs, hit = _rows_of(eng, [second, third], 8)
    assert [r.cached_tokens for r in reqs] == [32, 48]
    plain = _engine(model, variables)
    assert plain.cache.enable_prefix_cache is False
    assert plain.cache.snapshot_every == 0 and not plain.cache.snaps
    reqs2, cold = _rows_of(plain, [second, third], 8)
    assert [r.cached_tokens for r in reqs2] == [0, 0]
    for a, b in zip(hit, cold):
        np.testing.assert_array_equal(a, b)
    want = _reference_rows(cfg, second, ServeEngine._generated_of(reqs[0]))
    np.testing.assert_allclose(hit[0], want, atol=TOL, rtol=0)
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()


def test_a_snapshot_holds_rings_tail_and_state(toy):
    """What a snapshot place holds after a prompt's boundary is what the
    slot held there: every state array's entry and every ring block,
    place for place."""
    cfg, model, variables = toy
    eng = _engine(model, variables, enable_prefix_cache=True,
                  snapshot_tokens=16, snapshot_slots=2, max_batch_size=1)
    prompt = _tokens(cfg, np.random.default_rng(23), 16)[0] + [5]
    req = eng.add_request(prompt, max_new_tokens=1)
    # the chunk [0, 16), collected with nothing launched behind it: the
    # pool is read as the boundary's chunk left it (tests/test_engine.py
    # holds a snapshot to its boundary with the next step out)
    eng._step(ahead=False)
    assert req.prefill_pos == 16 and eng.cache.snapshots_held == 1
    place, blocks, _ = eng.cache._snap_index[tuple(prompt[:16])]
    slot = eng.cache.slot(req.req_id)
    lay = eng.cache.layout
    assert len(blocks) == 4
    for at, snap in zip(eng.cache.snap_places, eng.cache.snaps):
        pool, kind = eng.cache.pools[at], eng.cache.kinds[at]
        if kind == "window":
            first = 1 + (place - 1) * lay.ring_blocks
            np.testing.assert_array_equal(
                np.asarray(snap[first:first + lay.ring_blocks]),
                np.asarray(pool[jnp.asarray(lay.ring(slot))]))
        else:
            np.testing.assert_array_equal(np.asarray(snap[place]),
                                          np.asarray(pool[slot]))
            assert float(jnp.abs(snap[place]).max()) > 0
    eng.run()
    eng.cache.assert_quiesced()


def test_forks_refuse(toy):
    cfg, model, variables = toy
    eng = _engine(model, variables)
    with pytest.raises(ValueError, match="n=2 over recurrent state"):
        eng.add_request([1, 2, 3], n=2)
    eng.cache.alloc_sequence(7, [1, 2, 3])
    with pytest.raises(ValueError, match="fork over recurrent state"):
        eng.cache.fork_sequence(7, 8)
    eng.cache.free_sequence(7)
    # and unset, the prefix cache is off: this model asks for no
    # snapshots of its own
    assert eng.cache.enable_prefix_cache is False


# -- the two kernels ---------------------------------------------------------

def _packing(seed, dn=128, n=4, tq=8, dtype=jnp.float32):
    """A step of four rows and the null row: a chunk of 19 tokens that
    opens its sequence, a decode row, a chunk of 8 from position 16 and
    a chunk of 3 (a partial tile), then pad tiles."""
    r = np.random.default_rng(seed)
    row_slots = jnp.asarray([2, 4, 1, 3, 0], jnp.int32)
    q_starts = jnp.asarray([0, 7, 16, 30, 0], jnp.int32)
    ctx = jnp.asarray([19, 8, 24, 33, 1], jnp.int32)
    tile_rows = jnp.asarray([0, 0, 0, 1, 2, 3, 4, 4, 4, 4], jnp.int32)
    tile_offs = jnp.asarray([0, 8, 16, 0, 0, 0, 0, 0, 0, 0], jnp.int32)
    meta = scan.tile_meta(row_slots, ctx, q_starts, tile_rows, tile_offs, tq)
    t = tile_rows.shape[0] * tq
    u = jnp.asarray(r.normal(size=(t, dn)), dtype)
    delta = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                         size=(t, dn))), jnp.float32)
    a = -jnp.asarray(np.tile(np.arange(1, n + 1, dtype=np.float32)[:, None],
                             (1, dn)))
    b = jnp.asarray(r.normal(size=(t, n)), jnp.float32)
    c = jnp.asarray(r.normal(size=(t, n)), jnp.float32)
    d = jnp.asarray(r.normal(size=(dn,)), jnp.float32)
    state = jnp.asarray(r.normal(size=(6, n, dn)), jnp.float32)
    return (u, delta, a, b, c, d, state), meta, tile_offs


def test_tile_meta_reads_the_packing():
    _, (slots, real, fresh, last), _ = _packing(0)
    assert slots.tolist() == [2, 2, 2, 4, 1, 3, 0, 0, 0, 0]
    assert real.tolist() == [8, 8, 3, 1, 8, 3, 0, 0, 0, 0]
    assert fresh.tolist() == [1, 0, 0, 0, 0, 0, 0, 0, 0, 0]
    assert last.tolist() == [0, 0, 1, 1, 1, 1, 0, 0, 0, 0]


@pytest.mark.parametrize("seed,dtype", [(0, jnp.float32), (1, jnp.float32),
                                        (2, jnp.bfloat16)])
def test_scan_kernel_agrees_with_its_reference(seed, dtype):
    """The interpreted kernel against the `lax.scan` over positions on
    a packing with decode rows, chunks, a partial tile and the null
    row: outputs and states to float32 rounding (the kernel sums the
    states in another order), untouched slots bit for bit."""
    args, (slots, real, fresh, _), _ = _packing(seed, dtype=dtype)
    y0, s0 = scan.ragged_selective_scan_reference(*args, slots, real, fresh)
    y1, s1 = scan.ragged_selective_scan(*args, slots, real, fresh,
                                        use_kernel=True, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(s1, s0, atol=1e-5, rtol=1e-5)
    for idle in (0, 5):
        np.testing.assert_array_equal(s1[idle], args[-1][idle])
        np.testing.assert_array_equal(s0[idle], args[-1][idle])


@pytest.mark.parametrize("tier", [False, True], ids=["reference", "kernel"])
def test_pads_of_a_decode_tile_leave_the_state_as_it_was(tier):
    """A decode tile walks one token: the state after it is one
    update of the state before, whatever its seven pads hold, and the
    pads' outputs are 0."""
    (u, delta, a, b, c, d, state), (slots, real, fresh, _), _ = _packing(3)
    y, new = scan.ragged_selective_scan(
        u, delta, a, b, c, d, state, slots, real, fresh, use_kernel=tier,
        interpret=True if tier else None)
    i = 3 * 8                                  # the decode row's token
    want = (jnp.exp(delta[i][None] * a) * state[4]
            + (delta[i] * u[i])[None] * b[i][:, None])
    np.testing.assert_allclose(new[4], want, atol=1e-6, rtol=1e-6)
    assert float(jnp.abs(y[i + 1:i + 8]).max()) == 0.0
    noisy = u.at[i + 1:i + 8].set(1e6)
    _, again = scan.ragged_selective_scan(
        noisy, delta, a, b, c, d, state, slots, real, fresh, use_kernel=tier,
        interpret=True if tier else None)
    np.testing.assert_array_equal(again, new)


def test_conv_reads_and_writes_the_tail():
    """The packed convolution against a dense one over each row's
    whole history [tail | tokens], and the tail each row leaves."""
    (x, *_), (slots, real, fresh, last), tile_offs = _packing(4)
    r = np.random.default_rng(5)
    k, d = 4, x.shape[1]
    tails = jnp.asarray(r.normal(size=(6, (k - 1) * d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(k, d)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(d,)), jnp.float32)
    out, new = scan.ragged_causal_conv(x, tails, w, bias, slots, real, fresh,
                                       last, tile_offs)
    # rows: (first flat token, tokens, slot, opens its sequence)
    for first, n, slot, opens in ((0, 19, 2, True), (24, 1, 4, False),
                                  (32, 8, 1, False), (40, 3, 3, False)):
        before = (np.zeros((k - 1, d), np.float32) if opens else
                  np.asarray(tails[slot]).reshape(k - 1, d))
        hist = np.concatenate([before, np.asarray(x[first:first + n])])
        want = np.asarray(bias) + sum(
            np.asarray(w[j]) * hist[j:j + n] for j in range(k))
        np.testing.assert_allclose(out[first:first + n], want, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(new[slot]).reshape(k - 1, d), hist[-(k - 1):],
            atol=1e-6)
    for idle in (0, 5):
        np.testing.assert_array_equal(new[idle], tails[idle])


def _window_case(seed, bs, window, ring, mb):
    """Five rows over rings (logical block b in ring place b mod ring),
    with a dense oracle of the window."""
    r = np.random.default_rng(seed)
    hkv, d, groups, tq = 2, 16, 2, 8
    h = hkv * groups
    rows = [(0, 13, 1), (37, 1, 2), (20, 9, 3), (3, 1, 4), (40, 6, 5)]
    nrows = len(rows) + 1
    nb = 1 + len(rows) * ring
    keys = r.normal(size=(len(rows), mb * bs, hkv, d)).astype(np.float32)
    vals = r.normal(size=(len(rows), mb * bs, hkv, d)).astype(np.float32)
    pool = np.zeros((nb, bs, hkv, paged.head_lanes(d)), np.float32)
    bt = np.zeros((nrows, mb), np.int32)
    cl = np.ones((nrows,), np.int32)
    qs = np.zeros((nrows,), np.int32)
    tile_rows, tile_offs = [], []
    for i, (start, length, slot) in enumerate(rows):
        bt[i] = 1 + (slot - 1) * ring + np.arange(mb) % ring
        cl[i], qs[i] = start + length, start
        for p in range(start + length):    # later positions write over
            pool[bt[i, p // bs], p % bs, :, :d] = keys[i, p]
            pool[bt[i, p // bs], p % bs, :, d:2 * d] = vals[i, p]
        for j in range(-(-length // tq)):
            tile_rows.append(i)
            tile_offs.append(j * tq)
    tile_rows += [nrows - 1] * 2
    tile_offs += [0] * 2
    t = len(tile_rows) * tq
    q = r.normal(size=(t, h, d)).astype(np.float32)
    want = np.zeros((t, h, d), np.float32)
    real = np.zeros((t,), bool)
    tile = 0
    for i, (start, length, _) in enumerate(rows):
        for j in range(length):
            p = start + j
            lo = max(0, p - window + 1)
            for head in range(h):
                kk = keys[i, lo:p + 1, head // groups]
                sc = kk @ q[tile * tq + j, head] / np.sqrt(d)
                wgt = np.exp(sc - sc.max())
                want[tile * tq + j, head] = \
                    wgt / wgt.sum() @ vals[i, lo:p + 1, head // groups]
            real[tile * tq + j] = True
        tile += -(-length // tq)
    args = (jnp.asarray(q), jnp.asarray(pool.reshape(nb, bs, -1)),
            jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(qs),
            jnp.asarray(tile_rows, jnp.int32),
            jnp.asarray(tile_offs, jnp.int32))
    return args, want, real


@pytest.mark.parametrize("bs,window,ring", [(4, 8, 6), (4, 16, 8),
                                            (2, 8, 12), (16, 24, 3)])
def test_window_kernel_reads_rings(bs, window, ring):
    """The interpreted kernel and the XLA reference over rings whose
    places behind the window hold newer blocks' rows: both agree with a
    dense softmax over each query's window, and pads stay finite."""
    args, want, real = _window_case(1, bs, window, ring, mb=48 // bs)
    for kw in ({"use_kernel": False},
               {"use_kernel": True, "interpret": True}):
        got = np.asarray(paged.ragged_paged_attention(
            *args, groups=2, window=window, **kw))
        np.testing.assert_allclose(got[real], want[real], atol=2e-6)
        assert np.isfinite(got).all()


def test_window_narrower_than_a_tile_refuses():
    args, _, _ = _window_case(1, 4, 8, 6, mb=12)
    with pytest.raises(ValueError, match="narrower than the query tile"):
        paged.ragged_paged_attention(*args, groups=2, window=4,
                                     use_kernel=True, interpret=True)
