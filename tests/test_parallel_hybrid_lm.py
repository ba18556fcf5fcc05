"""The decoder of attention and Mamba-2 side by side
(`models/parallel_hybrid_lm.py`) against the benchmark's plain
reference (`benchmarks/reference_falconh1.py`), at the toy sizes of
`benchmarks/configs/falcon-h1-34b.json` on seeded weights: the published
form, then chunked prefill and decode through the engine's paged pools
and state slots (a layer keeping both), a slot handed on, a preempted
row, prefix hits over snapshots, the SSD kernel, and a layout layer of
two kinds.

Tolerances. Everything here is float32 on one backend, and the two
sides differ in formulation, not in precision: the reference scans a
sequence a position at a time and attends over the full score matrix,
the engine walks a step's tiles in the block form from a slot and reads
the paged pool. Logits have unit scale (the head's gain undoes
lm_head_multiplier), so 2e-4 is a dozen float32 roundings through two
layers; each ablation below moves them by a thousand times that.
"""

import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_falconh1 as reference
from benchmarks import weights_falconh1 as weights
from benchmarks.common import build_model
from paddle_tpu.engine import engine as engine_mod
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.engine.paged_cache import (CacheLayout, PagedKVCache,
                                          refuse_slots)
from paddle_tpu.kernels import lightning_attention as recurrence
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.kernels.paged_attention import head_lanes
from paddle_tpu.models import parallel_hybrid_lm
from paddle_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_019
TOL = 2e-4


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        return json.load(f)


def _toy() -> dict:
    cfg = _config()
    return {**cfg, **cfg["toy"]}


@pytest.fixture(scope="module")
def toy():
    cfg = _toy()
    model = build_model(cfg)
    return cfg, model, {"params": weights.make_params(cfg, SEED)}


def _tokens(cfg, rng, *lens):
    return [rng.integers(0, cfg["vocab_size"], n).tolist() for n in lens]


# -- the published form ------------------------------------------------------

def test_forward_agrees_with_the_reference(toy):
    """The model's whole-sequence form against the reference at 128
    positions."""
    cfg, model, variables = toy
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 128)), jnp.int32)
    got = model.apply(variables, tokens)
    rows = jnp.broadcast_to(jnp.arange(128), (2, 128))
    want = reference.logits_at(cfg, SEED, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=0)


def test_the_configuration_is_the_published_one_cut_in_depth():
    cfg = _config()
    assert cfg["parameters"] == weights.count_params(cfg) == 5_254_594_112
    assert cfg["num_hidden_layers"] == 6 and cfg["reduced"] == [
        "num_hidden_layers"] and cfg["published"]["num_hidden_layers"] == 72
    # a layer: attention 31.46 M, Mamba-2 68.35 M, MLP 330.30 M
    layer = sum(int(np.prod(shape)) for shape, _, _ in
                weights.layer_shapes(cfg).values())
    assert layer == 430_120_032
    # the published widths and multipliers, as the source's config has them
    published = {
        "hidden_size": 5120, "num_attention_heads": 20,
        "num_key_value_heads": 4, "head_dim": 128,
        "intermediate_size": 21504, "vocab_size": 261120,
        "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256,
        "mamba_n_groups": 2, "mamba_d_conv": 4, "rope_theta": 1e11,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284]}
    assert {k: cfg[k] for k in published} == published
    # the tree the program builds holds as many, at the toy widths
    toy = _toy()
    tree = jax.eval_shape(build_model(toy).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    assert sum(x.size for x in jax.tree.leaves(tree)) \
        == weights.count_params(toy)


# -- through the engine's pools and slots -----------------------------------

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
    kw = {"max_batch_size": 3, "block_size": 8, "num_blocks": 96,
          "max_prefill_tokens": 16, "tile_q": 8, "max_seq_len": 128,
          "registry": MetricsRegistry(), **kw}
    return ServeEngine(model, variables, **kw)


def _reference_rows(cfg, prompt, generated):
    seq = prompt + generated
    width = -(-len(seq) // 128) * 128
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(generated))
    return np.asarray(reference.logits_at(
        cfg, SEED, jnp.asarray(tokens), jnp.asarray(rows[None])))[0]


def _serve(eng, prompts, new_tokens):
    """(requests, their sampled logits rows [new_tokens, V] each)."""
    spy = Spy()
    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        reqs = [eng.add_request(p, max_new_tokens=new_tokens)
                for p in prompts]
        eng.run()
    rows = [np.stack([spy.rows[(r.req_id, len(p) + j)]
                      for j in range(new_tokens)])
            for r, p in zip(reqs, prompts)]
    return reqs, rows


def _served_against_reference(cfg, eng, prompts, new_tokens):
    reqs, rows = _serve(eng, prompts, new_tokens)
    for req, prompt, got in zip(reqs, prompts, rows):
        out = ServeEngine._generated_of(req)
        assert len(out) == new_tokens
        want = _reference_rows(cfg, prompt, out)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert out == want.argmax(-1).tolist()
    return reqs


@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        toy, tier, monkeypatch):
    """Prompts of 23 (ends mid-block), 37 (three chunks of 16) and 5,
    three at a time in three slots: prefill and decode through the
    paged pools and the state slots of the same layers against the
    reference's full forward pass, by logits; the kernels interpreted,
    then their XLA references."""
    monkeypatch.setenv("PTPU_PAGED_KERNEL", tier)
    cfg, model, variables = toy
    eng = _engine(model, variables)
    assert not eng.cache.enable_prefix_cache     # no snapshots asked for
    prompts = _tokens(cfg, np.random.default_rng(3), 23, 37, 5)
    _served_against_reference(cfg, eng, prompts, 12)
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
    assert eng.cache.slots_in_use == 0


def test_a_slot_handed_on_reads_zeros(toy):
    """One slot: the second sequence is admitted into the slot the
    first left its state and tail in, and is served as if alone."""
    cfg, model, variables = toy
    eng = _engine(model, variables, max_batch_size=1)
    prompts = _tokens(cfg, np.random.default_rng(4), 30, 11)
    reqs = _served_against_reference(cfg, eng, prompts, 6)
    assert [r.preemptions for r in reqs] == [0, 0]
    state = eng.cache.pools[eng.cache.kinds.index("state")]
    assert float(jnp.abs(state[1]).max()) > 0      # the slot was used
    assert float(jnp.abs(state[0]).max()) == 0     # the null slot never


def test_preemption_drops_the_state_and_recomputes_it(toy):
    """A sequence preempted in mid-decode gives back its blocks and its
    slot; readmitted, it re-prefills prompt + generated from position 0
    and goes on with the tokens and logits of the undisturbed run."""
    cfg, model, variables = toy
    prompt = _tokens(cfg, np.random.default_rng(5), 19)[0]
    eng = _engine(model, variables)
    spy = Spy()
    with mock.patch.multiple(engine_mod, _sample=spy,
                             _needs_logits=lambda req: True):
        req = eng.add_request(prompt, max_new_tokens=14)
        while req.num_generated < 6:
            eng.step()
        eng.scheduler.preempt(req)
        assert eng.cache.slots_in_use == 0
        assert eng.cache.occupancy() == 0.0
        eng.run()
    out = ServeEngine._generated_of(req)
    assert req.preemptions == 1 and len(out) == 14
    want = _reference_rows(cfg, prompt, out)
    assert out == want.argmax(-1).tolist()
    got = np.stack([spy.rows[(req.req_id, len(prompt) + j)]
                    for j in range(14)])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    eng.cache.assert_quiesced()


def test_a_hit_restored_from_a_snapshot_equals_the_uncached_run(toy):
    """Snapshots of a layer of two kinds work as over separate layers:
    asked for every 32 positions, a prompt of 75 leaves two; a second
    prompt sharing its first 70 tokens hits 64 deep, its paged blocks
    shared and its slot restored; its logits are the reference's."""
    cfg, model, variables = toy
    first = _tokens(cfg, np.random.default_rng(11), 75)[0]
    second = first[:70] + _tokens(cfg, np.random.default_rng(12), 9)[0]
    eng = _engine(model, variables, snapshot_tokens=32, snapshot_slots=4)
    assert eng.cache.enable_prefix_cache and eng.cache.snapshot_every == 32
    _serve(eng, [first], 4)
    assert eng.cache.snapshots_held == 2
    reqs = _served_against_reference(cfg, eng, [second], 8)
    assert reqs[0].cached_tokens == 64
    eng.cache.assert_quiesced()


_ABLATIONS = {
    # the attention's branch, the scan's branch, or the multipliers of
    # the input projection's five blocks left out of the program
    "attention": dict(finish=lambda f: lambda self, cx, blk, x, a, m: f(
        self, cx, blk, x, jnp.zeros_like(a), m)),
    "ssd": dict(finish=lambda f: lambda self, cx, blk, x, a, m: f(
        self, cx, blk, x, a, jnp.zeros_like(m))),
    "ssm_multipliers": dict(config={"ssm_multipliers": [1.0] * 5}),
}


@pytest.mark.parametrize("ablation", sorted(_ABLATIONS))
def test_each_mechanism_moves_the_logits_past_the_tolerance(toy, ablation):
    """The program served without one of its mechanisms misses the
    reference by far more than the tolerance: the comparison sees
    every one."""
    cfg, _, variables = toy
    how = _ABLATIONS[ablation]
    model = build_model({**cfg, **how.get("config", {})})
    prompt = _tokens(cfg, np.random.default_rng(7), 21)
    finish = parallel_hybrid_lm.ParallelHybridLM._finish
    with mock.patch.object(parallel_hybrid_lm.ParallelHybridLM, "_finish",
                           how.get("finish", lambda f: f)(finish)):
        reqs, rows = _serve(_engine(model, variables), prompt, 6)
    want = _reference_rows(cfg, prompt[0], ServeEngine._generated_of(reqs[0]))
    assert np.abs(rows[0] - want).max() > 1000 * TOL


# -- the SSD kernel ----------------------------------------------------------

def _packing(seed, heads=4, groups=2, n=16, p=8):
    """Four rows over 8 tiles of 8: a chunk of 13 from position 5 over
    two tiles (slot 2), a decode row at 30 (slot 4), a fresh chunk of 6
    that opens its sequence (slot 3, whose old state must not leak), the
    null row's pad tiles; slots 0, 1 and 5 idle."""
    r = np.random.default_rng(seed)
    tq = 8
    ctx = jnp.asarray([18, 31, 6, 0], jnp.int32)
    q_starts = jnp.asarray([5, 30, 0, 0], jnp.int32)
    tile_rows = jnp.asarray([0, 0, 1, 2, 3, 3, 3, 3], jnp.int32)
    tile_offs = jnp.asarray([0, 8, 0, 0, 0, 0, 0, 0], jnp.int32)
    row_slots = jnp.asarray([2, 4, 3, 0], jnp.int32)
    meta = scan.tile_meta(row_slots, ctx, q_starts, tile_rows, tile_offs, tq)
    t = 8 * tq
    x = jnp.asarray(r.normal(size=(t, heads, p)), jnp.float32)
    delta = jnp.asarray(np.log1p(np.exp(r.normal(size=(t, heads)))),
                        jnp.float32)                  # per token and head
    a = -jnp.asarray(np.geomspace(0.05, 2.0, heads), jnp.float32)
    b, c = (jnp.asarray(r.normal(size=(t, groups, n)) * 0.3, jnp.float32)
            for _ in range(2))
    d = jnp.asarray(r.normal(size=(heads,)), jnp.float32)
    state = jnp.asarray(r.normal(size=(6, heads, n, p)), jnp.float32)
    return (x, delta, a, b, c, d, state), meta, tile_offs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ssd_kernel_agrees_with_its_reference(seed):
    """The interpreted kernel (a decode tile's rank-1 path, a chunk's
    block form with a decay per token and head, a row's state handed
    from tile to tile, both groups of keys and queries, the skip)
    against the recurrence a position at a time. 1e-4: the block form
    sums a tile's eight products in another order."""
    args, (slots, real, fresh, _), offs = _packing(seed)
    want_y, want_s = recurrence.ragged_ssd(*args, slots, real, fresh, offs,
                                           use_kernel=False)
    got_y, got_s = recurrence.ragged_ssd(*args, slots, real, fresh, offs,
                                         use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("tier", [False, True], ids=["reference", "kernel"])
def test_ssd_pads_leave_the_state_as_it_was(tier):
    """Slots no real token walks come back bit for bit, pad positions
    read 0, and a row that opens its sequence starts from zeros
    whatever its slot held."""
    args, (slots, real, fresh, _), offs = _packing(3)
    state = args[-1]
    y, new = recurrence.ragged_ssd(*args, slots, real, fresh, offs,
                                   use_kernel=tier, interpret=True)
    for idle in (0, 1, 5):
        np.testing.assert_array_equal(np.asarray(new[idle]),
                                      np.asarray(state[idle]))
    live = np.repeat(np.arange(8)[None], 8, 0) < np.asarray(real)[:, None]
    assert float(jnp.abs(y.reshape(8, 8, -1)[~live]).max()) == 0.0
    zeroed, _ = recurrence.ragged_ssd(
        *args[:-1], state.at[3].set(0.0), slots, real, fresh, offs,
        use_kernel=tier, interpret=True)
    np.testing.assert_array_equal(np.asarray(y[24:32]),
                                  np.asarray(zeroed[24:32]))


@pytest.mark.parametrize("tier", [False, True], ids=["reference", "kernel"])
def test_lightning_is_the_ssd_with_a_constant_decay(tier):
    """One body: lightning attention over q, k, v is the SSD with a
    group a head, delta 1, A_h the constant log-decay, B = k, C = q and
    no skip."""
    args, (slots, real, fresh, _), offs = _packing(4, heads=4, groups=4,
                                                   n=8, p=8)
    x, _, a, k, q, _, state = args
    want = recurrence.ragged_lightning_attention(
        q, k, x, a, state, slots, real, fresh, offs, use_kernel=tier,
        interpret=True)
    got = recurrence.ragged_ssd(
        x, jnp.ones(x.shape[:2], jnp.float32), a, k, q,
        jnp.zeros_like(a), state, slots, real, fresh, offs,
        use_kernel=tier, interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=0)


def test_ssd_state_stays_float32():
    args, (slots, real, fresh, _), offs = _packing(5)
    with pytest.raises(ValueError, match="float32"):
        recurrence.ragged_ssd(*args[:-1], args[-1].astype(jnp.bfloat16),
                              slots, real, fresh, offs)


# -- a layout layer of two kinds ---------------------------------------------

STATE = (("ssm", (4, 16, 8), jnp.dtype(jnp.float32)),
         ("conv", (3 * 64,), jnp.dtype(jnp.float32)))


def test_the_model_declares_both_kinds_in_every_layer(toy):
    cfg, model, variables = toy
    assert [layer["kind"] for layer in model.cache_layout] == ["paged"] * 2
    eng = _engine(model, variables)
    assert eng.cache.kinds == ["paged", "state", "state"] * 2 + ["rows"]
    pool, ssm, tails = eng.cache.pools[:3]
    assert pool.shape == (96, 8, 2 * head_lanes(8))  # 2 kv heads, [k | v]
    assert ssm.shape == (3 + 1, 4, 16, 8) and ssm.dtype == jnp.float32
    assert tails.shape == (3 + 1, 3 * (32 + 2 * 2 * 16))


def test_a_layer_of_two_kinds_holds_blocks_and_a_slot():
    """Admission counts blocks and a slot, freeing gives both back, a
    full slot table refuses, and what a slotted cache cannot do is
    refused at construction."""
    layout = CacheLayout([{"kind": "paged", "arrays": STATE}], 8, 2, 16)
    assert layout.has_slots
    assert [k for k, _, _ in layout.arrays((32, 8, 16), jnp.float32)] == [
        "paged", "state", "state", "rows"]
    cache = PagedKVCache(1, 32, 8, 2, 4, layout=layout,
                         enable_prefix_cache=False)
    free = cache.free_blocks
    cache.alloc_sequence(1, list(range(20)))
    cache.alloc_sequence(2, list(range(3)))
    assert cache.slots_in_use == 2 and cache.free_blocks == free - 4
    assert not cache.can_allocate(4)        # blocks are left, no slot
    cache.free_sequence(1)
    assert cache.slots_in_use == 1 and cache.free_blocks == free - 1
    assert cache.can_allocate(4)
    cache.free_sequence(2)
    cache.assert_quiesced()
    with pytest.raises(ValueError, match="spec_k"):
        refuse_slots(2, 0, 0, 1, False)
    with pytest.raises(ValueError, match="host_tier"):
        refuse_slots(0, 1 << 20, 0, 1, False)
    with pytest.raises(ValueError, match="keeps no state"):
        CacheLayout([{"kind": "window", "window": 8, "arrays": STATE}], 8,
                    2, 16)
