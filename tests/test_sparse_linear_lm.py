"""The decoder of block-sparse and lightning layers
(`models/sparse_linear_lm.py`) against the benchmark's plain reference
(`benchmarks/reference_sala.py`), at the toy sizes of
`benchmarks/configs/minicpm-sala.json` on seeded weights: the published
form, then chunked prefill and decode through the engine's paged pools,
index pool and state slots, prefix hits restored from state snapshots,
and the two kernels this block brought.

Toy selection: blocks of 8, dense up to 32 positions, then the first
block, the blocks of the 16 newest positions and the best 2 of the
rest: 6 blocks of a context's 10 and more, so past position 47 blocks
are really dropped.

Tolerances. Everything here is float32 on one backend, and the two
sides differ in formulation, not in precision: the reference masks the
full score matrix, the engine reads compacted tables and block masks;
the reference scans a sequence a position at a time, the engine a
step's tiles in the block form from a slot. Logits have unit scale, so
2e-4 is a dozen float32 roundings through four layers; attention over
every block in the selection's place moves them by a hundred times
that (`test_dense_attention_in_the_selection_s_place_fails`).
"""

import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sala as reference
from benchmarks import weights_sala as weights
from benchmarks.common import build_model
from paddle_tpu.engine import engine as engine_mod
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.kernels import lightning_attention as lightning
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.models import sparse_linear_lm
from paddle_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_011
TOL = 2e-4


def _toy(layers=None) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["toy"]}
    if layers is not None:
        cfg["mixer_types"] = cfg["mixer_types"][:layers]
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

@pytest.mark.parametrize("layers", [1, 2, 4],
                         ids=["lightning", "sparse", "all"])
def test_forward_agrees_with_the_reference(layers):
    """The model's whole-sequence form against the reference at 128
    positions: three dense blocks, then selection."""
    cfg = _toy(layers)
    model = build_model(cfg)
    params = weights.make_params(cfg, SEED)
    tokens = jnp.asarray(np.random.default_rng(layers).integers(
        0, cfg["vocab_size"], (1, 128)), jnp.int32)
    got = model.apply({"params": params}, tokens)
    rows = jnp.arange(128)[None]
    want = reference.logits_at(cfg, SEED, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=0)


def test_the_configuration_counts_its_parameters_and_its_slice():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    assert cfg["parameters"] == weights.count_params(cfg) == 5_039_448_064
    assert cfg["mixer_types"] == cfg["published"]["mixer_types"][8:24]
    assert cfg["first_layer"] == 8 and cfg["num_hidden_layers"] == 16
    assert sorted(cfg["reduced"]) == ["mixer_types", "num_hidden_layers"]


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


def _served_against_reference(cfg, eng, prompts, new_tokens, tol=TOL):
    reqs, rows = _serve(eng, prompts, new_tokens)
    for req, prompt, got in zip(reqs, prompts, rows):
        out = ServeEngine._generated_of(req)
        assert len(out) == new_tokens
        want = _reference_rows(cfg, prompt, out)
        np.testing.assert_allclose(got, want, atol=tol, rtol=0)
        assert out == want.argmax(-1).tolist()
    return reqs


@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        toy, tier, monkeypatch):
    """Prompts of 23 (dense, decoded across dense_len 32 to 35), 75
    (five chunks of 16, the last three past dense_len: ten blocks of
    which a query keeps six) and 5, three at a time: prefill and decode
    through the paged pools, the index pool and the state slots against
    the reference's full forward pass, by logits; the kernels
    interpreted, then their XLA references."""
    monkeypatch.setenv("PTPU_PAGED_KERNEL", tier)
    cfg, model, variables = toy
    eng = _engine(model, variables, enable_prefix_cache=False)
    prompts = _tokens(cfg, np.random.default_rng(3), 23, 75, 5)
    _served_against_reference(cfg, eng, prompts, 12)
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
    assert eng.cache.slots_in_use == 0


def test_dense_attention_in_the_selection_s_place_fails(toy):
    """With every block kept where the selection would choose, the same
    run misses the reference by far more than the tolerance: the
    comparison sees the selection."""
    cfg, model, variables = toy
    prompt = _tokens(cfg, np.random.default_rng(7), 90)

    def dense(score, t, sel):
        b = jnp.arange(score.shape[-1], dtype=jnp.int32)
        return jnp.broadcast_to(b <= t[..., None] // sel["block"],
                                score.shape)

    with mock.patch.object(sparse_linear_lm, "kept_blocks", dense):
        eng = _engine(model, variables, enable_prefix_cache=False)
        reqs, rows = _serve(eng, prompt, 6)
    want = _reference_rows(cfg, prompt[0], ServeEngine._generated_of(reqs[0]))
    assert np.abs(rows[0] - want).max() > 50 * TOL
    # and with the selection in place the same prompt passes
    _served_against_reference(
        cfg, _engine(model, variables, enable_prefix_cache=False), prompt, 6)


def test_the_model_declares_its_layout(toy):
    cfg, model, variables = toy
    kinds = [layer["kind"] for layer in model.cache_layout]
    assert kinds == ["state", "paged", "state", "state"]
    sparse = model.cache_layout[1]
    assert sparse["pools"] == 2 and sparse["index"] == {"stride": 2,
                                                        "lanes": 16}
    eng = _engine(model, variables)
    assert eng.cache.kinds == ["state", "paged", "paged", "index", "state",
                               "state", "rows"]
    index = eng.cache.pools[3]
    assert index.shape == (96, 8 // 2, 16)     # a row a stride, a block's 4
    state = eng.cache.pools[0]
    assert state.shape == (3 + 1, 4, 8, 8) and state.dtype == jnp.float32


def test_the_row_counts_are_the_selection_s(toy):
    """`sparse_counts` (the engine's span fields) against the selection
    itself: the blocks `kept_blocks` keeps for any scores, and their
    keys."""
    cfg, model, variables = toy
    sel = model.sparse
    for start, length in ((0, 16), (30, 5), (70, 1), (64, 13), (120, 1)):
        kept = keys = 0
        for t in range(start, start + length):
            if t < sel["dense_len"]:
                keys += t + 1
                continue
            nb = t // sel["block"] + 1
            score = jnp.asarray(np.random.default_rng(t).random(nb))
            keep = np.asarray(sparse_linear_lm.kept_blocks(
                score, jnp.asarray(t, jnp.int32), sel))
            assert keep[0] and keep[nb - 1]
            kept += int(keep.sum())
            keys += int(keep.sum() - 1) * sel["block"] \
                + t % sel["block"] + 1
        got = model.sparse_counts(start, length)
        assert (got["blocks_selected"], got["sparse_keys"]) == (kept, keys)
        end = start + length
        assert got["sparse_rows_read"] == (keys if length == 1 else end)
        windows = (end - sel["kernel"]) // sel["stride"] + 1
        assert got["index_rows_read"] == (windows
                                          if end > sel["dense_len"] else 0)


# -- prefix reuse over recurrent state: snapshots ---------------------------

def test_a_hit_restored_from_a_snapshot_equals_the_uncached_run(toy):
    """A prompt of 75 leaves snapshots at 32 and 64; a second prompt
    that shares its first 70 tokens hits 64 deep (not 70: the deepest
    boundary), its slot restored from the snapshot; its logits are those
    of an engine without the prefix cache bit for bit, and the
    reference's within the tolerance."""
    cfg, model, variables = toy
    first = _tokens(cfg, np.random.default_rng(11), 75)[0]
    second = first[:70] + _tokens(cfg, np.random.default_rng(12), 9)[0]
    eng = _engine(model, variables)
    assert eng.cache.enable_prefix_cache and eng.cache.snapshot_every == 32
    _serve(eng, [first], 4)
    assert eng.cache.snapshots_held == 2
    reqs, hit = _serve(eng, [second], 10)
    assert reqs[0].cached_tokens == 64
    stats = eng.cache.stats()
    assert stats["snapshots_restored"] == 1
    assert stats["snapshot_tokens_skipped"] == 64
    plain = _engine(model, variables, enable_prefix_cache=False)
    reqs2, cold = _serve(plain, [second], 10)
    assert reqs2[0].cached_tokens == 0
    np.testing.assert_array_equal(hit[0], cold[0])
    out = ServeEngine._generated_of(reqs[0])
    np.testing.assert_allclose(hit[0], _reference_rows(cfg, second, out),
                               atol=TOL, rtol=0)
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()


def test_a_hit_falls_back_to_the_deepest_snapshot_left(toy):
    """With the snapshot at 64 gone the same prompt hits 32 deep; with
    none left it hits nothing: over slots a hit goes by snapshots alone."""
    cfg, model, variables = toy
    first = _tokens(cfg, np.random.default_rng(13), 75)[0]
    second = first[:70] + [1, 2, 3]
    eng = _engine(model, variables)
    _serve(eng, [first], 2)
    deep = tuple(first[:64])
    assert deep in eng.cache._snap_index
    eng.cache._drop_snapshot(deep)
    reqs, rows = _serve(eng, [second], 4)
    assert reqs[0].cached_tokens == 32
    plain = _engine(model, variables, enable_prefix_cache=False)
    np.testing.assert_array_equal(rows[0], _serve(plain, [second], 4)[1][0])
    for key in list(eng.cache._snap_index):
        eng.cache._drop_snapshot(key)
    reqs, _ = _serve(eng, [first[:70] + [4, 5]], 2)
    assert reqs[0].cached_tokens == 0
    eng.cache.assert_quiesced()


def test_snapshots_are_evicted_least_recently_used_first(toy):
    """Two places: a third snapshot takes the least recently used one's;
    a hit touches its snapshot; a block handed out for fresh content
    takes the snapshots that lean on it along."""
    cfg, model, variables = toy
    reg = MetricsRegistry()
    eng = _engine(model, variables, snapshot_slots=2, registry=reg)
    a, b = _tokens(cfg, np.random.default_rng(14), 40, 40)
    _serve(eng, [a], 2)                   # snapshot of a[:32]
    _serve(eng, [b], 2)                   # of b[:32]
    _serve(eng, [a[:35]], 2)              # hit: a's is now the newer
    assert eng.cache.stats()["snapshots_restored"] == 1
    c = _tokens(cfg, np.random.default_rng(15), 70)[0]
    _serve(eng, [c], 2)                   # c[:32] evicts b's, c[:64] a's
    held = set(eng.cache._snap_index)
    assert held == {tuple(c[:32]), tuple(c[:64])}
    assert eng.cache.stats()["snapshots_evicted"] == 2
    text = reg.render_prometheus()
    assert 'ptpu_state_snapshots_total{event="evicted"} 2' in text
    assert 'ptpu_state_snapshots_total{event="taken"} 4' in text
    assert "ptpu_state_snapshots_held 2" in text
    # three places: a snapshot that a deeper one of the same prompt stands
    # behind goes before an older one that stands alone
    three = _engine(model, variables, snapshot_slots=3)
    _serve(three, [b], 2)                 # b[:32], the oldest
    _serve(three, [c], 2)                 # c[:32], c[:64]
    _serve(three, [a], 2)                 # a[:32] takes c[:32]'s place
    assert set(three.cache._snap_index) == {tuple(b[:32]), tuple(c[:64]),
                                            tuple(a[:32])}
    three.cache.assert_quiesced()
    # a pool too small to keep c's blocks cached: fresh prompts recycle
    # them, and the snapshots over them go
    small = _engine(model, variables, num_blocks=14)
    _serve(small, [c], 2)
    assert small.cache.snapshots_held == 2
    _serve(small, _tokens(cfg, np.random.default_rng(16), 60), 2)
    assert not held & set(small.cache._snap_index)
    small.cache.assert_quiesced()
    eng.cache.assert_quiesced()


def test_a_request_waits_for_its_prefix_s_first_owner(toy):
    """Two prompts that share 70 tokens arrive together: the second is
    not admitted beside the first to compute the prefix again; it waits
    in the queue while the first prefills and is admitted onto the
    snapshot at 64. A third prompt behind it that shares nothing passes
    it (the second waits of its own accord and loses nothing) and is
    served as alone."""
    cfg, model, variables = toy
    first = _tokens(cfg, np.random.default_rng(18), 75)[0]
    second = first[:70] + [7, 8, 9]
    other = _tokens(cfg, np.random.default_rng(19), 20)[0]
    eng = _engine(model, variables)
    reqs = [eng.add_request(p, max_new_tokens=3)
            for p in (first, second, other)]
    eng.step()
    assert [r.state for r in reqs] == ["running", "waiting", "running"]
    eng.run()
    assert [r.cached_tokens for r in reqs] == [0, 64, 0]
    assert eng.cache.stats()["snapshots_restored"] == 1
    plain = _engine(model, variables, enable_prefix_cache=False)
    alone = plain.generate([first, second, other], max_new_tokens=3)
    assert [ServeEngine._generated_of(r) for r in reqs] == alone
    eng.cache.assert_quiesced()


def test_chunks_end_on_snapshot_boundaries(toy):
    """A budget of 24 against boundaries every 32: the chunks of a
    prompt of 75 end at 24, 32, 56, 64, 75."""
    cfg, model, variables = toy
    eng = _engine(model, variables, max_prefill_tokens=24)
    req = eng.add_request(_tokens(cfg, np.random.default_rng(17), 75)[0],
                          max_new_tokens=1)
    ends = []
    plan = eng.scheduler.next_batch

    def noted():
        # (a call of `step()` plans the step behind the one it collects)
        rows = plan()
        ends.extend(w.start + w.length for w in rows or () if not w.decode)
        return rows

    eng.scheduler.next_batch = noted
    eng.run()
    assert ends == [24, 32, 56, 64, 75] and req.finish_reason == "length"


# -- the kernels -------------------------------------------------------------

def _packing(seed, heads=4, d=128, tq=8):
    """A step of four rows and the null row: a chunk of 19 tokens that
    opens its sequence, a decode row, a chunk of 8 from position 16 and
    a chunk of 3 (a partial tile), then pad tiles."""
    r = np.random.default_rng(seed)
    row_slots = jnp.asarray([2, 4, 1, 3, 0], jnp.int32)
    q_starts = jnp.asarray([0, 7, 16, 30, 0], jnp.int32)
    ctx = jnp.asarray([19, 8, 24, 33, 1], jnp.int32)
    tile_rows = jnp.asarray([0, 0, 0, 1, 2, 3, 4, 4], jnp.int32)
    tile_offs = jnp.asarray([0, 8, 16, 0, 0, 0, 0, 0], jnp.int32)
    meta = scan.tile_meta(row_slots, ctx, q_starts, tile_rows, tile_offs, tq)
    t = tile_rows.shape[0] * tq
    q, k, v = (jnp.asarray(r.normal(size=(t, heads, d)) * s, jnp.float32)
               for s in (0.3, 0.3, 1.0))
    decay = -jnp.asarray(np.geomspace(0.6, 0.003, heads), jnp.float32)
    state = jnp.asarray(r.normal(size=(6, heads, d, d)), jnp.float32)
    return (q, k, v, decay, state), meta, tile_offs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lightning_kernel_agrees_with_its_reference(seed):
    """The interpreted kernel (a decode tile's rank-1 path, a chunk's
    block form, a row's state handed from tile to tile) against the
    recurrence a position at a time. 1e-4: the block form sums a tile's
    eight products in another order, on states of unit scale."""
    (q, k, v, decay, state), (slots, real, fresh, _), offs = _packing(seed)
    want_o, want_s = lightning.ragged_lightning_attention(
        q, k, v, decay, state, slots, real, fresh, offs, use_kernel=False)
    got_o, got_s = lightning.ragged_lightning_attention(
        q, k, v, decay, state, slots, real, fresh, offs, use_kernel=True,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("tier", [False, True], ids=["reference", "kernel"])
def test_lightning_pads_leave_the_state_as_it_was(tier):
    """Slots no real token walks (the null slot 0, slot 5) come back
    bit for bit, pad positions read 0, and a row that opens its sequence
    starts from zeros whatever its slot held."""
    (q, k, v, decay, state), (slots, real, fresh, _), offs = _packing(3)
    o, new = lightning.ragged_lightning_attention(
        q, k, v, decay, state, slots, real, fresh, offs, use_kernel=tier,
        interpret=True)
    for idle in (0, 5):
        np.testing.assert_array_equal(np.asarray(new[idle]),
                                      np.asarray(state[idle]))
    live = np.repeat(np.arange(8)[None], 8, 0) < np.asarray(real)[:, None]
    assert float(jnp.abs(o.reshape(8, 8, -1)[~live]).max()) == 0.0
    zeroed, _ = lightning.ragged_lightning_attention(
        q, k, v, decay, state.at[2].set(0.0), slots, real, fresh, offs,
        use_kernel=tier, interpret=True)
    np.testing.assert_array_equal(np.asarray(o[:24]), np.asarray(zeroed[:24]))


def test_lightning_state_stays_float32():
    (q, k, v, decay, state), (slots, real, fresh, _), offs = _packing(4)
    with pytest.raises(ValueError, match="float32"):
        lightning.ragged_lightning_attention(
            q, k, v, decay, state.astype(jnp.bfloat16), slots, real, fresh,
            offs)


def _masked_call(seed, **kw):
    r = np.random.default_rng(seed)
    h, d, tq, bs, nb, mb = 4, 128, 8, 16, 40, 12
    pool = jnp.asarray(r.normal(size=(nb, bs, paged.head_lanes(d))),
                       jnp.float32)
    bt = np.zeros((3, mb), np.int32)
    bt[0, :8] = r.permutation(np.arange(1, nb))[:8]
    bt[1, :10] = r.permutation(np.arange(1, nb))[:10]
    ctx = jnp.asarray([113, 150, 1], jnp.int32)
    qs = jnp.asarray([100, 149, 0], jnp.int32)
    tile_rows = jnp.asarray([0, 0, 1, 2], jnp.int32)
    tile_offs = jnp.asarray([0, 8, 0, 0], jnp.int32)
    q = jnp.asarray(r.normal(size=(4 * tq, h, d)), jnp.float32)
    mask = r.random((4 * tq, mb)) < 0.5
    mask[:, 0] = True
    qpos = np.concatenate([100 + np.arange(16), 149 + np.arange(8),
                           np.arange(8)])
    mask[np.arange(4 * tq), np.minimum(qpos // bs, mb - 1)] = True
    return paged.ragged_paged_attention(
        q, pool, jnp.asarray(bt), ctx, qs, tile_rows, tile_offs, groups=h,
        block_mask=jnp.asarray(mask) if kw.pop("masked", True) else None,
        **kw)


@pytest.mark.parametrize("seed", [0, 1])
def test_block_mask_kernel_agrees_with_its_reference(seed):
    """The ragged kernel under a block mask (a chunk of 13 queries that
    each keep other blocks, a decode row, a pad tile), interpreted,
    against the XLA reference; and the mask is seen: the unmasked call
    differs."""
    real = np.r_[0:13, 16]
    want = np.asarray(_masked_call(seed, use_kernel=False))[real]
    got = np.asarray(_masked_call(seed, use_kernel=True, interpret=True))
    np.testing.assert_allclose(got[real], want, atol=1e-5, rtol=0)
    dense = np.asarray(_masked_call(seed, use_kernel=False, masked=False))
    assert np.abs(dense[real] - want).max() > 0.1


def test_a_block_mask_wants_one_kv_head():
    r = np.random.default_rng(0)
    pool = jnp.zeros((8, 16, 2 * paged.head_lanes(128)), jnp.float32)
    q = jnp.asarray(r.normal(size=(8, 4, 128)), jnp.float32)
    args = (jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="one kv head"):
        paged.ragged_paged_attention(
            q, pool, *args, groups=2, use_kernel=True, interpret=True,
            block_mask=jnp.ones((8, 4), bool))


def test_a_compacted_table_is_the_mask_for_a_decode_row():
    """Without positions a decode row's kept blocks in order, the
    context shortened to match, read what the mask over the whole table
    reads."""
    r = np.random.default_rng(5)
    h, d, bs, nb, mb = 4, 128, 16, 40, 12
    pool = jnp.asarray(r.normal(size=(nb, bs, paged.head_lanes(d))),
                       jnp.float32)
    table = np.zeros((2, mb), np.int32)
    table[0, :10] = r.permutation(np.arange(1, nb))[:10]
    keep = np.zeros(mb, bool)
    keep[[0, 3, 4, 8, 9]] = True                  # the context ends in 9
    q = jnp.asarray(r.normal(size=(8, h, d)), jnp.float32)
    tiles = (jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
    masked = paged.ragged_paged_attention(
        q, pool, jnp.asarray(table), jnp.asarray([150, 1], jnp.int32),
        jnp.asarray([149, 0], jnp.int32), *tiles, groups=h,
        use_kernel=False, block_mask=jnp.asarray(np.tile(keep, (8, 1))))
    short = np.zeros((2, mb), np.int32)
    short[0, :5] = table[0, keep]
    held = 4 * bs + 150 - 9 * bs
    compact = paged.ragged_paged_attention(
        q, pool, jnp.asarray(short), jnp.asarray([held, 1], jnp.int32),
        jnp.asarray([held - 1, 0], jnp.int32), *tiles, groups=h,
        use_kernel=False)
    np.testing.assert_allclose(np.asarray(compact[0]), np.asarray(masked[0]),
                               atol=1e-6, rtol=0)
