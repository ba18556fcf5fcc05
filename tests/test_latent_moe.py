"""The latent-attention, routed-expert decoder (models/latent_moe.py)
against its plain reference (benchmarks/reference_glm.py) at toy widths,
float32, seeded weights, on the CPU: the plain forward, the engine's
latent pool (chunked prefill, decode, a prefix hit, a copy-on-write),
the absorbed attention against the published form, the latent kernel in
interpret mode, and the expert layer under total imbalance.

Tolerances: program and reference run the same float32 operations at
"highest" in another order (absorbed products, sorted experts, a
chunked softmax), so logits of magnitude 4 agree to a few 1e-6; the
limit is 5e-5.
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import reference_glm, weights_glm  # noqa: E402
from benchmarks.common import build_model  # noqa: E402
from paddle_tpu.engine import engine as engine_mod  # noqa: E402
from paddle_tpu.engine.engine import ServeEngine  # noqa: E402

SEED = 5
TOL = 5e-5


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        full = json.load(f)
    return {**full, **full["toy"]}


@pytest.fixture(scope="module")
def model(cfg):
    return build_model(cfg)


@pytest.fixture(scope="module")
def params(cfg):
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights_glm.make_params(cfg, SEED))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _engine(model, params, **kw):
    kw = {**dict(max_batch_size=4, block_size=4, num_blocks=64,
                 max_prefill_tokens=16, tile_q=8, max_seq_len=128), **kw}
    return ServeEngine(model, {"params": params}, **kw)


def _reference(cfg, prompt, generated, weights=weights_glm, width=64):
    """The reference's logits at the positions that produced
    `generated`, teacher-forced."""
    seq = list(prompt) + list(generated)
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :len(seq)] = seq
    rows = (len(prompt) - 1 + np.arange(len(generated)))[None]
    logits, chosen = reference_glm.logits_at(
        cfg, SEED, jnp.asarray(tokens), jnp.asarray(rows), weights=weights)
    return np.asarray(logits[0]), np.asarray(chosen)[:, 0, :len(seq)]


@pytest.fixture
def sampled(monkeypatch):
    """Every logits row the engine samples from, in order."""
    rows = []
    plain = engine_mod._sample

    def spy(logits, req, pos):
        rows.append(np.array(logits))
        return plain(logits, req, pos)
    monkeypatch.setattr(engine_mod, "_sample", spy)
    # a greedy row's logits stay on the device: ask for them
    monkeypatch.setattr(engine_mod, "_needs_logits", lambda req: True)
    return rows


def test_the_tree_is_the_one_the_model_expects(cfg, model, params):
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    assert (jax.tree.map(lambda x: x.shape, want)
            == jax.tree.map(lambda x: x.shape, params))


def test_forward_is_the_reference_and_routes_as_it_does(cfg, model, params):
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    got, routing = model.apply({"params": params}, jnp.asarray(tokens),
                               return_routing=True)
    rows = np.tile(np.arange(24)[None], (2, 1))
    ref, chosen = reference_glm.logits_at(cfg, SEED, jnp.asarray(tokens),
                                          jnp.asarray(rows))
    assert float(jnp.abs(got - ref).max()) < TOL
    dense = cfg["first_k_dense_replace"]
    differ = np.any(np.sort(np.asarray(routing), -1)
                    != np.sort(np.asarray(chosen)[dense:], -1), axis=-1)
    print(f"(token, layer) pairs whose chosen experts differ from the "
          f"reference's: {differ.sum()} of {differ.size}")
    assert differ.sum() == 0


def test_engine_latent_pool_is_the_reference(cfg, model, params, sampled):
    """Prompts prefilled in chunks of 16 then decoded through the latent
    pool, the second after a prefix-cache hit on the shared document."""
    eng = _engine(model, params)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, cfg["vocab_size"], 24).tolist()
    cases = []
    for extra in (7, 5):
        prompt = doc + rng.integers(0, cfg["vocab_size"], extra).tolist()
        cases.append((prompt, eng.generate([prompt], max_new_tokens=6)[0]))
    assert eng.stats()["hit_tokens"] == 24        # the document, once
    assert eng.stats()["max_chunk_tokens"] == 16
    at = 0
    for prompt, generated in cases:
        ref, _ = _reference(cfg, prompt, generated)
        got = np.stack(sampled[at:at + len(generated)])
        at += len(generated)
        assert np.abs(ref - got).max() < TOL
        assert ref.argmax(-1).tolist() == generated
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()


def test_copy_on_write_of_a_latent_block(cfg, model, params, sampled):
    """A whole-prompt hit while the first holder still decodes: the
    last token recomputes into the shared last block, which is copied."""
    eng = _engine(model, params)
    prompt = np.random.default_rng(2).integers(
        0, cfg["vocab_size"], 16).tolist()        # four whole blocks
    first = eng.add_request(prompt, max_new_tokens=8)
    while not first.generated:
        eng.step()
    second = eng.add_request(prompt, max_new_tokens=5)
    eng.run()
    assert eng.stats()["cow_copies"] >= 1
    assert second.cached_tokens == 15
    assert second.generated == first.generated[:5]
    ref, _ = _reference(cfg, prompt, first.generated)
    assert ref.argmax(-1).tolist() == first.generated
    eng.cache.assert_quiesced()


def test_absorbed_is_the_published_form(cfg, model, params, sampled):
    """The engine's absorbed attention over cached latents against the
    model's own un-absorbed forward."""
    eng = _engine(model, params)
    prompt = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], 21).tolist()
    generated = eng.generate([prompt], max_new_tokens=5)[0]
    seq = prompt + generated
    plain = model.apply({"params": params}, jnp.asarray([seq], jnp.int32))
    want = np.asarray(plain[0, len(prompt) - 1:len(seq) - 1])
    assert np.abs(want - np.stack(sampled)).max() < TOL


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_latent_kernel_interpreted_is_the_gather_reference(dtype, tol):
    from paddle_tpu.kernels.paged_attention import (latent_lanes,
                                                    pack_latent,
                                                    ragged_paged_attention)
    rng = np.random.default_rng(0)
    heads, k_dim, v_dim, bs, nb, mb, tq = 4, 20, 16, 4, 32, 8, 8
    pool = pack_latent(rng.normal(size=(nb, bs, k_dim)).astype(np.float32),
                       latent_lanes(k_dim))
    assert pool.shape[-1] == 128
    # a decode row at context 13, a chunk of 11 from position 5 (two
    # tiles), a pad tile on the null row
    tables = np.zeros((3, mb), np.int32)
    tables[0, :4], tables[1, :4] = [3, 7, 9, 11], [2, 4, 6, 8]
    args = [jnp.asarray(rng.normal(size=(4 * tq, heads, k_dim)), dtype),
            jnp.asarray(pool, dtype), jnp.asarray(tables),
            jnp.asarray([13, 16, 1], jnp.int32),
            jnp.asarray([12, 5, 0], jnp.int32),
            jnp.asarray([0, 1, 1, 2], jnp.int32),
            jnp.asarray([0, 0, 8, 0], jnp.int32)]
    kw = dict(scale=0.3, groups=heads, value_lanes=(0, v_dim))
    ref = ragged_paged_attention(*args, use_kernel=False, **kw)
    got = ragged_paged_attention(*args, use_kernel=True, interpret=True,
                                 **kw)
    assert got.shape == (4 * tq, heads, v_dim)
    real = np.r_[0:1, 8:19]           # the rows that are tokens
    diff = np.abs(np.asarray(ref, np.float32) - np.asarray(got, np.float32))
    assert diff[real].max() < tol


def test_all_tokens_to_one_pair_of_experts_drops_nothing(cfg, model, params,
                                                         sampled):
    """A selection bias that sends every token to experts 3 and 5: the
    sorted layer has no capacity to overflow, and gives the reference's
    result."""
    def biased(tree):
        out = jax.tree.map(lambda x: x, tree)
        if "moe" in out:
            bias = np.zeros(cfg["n_routed_experts"], np.float32)
            bias[[3, 5]] = 10.0
            out["moe"]["router"]["bias"] = jnp.asarray(bias)
        return out
    weights = types.SimpleNamespace(
        embed=weights_glm.embed, head=weights_glm.head,
        norm_f=weights_glm.norm_f,
        layer=lambda c, s, i: biased(weights_glm.layer(c, s, i)))
    skewed = {k: biased(v) if k.startswith("blocks_") else v
              for k, v in params.items()}
    eng = _engine(model, skewed)
    prompt = np.random.default_rng(4).integers(
        0, cfg["vocab_size"], 27).tolist()
    generated = eng.generate([prompt], max_new_tokens=4)[0]
    ref, chosen = _reference(cfg, prompt, generated, weights)
    assert np.abs(ref - np.stack(sampled)).max() < TOL
    dense = cfg["first_k_dense_replace"]
    assert set(np.unique(chosen[dense:])) == {3, 5}
    tokens = len(prompt) + len(generated) - 1      # each computed once
    layers = cfg["num_hidden_layers"] - dense
    want = np.zeros((layers, cfg["n_routed_experts"]), np.int64)
    want[:, [3, 5]] = tokens
    assert (eng.expert_tokens == want).all()


def test_pad_rows_are_not_routed_and_not_counted(cfg, model, params):
    """A step is 48 positions wide whatever it carries; only the real
    ones reach an expert or a count."""
    eng = _engine(model, params)
    assert eng.flat_tokens == 48
    eng.generate([[7, 8, 9, 10, 11], [3, 4, 5]], max_new_tokens=3)
    computed = 5 + 3 + 2 * 2      # prompts, then two decode steps each
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert eng.expert_tokens.sum() == (computed * layers
                                       * cfg["num_experts_per_tok"])
    assert eng.obs.render_prometheus().count("ptpu_moe_assignments_total")


def test_a_latent_pool_refuses_tp_and_the_int8_tier(model, params):
    with pytest.raises(ValueError, match="no kv head to divide"):
        _engine(model, params, tp_size=2)
    with pytest.raises(ValueError, match="k scale and one v scale"):
        _engine(model, params, kv_compress_blocks=8)


def test_host_tier_round_trip_of_a_latent_block(cfg, model, params):
    """A block's rows leave as (value lanes, the rest) and come back
    as the same rows."""
    eng = _engine(model, params)
    eng.generate([list(range(1, 14))], max_new_tokens=2)
    block = eng.cache._index[tuple(range(1, 5))]
    for pool, (k, v) in zip(eng.cache.pools, eng.cache.read_block(block)):
        assert k.shape == (4, 1, cfg["kv_lora_rank"])
        assert v.shape == (4, 1, cfg["qk_rope_head_dim"])
        assert (np.asarray(eng.cache.pack_block(k, v))
                == np.asarray(pool[block])).all()
        assert np.abs(k).max() > 0 and np.abs(v).max() > 0
