"""The post-norm decoder of sliding-window and full GQA layers with
routed and shared experts (`models/window_moe_lm.py`) against the
benchmark's plain reference (`benchmarks/reference_kexaone.py`), at the
toy sizes of `benchmarks/configs/k-exaone-236b-a23b.json` on seeded
weights: the published form, a window layer's mask and a full layer's
missing rotary against ones built by hand, chunked prefill and decode
through the engine's window rings and paged pool, the expert layer as
one share of an expert-parallel layer, and each mechanism's effect.

Tolerances. Everything here is float32 on one backend, and the two
sides differ in formulation, not in precision: the reference runs every
held expert over every token under a mask and attends over the full
score matrix, the engine sorts the step's (token, expert) pairs into
grouped products and reads the rings and the pool. At the toy width
the logits have unit scale (an untied head of std 1 / sqrt(d) over a
normed stream), so 1e-4 is a few float32 roundings through four
post-norm layers; bf16 compute misses by a hundred times that, and each
ablation below by more.
"""

import json
import math
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_kexaone as reference
from benchmarks import weights_kexaone as weights
from benchmarks.common import build_model
from paddle_tpu.engine import engine as engine_mod
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.models.shared_layers import Attention, RoutedExperts
from paddle_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_043
TOL = 1e-4


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "k-exaone-236b-a23b.json")) as f:
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
    positions, 16 windows' worth, the logits at their scale."""
    cfg, model, variables = toy
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 128)), jnp.int32)
    got = np.asarray(model.apply(variables, tokens))
    rows = jnp.broadcast_to(jnp.arange(128), (2, 128))
    want = np.asarray(reference.logits_at(cfg, SEED, tokens, rows))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert 0.5 < want.std() < 2.0
    # bf16 compute is not within the tolerance
    half = build_model({**cfg, "compute_dtype": "bfloat16"})
    assert np.abs(np.asarray(half.apply(variables, tokens)) - want).max() \
        > 100 * TOL


def test_the_configuration_is_the_published_one_cut_to_one_chips_share():
    cfg = _config()
    assert cfg["parameters"] == weights.count_params(cfg) == 3_712_028_416
    assert cfg["reduced"] == ["num_experts", "vocab_size",
                              "num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_nextn_predict_layers"]
    pub = cfg["published"]
    assert (pub["num_experts"], pub["vocab_size"], pub["num_hidden_layers"],
            pub["num_nextn_predict_layers"]) == (128, 153_600, 48, 1)
    # published layers 0..4: the dense layer and one whole LLLG period
    assert cfg["layer_types"] == pub["layer_types"][:5] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"] == pub["mlp_layer_types"][:5] == [
        "dense"] + ["sparse"] * 4
    # eight chips share a layer: 16 experts and an eighth of the ids each
    assert (cfg["num_experts"] * cfg["expert_shards"],
            cfg["vocab_size"] * cfg["expert_shards"]) == (128, 153_600)
    assert cfg["expert_rank"] == 0
    # the parts the cut is made of (bf16): the dense layer, an expert
    # layer's share, the table, head and final norm
    sizes = [sum(math.prod(shape) for shape, _ in
                 weights.layer_shapes(cfg, i).values()) for i in range(5)]
    assert sizes == [452_997_376] + [755_773_824] * 4
    assert cfg["parameters"] - sum(sizes) == 235_935_744
    # the whole model, reckoned the same way: 47 expert layers of 128
    whole = {**cfg, "num_experts": 128, "expert_shards": 1}
    expert_layer = sum(math.prod(shape) for shape, _ in
                       weights.layer_shapes(whole, 1).values())
    assert expert_layer == 4_983_632_256
    assert (452_997_376 + 47 * expert_layer + 2 * 153_600 * 6144 + 6144
            == pub["parameters"] == 236_571_156_352)
    published = {
        "hidden_size": 6144, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "head_dim": 128,
        "num_attention_heads": 64, "num_key_value_heads": 8,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "sliding_window": 128, "rms_norm_eps": 1e-05,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "max_position_embeddings": 262144}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_theta"] == cfg["rope_parameters"]["rope_theta"] == 1e6
    toy = _toy()
    tree = jax.eval_shape(build_model(toy).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    assert sum(x.size for x in jax.tree.leaves(tree)) \
        == weights.count_params(toy)


# -- a window layer's mask, a full layer's missing rotary ---------------------

def _by_hand(x, p, heads, kv_heads, hd, mask, angles):
    """GQA with QK-norm in numpy: `mask` [T, T] the keys each query
    sees, `angles` [T, hd / 2] the rotary's angle a position and pair
    (zeros: no rotary)."""
    t = x.shape[0]
    qkv = x @ np.asarray(p["qkv"]["weight"])
    q = qkv[:, :heads * hd].reshape(t, heads, hd)
    k = qkv[:, heads * hd:(heads + kv_heads) * hd].reshape(t, kv_heads, hd)
    v = qkv[:, (heads + kv_heads) * hd:].reshape(t, kv_heads, hd)

    def norm(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * g

    def turn(a):
        c, s = np.cos(angles)[:, None], np.sin(angles)[:, None]
        a1, a2 = a[..., :hd // 2], a[..., hd // 2:]
        return np.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)
    q = turn(norm(q, np.asarray(p["q_norm"]["scale"])))
    k = turn(norm(k, np.asarray(p["k_norm"]["scale"])))
    g = heads // kv_heads
    out = np.zeros((t, heads, hd))
    for h in range(heads):
        s = q[:, h] @ k[:, h // g].T / math.sqrt(hd)
        s = np.where(mask, s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (a / a.sum(-1, keepdims=True)) @ v[:, h // g]
    return out.reshape(t, heads * hd) @ np.asarray(p["o"]["weight"])


@pytest.mark.parametrize("window", [None, 5])
def test_window_keys_stop_at_w_and_a_full_layer_turns_nothing(window):
    """A window layer's query at i sees keys i - W < j <= i, rotated at
    theta; a full layer's sees every j <= i and nothing is rotated:
    each against attention built by hand from that mask and those
    angles."""
    t, d, heads, kv_heads, hd, theta = 19, 24, 4, 2, 8, 100.0
    attn = Attention(d, heads, kv_heads, hd, theta, 1.0, jnp.float32,
                     jnp.float32, qk_norm_eps=1e-5,
                     rotary=window is not None, window=window)
    x = np.random.default_rng(5).normal(size=(1, t, d)).astype(np.float32)
    variables = attn.init(jax.random.PRNGKey(1), jnp.asarray(x))
    p = jax.tree.map(np.asarray, variables["params"])
    p["q_norm"]["scale"] = p["q_norm"]["scale"] + 0.1 * np.arange(hd)
    got = np.asarray(attn.apply({"params": p}, jnp.asarray(x)))[0]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    if window is None:
        mask, angles = j <= i, np.zeros((t, hd // 2))
    else:
        mask = (j <= i) & (j > i - window)
        angles = np.arange(t)[:, None] * theta ** (
            -np.arange(hd // 2) / (hd // 2))
    want = _by_hand(x[0].astype(np.float64), p, heads, kv_heads, hd, mask,
                    angles)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    # and the other choice of mask and angles is far from it
    other = _by_hand(x[0].astype(np.float64), p, heads, kv_heads, hd,
                     j <= i, np.arange(t)[:, None] * theta ** (
                         -np.arange(hd // 2) / (hd // 2)))
    assert np.abs(other - want).max() > 1e-2


# -- the expert layer as one share of an expert-parallel layer ----------------

def test_the_shares_of_four_ranks_sum_to_the_whole_layer():
    """The share test of expert parallelism: 16 experts over 4 ranks of
    4. The router is the whole layer's at every rank; each rank computes
    its own experts' part; the four parts, the shared expert counted
    once, are the unsharded layer's output, and the pairs each rank
    keeps and sends away add up to every real pair."""
    d, f, e, k, ranks = 16, 8, 16, 4, 4
    x = jnp.asarray(np.random.default_rng(2).normal(size=(9, d)),
                    jnp.float32)
    real = jnp.asarray([True] * 7 + [False] * 2)
    whole = RoutedExperts(d, f, e, k, num_shared=1, scaling=2.5)
    variables = whole.init(jax.random.PRNGKey(3), x)
    p = variables["params"]
    y, counts, chosen = whole.apply(variables, x, real)
    parts, away = [], 0
    for r in range(ranks):
        share = RoutedExperts(d, f, e // ranks, k, num_shared=1, scaling=2.5,
                              expert_shards=ranks, rank=r)
        held = slice(r * e // ranks, (r + 1) * e // ranks)
        ps = dict(p, experts={n: w[held] for n, w in p["experts"].items()})
        ys, cs, cr = share.apply({"params": ps}, x, real)
        np.testing.assert_array_equal(np.asarray(cr), np.asarray(chosen))
        assert cs.shape == (e // ranks + 1,)
        np.testing.assert_array_equal(np.asarray(cs[:-1]),
                                      np.asarray(counts[held]))
        assert int(cs[:-1].sum() + cs[-1]) == 7 * k
        parts.append(np.asarray(ys))
        away += int(cs[-1])
    shared = np.asarray(whole.shared.apply({"params": p["shared"]}, x))
    total = sum(parts) - (ranks - 1) * shared
    np.testing.assert_allclose(total[:7], np.asarray(y)[:7], atol=1e-5,
                               rtol=0)
    assert away == (ranks - 1) * 7 * k
    # a share's own part is not the whole: the others' experts count
    assert np.abs(parts[0][:7] - np.asarray(y)[:7]).max() > 1e-2


def test_one_shard_is_todays_layer():
    """`expert_shards` 1 (the default) is the layer that holds every
    expert: the router as wide as the experts, counts of [E] with no
    column for pairs sent away, and the output the sum of the chosen
    experts written out by hand, plus the shared expert."""
    d, f, e, k = 16, 8, 6, 2
    layer = RoutedExperts(d, f, e, k, num_shared=1, scaling=2.5)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(7, d)),
                    jnp.float32)
    variables = layer.init(jax.random.PRNGKey(5), x)
    p = jax.tree.map(np.asarray, variables["params"])
    assert p["router"]["weight"].shape == (d, e)
    real = jnp.asarray([True] * 5 + [False] * 2)
    y, counts, chosen = layer.apply(variables, x, real)
    assert counts.shape == (e,)
    xs = np.asarray(x)
    s = 1 / (1 + np.exp(-xs @ p["router"]["weight"]))
    shared = np.asarray(layer.shared.apply({"params": variables["params"][
        "shared"]}, x))
    want = shared.copy()
    hist = np.zeros(e, int)
    for t in range(5):
        pick = np.argsort(-(s[t] + p["router"]["bias"]))[:k]
        assert sorted(pick) == sorted(np.asarray(chosen[t]).tolist())
        hist[pick] += 1
        for j in pick:
            ex = p["experts"]
            g = xs[t] @ ex["gate"][j]
            h = g / (1 + np.exp(-g)) * (xs[t] @ ex["up"][j])
            want[t] += 2.5 * s[t, j] / s[t, pick].sum() * (h @ ex["down"][j])
    np.testing.assert_allclose(np.asarray(y)[:5], want[:5], atol=1e-5,
                               rtol=0)
    np.testing.assert_array_equal(np.asarray(counts), hist)


# -- through the engine's window rings and paged pool -------------------------

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
        reqs = [eng.add_request(p, max_new_tokens=n)
                for p, n in zip(prompts, new_tokens)]
        eng.run()
    rows = [np.stack([spy.rows[(r.req_id, len(p) + j)] for j in range(n)])
            for r, p, n in zip(reqs, prompts, new_tokens)]
    return reqs, rows


@pytest.mark.parametrize("tier,budget", [("reference", 16),
                                         ("interpret", 16),
                                         ("reference", 12)])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        toy, tier, budget, monkeypatch):
    """Prompts of 23, 37 and 5, three at a time: prefill in chunks of
    the budget, each chunk's window layers reading back 7 positions
    into the ring, then decode past several windows, against the
    reference's full forward pass, by logits; the ragged kernel
    interpreted, then its XLA reference. The held experts' tokens and
    the pairs sent away add up to every pair of every computed token."""
    monkeypatch.setenv("PTPU_PAGED_KERNEL", tier)
    cfg, model, variables = toy
    eng = _engine(model, variables, max_prefill_tokens=budget)
    prompts = _tokens(cfg, np.random.default_rng(3), 23, 37, 5)
    reqs, rows = _serve(eng, prompts, [12, 12, 12])
    for req, prompt, got in zip(reqs, prompts, rows):
        out = ServeEngine._generated_of(req)
        want = _reference_rows(cfg, prompt, out)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert out == want.argmax(-1).tolist()
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
    assert eng.cache.slots_in_use == 0
    pairs = (23 + 37 + 5 + 3 * 11) * model.expert_layers \
        * cfg["num_experts_per_tok"]
    held = eng.obs.get("ptpu_moe_pairs_total").labels(where="held").value
    away = eng.obs.get("ptpu_moe_pairs_total").labels(where="away").value
    assert held == eng.expert_tokens.sum() == eng.obs.get(
        "ptpu_moe_assignments_total").value
    assert held + away == pairs and 0 < held < away


def test_the_model_declares_rings_and_a_pool(toy):
    cfg, model, variables = toy
    assert model.cache_layout == [
        {"kind": "window", "window": 8}, {"kind": "window", "window": 8},
        {"kind": "paged"}, {"kind": "window", "window": 8}]
    eng = _engine(model, variables)
    assert eng.cache.kinds == ["window", "window", "paged", "window", "rows"]
    assert (model.expert_layers, model.num_experts,
            model.expert_shards) == (3, 4, 4)
    names = [b.attn.kernel_name for b in model.blocks]
    assert names == ["ragged_gqa_window"] * 2 + ["ragged_gqa_full",
                                                 "ragged_gqa_window"]
    # what can not work over a ring is refused at construction
    with pytest.raises(ValueError, match="window ring"):
        _engine(model, variables, spec_k=2)


def _turned(model, which):
    """The rotary given to the full layers, or taken from the window
    layers."""
    for blk in model.blocks:
        if (blk.attn.window is None) == (which == "full"):
            object.__setattr__(blk.attn, "rotary", which == "full")


def _no_window(model):
    for blk in model.blocks:
        object.__setattr__(blk.attn, "window", None)


def _no_bias(variables):
    params = jax.tree.map(lambda x: x, variables["params"])
    for name, blk in params.items():
        if name.startswith("blocks_") and "moe" in blk:
            blk["moe"]["router"]["bias"] = jnp.zeros_like(
                blk["moe"]["router"]["bias"])
    return {"params": params}


_ABLATIONS = {
    # rotary on the full layers too; no rotary on the window layers;
    # the window layers over the whole context (their rings would not
    # hold it: served through pools here); the selection bias left out
    "full_rotary": dict(model=lambda m: _turned(m, "full")),
    "window_no_rotary": dict(model=lambda m: _turned(m, "window")),
    "no_window": dict(model=_no_window),
    "selection_bias": dict(variables=_no_bias),
}


@pytest.mark.parametrize("ablation", sorted(_ABLATIONS))
def test_each_mechanism_moves_the_logits_past_the_tolerance(toy, ablation):
    """The published form without one of its mechanisms misses the
    reference by far more than the tolerance: the comparison sees
    every one."""
    cfg, _, variables = toy
    how = _ABLATIONS[ablation]
    model = build_model(cfg)
    how.get("model", lambda m: None)(model)
    variables = how.get("variables", lambda v: v)(variables)
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg["vocab_size"], (1, 40)), jnp.int32)
    got = np.asarray(model.apply(variables, tokens))
    want = np.asarray(reference.logits_at(
        cfg, SEED, tokens, jnp.arange(40)[None]))
    assert np.abs(got - want).max() > 50 * TOL
