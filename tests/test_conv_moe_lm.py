"""The decoder of gated short convolutions, attention with QK-norm and
routed experts (`models/conv_moe_lm.py`) against the benchmark's plain
reference (`benchmarks/reference_lfm2.py`), at the toy sizes of
`benchmarks/configs/lfm2-8b-a1b.json` on seeded weights: the published
form, then chunked prefill and decode through the engine's paged pools
and conv state slots, slots handed on as rows finish and others are
admitted, the expert layer without a shared expert, and the export.

Tolerances. Everything here is float32 on one backend, and the two
sides differ in formulation, not in precision: the reference runs every
expert over every token under a mask and attends over the full score
matrix, the engine sorts the step's (token, expert) pairs into grouped
products, reads the paged pool and carries the convolution's last two
inputs in a slot. At the toy width the logits' scale is 0.1 (a tied
head over a table of std 0.02), so 2e-5 is a few float32 roundings
through four layers; bf16 compute misses by a hundred times that, and
each ablation below by more.
"""

import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_lfm2 as reference
from benchmarks import weights_lfm2 as weights
from benchmarks.common import build_model
from paddle_tpu.engine import engine as engine_mod
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.models import conv_moe_lm
from paddle_tpu.models.shared_layers import RoutedExperts
from paddle_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_039
TOL = 2e-5


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
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
    positions, the logits at their scale."""
    cfg, model, variables = toy
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 128)), jnp.int32)
    got = np.asarray(model.apply(variables, tokens))
    rows = jnp.broadcast_to(jnp.arange(128), (2, 128))
    want = np.asarray(reference.logits_at(cfg, SEED, tokens, rows))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert 0.05 < want.std() < 0.5
    # bf16 compute is not within the tolerance
    half = build_model({**cfg, "compute_dtype": "bfloat16"})
    assert np.abs(np.asarray(half.apply(variables, tokens)) - want).max() \
        > 20 * TOL


def test_the_configuration_is_the_published_one_cut_in_depth():
    cfg = _config()
    assert cfg["parameters"] == weights.count_params(cfg) == 4_606_249_728
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (24, 2)
    # published layers 1..13: the second dense layer and three periods
    assert cfg["layer_types"] == pub["layer_types"][1:14]
    assert cfg["layer_types"] == ["conv"] + (["full_attention"]
                                             + ["conv"] * 3) * 3
    # the parts the cut is made of (bf16): a dense layer, a conv and an
    # attention expert layer, the table
    sizes = {kind: sum(int(np.prod(shape)) for shape, _ in
                       weights.layer_shapes(cfg, i).values())
             for kind, i in (("dense", 0), ("attn", 1), ("conv", 2))}
    assert sizes == {"dense": 60_827_648, "attn": 362_877_088,
                     "conv": 369_174_560}
    published = {
        "hidden_size": 2048, "intermediate_size": 7168,
        "moe_intermediate_size": 1792, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts": 32,
        "num_experts_per_tok": 4, "vocab_size": 65536, "conv_L_cache": 3,
        "conv_bias": False, "rope_theta": 1000000, "norm_eps": 1e-05,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "norm_topk_prob": True}
    assert {k: cfg[k] for k in published} == published
    toy = _toy()
    tree = jax.eval_shape(build_model(toy).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    assert sum(x.size for x in jax.tree.leaves(tree)) \
        == weights.count_params(toy)


# -- the expert layer without a shared expert ---------------------------------

def test_routed_experts_without_a_shared_expert_are_the_chosen_sum():
    """`num_shared=0` builds no shared FFN, and the layer is the sum of
    the chosen experts' gated FFNs weighted by their scores over the
    chosen scores' sum + eps, written out by hand; padding rows come out
    zero and are counted nowhere."""
    d, f, e, k = 16, 8, 6, 2
    layer = RoutedExperts(d, f, e, k, num_shared=0, eps=1e-6)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(7, d)),
                    jnp.float32)
    variables = layer.init(jax.random.PRNGKey(3), x)
    p = variables["params"]
    assert "shared" not in p and not hasattr(layer, "shared")
    real = jnp.asarray([True] * 5 + [False] * 2)
    y, counts, chosen = layer.apply(variables, x, real)
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(p["router"]["weight"])))
    want = np.zeros((7, d), np.float32)
    for t in range(5):
        pick = np.argsort(-(s[t] + np.asarray(p["router"]["bias"])))[:k]
        assert sorted(pick) == sorted(np.asarray(chosen[t]).tolist())
        for j in pick:
            ex = p["experts"]
            g = np.asarray(x[t]) @ np.asarray(ex["gate"][j])
            h = g / (1 + np.exp(-g)) * (np.asarray(x[t])
                                        @ np.asarray(ex["up"][j]))
            want[t] += s[t, j] / (s[t, pick].sum() + 1e-6) \
                * (h @ np.asarray(ex["down"][j]))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=0)
    assert int(counts.sum()) == 5 * k


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
        reqs = [eng.add_request(p, max_new_tokens=n)
                for p, n in zip(prompts, new_tokens)]
        eng.run()
    rows = [np.stack([spy.rows[(r.req_id, len(p) + j)] for j in range(n)])
            for r, p, n in zip(reqs, prompts, new_tokens)]
    return reqs, rows


def _served_against_reference(cfg, eng, prompts, new_tokens):
    reqs, rows = _serve(eng, prompts, new_tokens)
    for req, prompt, got in zip(reqs, prompts, rows):
        out = ServeEngine._generated_of(req)
        assert len(out) == len(got)
        want = _reference_rows(cfg, prompt, out)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert out == want.argmax(-1).tolist()
    return reqs


@pytest.mark.parametrize("tier,budget", [("reference", 16),
                                         ("interpret", 16),
                                         ("reference", 12)])
def test_chunked_prefill_and_decode_agree_with_the_reference(
        toy, tier, budget, monkeypatch):
    """Prompts of 23, 37 and 5, three at a time in three slots: prefill
    in chunks of the budget (16: whole tiles, a prompt's last chunk
    ending inside one; 12: a chunk that ends inside a tile and a next
    one that opens a tile of its own), so the conv state is handed on
    from chunk to chunk through its slot, then decode, against the
    reference's full forward pass, by logits; the ragged kernel
    interpreted, then its XLA reference."""
    monkeypatch.setenv("PTPU_PAGED_KERNEL", tier)
    cfg, model, variables = toy
    eng = _engine(model, variables, max_prefill_tokens=budget)
    assert not eng.cache.enable_prefix_cache     # no snapshots asked for
    prompts = _tokens(cfg, np.random.default_rng(3), 23, 37, 5)
    _served_against_reference(cfg, eng, prompts, [12, 12, 12])
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
    assert eng.cache.slots_in_use == 0
    # every computed token (a prompt's, and each generated one but the
    # last) went to top_k experts in each expert layer
    assert eng.expert_tokens.sum() == (23 + 37 + 5 + 3 * 11) \
        * model.expert_layers * cfg["num_experts_per_tok"]


def test_rows_admitted_mid_run_start_from_zeroed_slots(toy):
    """Two slots, five requests of different lengths: rows finish and
    others are admitted into the slots they leave, mid-run, beside
    rows still decoding; every one is served as if alone."""
    cfg, model, variables = toy
    eng = _engine(model, variables, max_batch_size=2)
    prompts = _tokens(cfg, np.random.default_rng(4), 30, 11, 17, 9, 26)
    reqs = _served_against_reference(cfg, eng, prompts, [3, 9, 5, 7, 4])
    assert [r.preemptions for r in reqs] == [0] * 5
    state = eng.cache.pools[eng.cache.kinds.index("state")]
    assert float(jnp.abs(state[1:]).max()) > 0     # the slots were used
    assert float(jnp.abs(state[0]).max()) == 0     # the null slot never
    eng.cache.assert_quiesced()


def test_the_model_declares_a_pool_or_a_slot_a_layer(toy):
    cfg, model, variables = toy
    assert [layer["kind"] for layer in model.cache_layout] == [
        "state", "paged", "state", "state"]
    eng = _engine(model, variables)
    assert eng.cache.kinds == ["state", "paged", "state", "state", "rows"]
    tails = eng.cache.pools[0]
    assert tails.shape == (3 + 1, 2 * cfg["hidden_size"])
    assert model.expert_layers == 3 and model.num_experts == 8


def _no_qk_norm(model):
    for blk in model.blocks:
        if blk.kind != "conv":
            object.__setattr__(blk.attn, "qk_norm", False)


def _no_bias(variables):
    params = jax.tree.map(lambda x: x, variables["params"])
    for name, blk in params.items():
        if name.startswith("blocks_") and "moe" in blk:
            blk["moe"]["router"]["bias"] = jnp.zeros_like(
                blk["moe"]["router"]["bias"])
    return {"params": params}


def _no_state(f):
    def conv(x, tails, weight, bias, slots, real, fresh, last, offs):
        return f(x, tails, weight, bias, slots, real, jnp.ones_like(fresh),
                 last, offs)
    return conv


_ABLATIONS = {
    # the query and key norms left out; the expert bias left out of the
    # selection; the conv state not handed on (every tile reads zeros)
    "qk_norm": dict(model=_no_qk_norm),
    "expert_bias": dict(variables=_no_bias),
    "conv_state": dict(conv=_no_state),
}


@pytest.mark.parametrize("ablation", sorted(_ABLATIONS))
def test_each_mechanism_moves_the_logits_past_the_tolerance(toy, ablation):
    """The program served without one of its mechanisms misses the
    reference by far more than the tolerance: the comparison sees
    every one."""
    cfg, _, variables = toy
    how = _ABLATIONS[ablation]
    model = build_model(cfg)
    how.get("model", lambda m: None)(model)
    variables = how.get("variables", lambda v: v)(variables)
    prompt = _tokens(cfg, np.random.default_rng(7), 21)
    conv = conv_moe_lm.scan.ragged_causal_conv
    with mock.patch.object(conv_moe_lm.scan, "ragged_causal_conv",
                           how.get("conv", lambda f: f)(conv)):
        reqs, rows = _serve(_engine(model, variables), prompt, [6])
    want = _reference_rows(cfg, prompt[0], ServeEngine._generated_of(reqs[0]))
    assert np.abs(rows[0] - want).max() > 50 * TOL
