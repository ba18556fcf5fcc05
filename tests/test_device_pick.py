"""The step picks its own greedy tokens (ENGINE.md "Sampling"): beside
the logits the compiled step returns each row's best id, that id's
logit and the row's log-sum-exp, and a greedy step downloads those
three numbers a row and nothing else. A step in which a row samples at
a temperature downloads the logits too, and that row goes through
`_sample` on the host.

The load-bearing assertion is identity with the host path: the same
traffic with every row's logits asked for (`_needs_logits` patched to
say yes, which is what every step did before) gives the same tokens
and the same `logprob_sum`, bit for bit. On the CPU toy engine: counts
and bytes are checked, never a time.
"""

import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.engine.engine as engine_mod
from paddle_tpu.engine.engine import (ServeEngine, _pick, _sample,
                                      compile_steps)
from paddle_tpu.engine.scheduler import Request
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.models.transformer import CausalLM
from paddle_tpu.obs.metrics import MetricsRegistry

# the package re-exports a function named `profiler` over the submodule
prof = importlib.import_module("paddle_tpu.profiler.profiler")

pytestmark = pytest.mark.serve

VOCAB = 61
REPEATY = [1, 2, 3, 4, 5, 1, 2, 3, 4, 5, 1, 2, 3]
PROMPTS = [[5, 9, 2], [7, 1, 1, 3, 8], [4], [11, 12, 13, 14, 15, 16, 17]]


@pytest.fixture(scope="module")
def model_and_vars():
    model = CausalLM(vocab=VOCAB, model_dim=16, num_heads=4, num_layers=2,
                     ffn_dim=32, dropout=0.0, max_len=64)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables, **kw):
    kw = {"max_batch_size": 4, "block_size": 4, "num_blocks": 64,
          "registry": MetricsRegistry(), **kw}
    return ServeEngine(model, variables, **kw)


def _host_path():
    """Every row's logits on the host and through `_sample`: what each
    step did before the step picked for itself."""
    return mock.patch.object(engine_mod, "_needs_logits", lambda req: True)


def _logits_bytes(eng) -> int:
    return (eng.max_batch_size * eng.spec_len * VOCAB
            * jnp.dtype(eng.model.dtype).itemsize)


def _serve(eng, requests):
    """Serve `requests` (keyword arguments of `add_request`) over an
    emptied ring; ([(tokens, logprob_sum) a candidate], fetch spans,
    sample spans)."""
    prof.reset_profiler()
    reqs = [eng.add_request(**kw) for kw in requests]
    eng.run()
    out = [(ServeEngine._generated_of(c), c.logprob_sum)
           for r in reqs for c in [r] + r.forks]
    events = prof.get_events()
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()
    return out, *([e["args"] for e in events if e["name"] == name]
                  for name in ("engine.fetch", "engine.sample"))


def _downloads(eng) -> int:
    return int(eng.obs.get("ptpu_engine_logit_downloads_total").value)


# -- the pick itself --------------------------------------------------------

class _Planted(ServedModel):
    """A model whose step returns the logits it was handed: its rows are
    the sampled rows' flat indices, and its head reads the planted
    logits at them."""
    cache_layout = [{"kind": "paged"}]

    def trunk(self, cx, batch, pools):
        return batch.packing.last.reshape(-1), pools, None

    def logits(self, cx, rows):
        planted = cx._core.variables["logits"]
        return planted.reshape(-1, planted.shape[-1])[rows]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_steps_pick_is_samples_on_every_row_ties_planted(dtype):
    """Seeded logits, the row's maximum planted again at later ids (and
    in bfloat16 tied by rounding besides): the device's id is
    np.argmax's, the first, and (id, top - lse) is `_pick`'s pair from
    the row itself, bit for bit."""
    rng = np.random.default_rng(32)
    b, s, v = 6, 3, 2_000
    logits = (rng.standard_normal((b, s, v)) * 3).astype(np.float32)
    for i in range(b):
        for j in range(s):
            row = logits[i, j]
            first = int(rng.integers(0, v - 1))
            later = rng.integers(first + 1, v, size=1 + (i + j) % 3)
            row[first] = row[later] = row.max() + (0.5 if i % 2 else 0.0)
    logits = jnp.asarray(logits, dtype)
    step, _ = compile_steps(_Planted(), None, False, None, ["paged"])
    zeros = np.zeros((4,), np.int32)
    (out, lse, ids, top), _ = step(
        {"logits": logits}, zeros, zeros, [jnp.zeros((2, 2))], [], [],
        *[zeros] * 6, np.arange(b * s, dtype=np.int32).reshape(b, s))
    out, lse, ids, top = jax.device_get((out, lse, ids, top))
    assert ids.dtype == np.int32 and ids.shape == (b, s)
    assert top.dtype == lse.dtype == np.float32
    req = Request(prompt=[1], max_new_tokens=1)
    for i in range(b):
        for j in range(s):
            row = out[i, j]
            assert (row == row.max()).sum() >= 2        # a tie, planted
            assert _sample(row, req, 0) == (int(ids[i, j]), None)
            assert (_pick(None, ids[i, j], top[i, j], lse[i, j], req, 0)
                    == _pick(row, ids[i, j], top[i, j], lse[i, j], req, 0))


# -- through the engine -----------------------------------------------------

@pytest.mark.parametrize("kw", [
    {}, {"max_prefill_tokens": 4}, {"spec_k": 4},
    {"max_prefill_tokens": 8, "spec_k": 3}], ids=str)
def test_a_greedy_run_is_the_host_paths_and_moves_no_logits(
        model_and_vars, kw):
    """Greedy traffic (whole prompts, chunked prefill, speculative
    rows): tokens and `logprob_sum` are the host path's, no step
    downloads its logits, `engine.fetch` moves 12 bytes a (row, window
    position) and no row goes through `_sample`; on the host path every
    step moves its logits as well and every row that samples is a host
    row."""
    requests = [{"prompt": list(p), "max_new_tokens": 12}
                for p in PROMPTS + [REPEATY]]
    eng = _engine(*model_and_vars, **kw)
    with mock.patch.object(engine_mod, "_sample",
                           side_effect=AssertionError("a greedy row")):
        got, fetches, samples = _serve(eng, requests)
    picks = 12 * eng.max_batch_size * eng.spec_len
    assert _downloads(eng) == 0
    assert [f["bytes"] for f in fetches] == [picks] * eng.steps
    assert picks < 1024
    assert [s["host_rows"] for s in samples] == [0] * eng.steps
    if "spec_k" in kw:
        assert eng._m_spec_accepted.value > 0

    host = _engine(*model_and_vars, **kw)
    with _host_path():
        want, fetches, samples = _serve(host, requests)
    assert got == want          # token streams and float sums, exactly
    assert host.steps == eng.steps == _downloads(host)
    assert [f["bytes"] for f in fetches] == \
        [picks + _logits_bytes(host)] * host.steps
    # a row emits its own token and one more for each draft accepted
    assert sum(s["host_rows"] for s in samples) == sum(
        s["emitted"] for s in samples) - host._m_spec_accepted.value


def test_a_mixed_step_samples_one_row_on_the_host(model_and_vars):
    """Three greedy requests and one at a temperature, decoded
    together: every stream is the host path's (and a solo run's); the
    logits come down in exactly the steps in which the sampled request
    draws a token, where it is the one host row."""
    requests = [{"prompt": list(p), "max_new_tokens": 10}
                for p in PROMPTS[:3]]
    hot = {"prompt": [3, 1, 4, 1, 5, 9, 2, 6], "max_new_tokens": 6,
           "temperature": 0.7, "top_k": 20, "seed": 11}
    eng = _engine(*model_and_vars)
    got, fetches, samples = _serve(eng, requests + [hot])
    host = _engine(*model_and_vars)
    with _host_path():
        want, _, _ = _serve(host, requests + [hot])
    assert got == want
    solo, _, _ = _serve(_engine(*model_and_vars), [hot])
    assert got[3] == solo[0]
    # 6 tokens: the final chunk's and five decode rows'
    assert _downloads(eng) == 6 < eng.steps
    assert sum(s["host_rows"] for s in samples) == 6
    small = 12 * eng.max_batch_size
    for f, s in zip(fetches, samples):
        assert s["host_rows"] in (0, 1)
        assert f["bytes"] == small + s["host_rows"] * _logits_bytes(eng)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_fork_on_a_final_chunk(model_and_vars, temperature):
    """`n=3` off one prefill beside a greedy neighbour. At a
    temperature the siblings draw their first tokens from the final
    chunk's row on the host, under their own seeds; greedy siblings
    take the step's pick and no logits move. Either way every candidate
    is the host path's."""
    requests = [
        {"prompt": [7, 8, 9, 10, 11, 12, 13, 14], "max_new_tokens": 8,
         "temperature": temperature, "seed": 5, "n": 3},
        {"prompt": [2, 2, 7], "max_new_tokens": 5}]
    eng = _engine(*model_and_vars, max_prefill_tokens=4)
    got, fetches, samples = _serve(eng, requests)
    host = _engine(*model_and_vars, max_prefill_tokens=4)
    with _host_path():
        want, _, _ = _serve(host, requests)
    assert len(got) == 4 and got == want
    if temperature:
        assert len({tuple(toks) for toks, _ in got[:3]}) > 1
        # the final chunk's step, then seven in which three rows decode
        assert _downloads(eng) == 8
        assert sum(s["host_rows"] for s in samples) == 1 + 7 * 3
    else:
        assert got[0] == got[1] == got[2]
        assert _downloads(eng) == 0
        assert max(f["bytes"] for f in fetches) < 1024


def test_a_speculative_row_at_a_temperature(model_and_vars):
    """Drafts of a row that samples are held against `_sample`'s token
    on the host, a greedy neighbour's against the step's ids, in the
    same steps: both streams are the plain engine's."""
    requests = [
        {"prompt": list(REPEATY), "max_new_tokens": 16,
         "temperature": 0.7, "seed": 11},
        {"prompt": [1, 2, 3] * 5, "max_new_tokens": 16}]
    plain, _, _ = _serve(_engine(*model_and_vars), requests)
    eng = _engine(*model_and_vars, spec_k=4)
    got, fetches, samples = _serve(eng, requests)
    assert got == plain
    assert eng._m_spec_accepted.value > 0
    assert 0 < _downloads(eng) == sum(s["host_rows"] for s in samples)
    assert all(f["bytes"] in (12 * 4 * 5, 12 * 4 * 5 + _logits_bytes(eng))
               for f in fetches)


def test_the_counter_stands_beside_the_steps(model_and_vars):
    """`ptpu_engine_logit_downloads_total` is registered with the
    engine, reads 0 after greedy traffic and is scraped with the rest."""
    eng = _engine(*model_and_vars)
    eng.generate(PROMPTS, max_new_tokens=4)
    text = eng.metrics_text()
    assert "ptpu_engine_logit_downloads_total 0" in text
    assert f"ptpu_engine_steps_total {eng.steps}" in text
