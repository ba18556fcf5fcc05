"""The one step contract between the engine and the six served models
(models/step_rows.py): each model declares what the engine reads
(`ServedModel`), an export rebuilds the same model through its
`model_type`, and the benchmark's lowering of the engine's step by its
positional operands (`benchmarks/runners/serve_closed.py` `lower_step`)
still lowers. On the CPU, at the toy widths of tests/test_step_rows.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.runners.serve_closed import lower_step
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.obs.metrics import MetricsRegistry
from test_step_rows import VOCAB, _models

pytestmark = pytest.mark.serve

NAMES = sorted(_models())
KW = dict(max_batch_size=4, block_size=4, num_blocks=64,
          max_prefill_tokens=8, tile_q=4)


def _built(name):
    model = _models()[name]()
    variables = model.init(jax.random.PRNGKey(5),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables):
    return ServeEngine(model, variables, registry=MetricsRegistry(), **KW)


@pytest.mark.parametrize("name", NAMES)
def test_the_model_declares_the_step_contract(name):
    """A layout entry a layer, one kind of pool row, the expert layers
    it counts, its own `trunk` and `logits`, and a manifest block it
    rebuilds itself from."""
    model, _ = _built(name)
    assert isinstance(model, ServedModel)
    assert len(model.cache_layout) == len(model.blocks)
    assert (model.kv_row is None) != (model.latent_row is None)
    routed = sum(getattr(b, "routed", False) for b in model.blocks)
    assert model.expert_layers == routed
    assert (model.num_experts > 0) == (name in ("latent_moe", "conv_moe",
                                                "window_moe"))
    for method in ("trunk", "logits"):
        assert getattr(type(model), method) is not getattr(ServedModel,
                                                           method)
    meta = model.serve_metadata()
    assert meta["model_type"] == model.model_type
    again = type(model).from_serve_metadata(meta)
    assert again.serve_metadata() == meta
    assert again.cache_layout == model.cache_layout


@pytest.mark.parametrize("name", NAMES)
def test_an_export_serves_the_in_memory_engines_tokens(name, tmp_path):
    """Export, then `ServeEngine.from_saved_model`: the same class, the
    same cache, the same greedy tokens as the engine over the model in
    memory."""
    from paddle_tpu.io.inference import save_inference_model
    model, variables = _built(name)
    path = str(tmp_path / "m")
    save_inference_model(path, model, variables,
                         [jnp.zeros((1, 4), jnp.int32)],
                         input_names=["tokens"],
                         serve_meta=model.serve_metadata())
    eng = ServeEngine.from_saved_model(path, registry=MetricsRegistry(),
                                       **KW)
    here = _engine(model, variables)
    assert type(eng.model) is type(model)
    assert eng.cache.kinds == here.cache.kinds
    assert eng.cache.latent == here.cache.latent
    prompts = [np.random.default_rng(9).integers(0, VOCAB, n).tolist()
               for n in (13, 6)]
    assert (eng.generate(prompts, max_new_tokens=4)
            == here.generate(prompts, max_new_tokens=4))
    assert eng._step_fn._cache_size() == 1


@pytest.mark.parametrize("name", NAMES)
def test_the_benchmark_lowers_the_engine_step(name):
    """`lower_step` hands `_step_fn` its 13 operands by position, the
    cache's int8 pools and scales among them: the step lowers, and its
    outputs are the picks of B x spec_len rows and the pools as they
    went in (and, for an expert model, the tokens per expert)."""
    model, variables = _built(name)
    eng = _engine(model, variables)
    lowered = lower_step(eng, lambda x: jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype))
    (logits, lse, ids, top), pools, *rest = lowered.out_info
    b, s = eng.max_batch_size, eng.spec_len
    assert logits.shape == (b, s, VOCAB)
    assert lse.shape == ids.shape == top.shape == (b, s)
    assert [p.shape for p in pools] == [p.shape for p in eng.cache.pools]
    # a share of an expert-parallel layer counts the pairs it sent away
    # in one column more
    assert [r.shape for r in rest] == (
        [(model.expert_layers,
          model.num_experts + (model.expert_shards > 1))]
        if model.num_experts else [])
