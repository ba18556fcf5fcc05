"""Shared saved-model fixture helpers.

The export-then-verify dance (save_inference_model → InferencePredictor
→ assert served == direct apply) was growing copies in
tests/test_serving.py, examples/quantize_int8_serve.py, and the engine
tests; this is the single implementation.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np


def export_servable(path: str, model, variables,
                    example_inputs: Sequence[Any],
                    input_names: Optional[Sequence[str]] = None,
                    serve_meta: Optional[dict] = None,
                    verify: bool = False) -> str:
    """Export `model` as a servable directory at `path`; with
    verify=True, round-trip the example inputs through an
    InferencePredictor and assert the served outputs match the direct
    apply() — the exported artifact provably computes the same function.
    Returns `path`."""
    import jax.numpy as jnp

    from paddle_tpu.io.inference import (InferencePredictor,
                                         save_inference_model)

    save_inference_model(path, model, variables, example_inputs,
                         input_names=input_names, serve_meta=serve_meta)
    if verify:
        served = InferencePredictor(path).run(
            [np.asarray(x) for x in example_inputs])[0]
        direct = np.asarray(model.apply(
            variables, *[jnp.asarray(x) for x in example_inputs],
            training=False))
        np.testing.assert_allclose(served, direct, rtol=1e-6, atol=1e-6)
    return path


def export_causal_lm(path: str, vocab: int = 61, model_dim: int = 16,
                     num_heads: int = 2, num_layers: int = 2,
                     ffn_dim: int = 32, max_len: int = 64,
                     num_kv_heads: Optional[int] = None, seed: int = 0,
                     dtype=None):
    """Servable CausalLM for engine tests/benches and chip_smoke.py
    (tiny by default): init with a fixed seed, export with the manifest
    `serve` block, return (path, model, variables). dtype is the
    compute dtype (None: the model's float32 default)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.io.inference import save_inference_model
    from paddle_tpu.models.transformer import CausalLM

    model = CausalLM(vocab=vocab, model_dim=model_dim, num_heads=num_heads,
                     num_layers=num_layers, ffn_dim=ffn_dim, dropout=0.0,
                     max_len=max_len, num_kv_heads=num_kv_heads,
                     dtype=dtype or jnp.float32)
    variables = model.init(jax.random.PRNGKey(seed),
                           jnp.zeros((1, 4), jnp.int32))
    save_inference_model(  # export the forward; engine rebuilds from serve
        path, model, variables, [jnp.zeros((1, 4), jnp.int32)],
        input_names=["tokens"], serve_meta=model.serve_metadata())
    return path, model, variables
