"""Inference model export/load.

Capability-equivalent of the reference inference stack:
- save_inference_model (io.py:859): prune to fetch targets + serialize
  program + params → here: export the *traced* forward fn as StableHLO
  (jax.export) + params checkpoint + a JSON signature. StableHLO is the
  TPU-native analog of the pruned ProgramDesc: a compiler-stable, versioned
  serialization of exactly the computation to serve.
- load_inference_model (io.py:1011) / AnalysisPredictor::Run
  (api/analysis_predictor.h:52): `InferencePredictor` deserializes and
  compiles once, then `run()` is zero-overhead (≈ ZeroCopyRun :61).
- The reference's Analyzer fusion passes (analysis/ir_pass_manager.cc) are
  XLA's job at compile time — the export records optimization-independent
  semantics.

The C++ serving shim (paddle_tpu/serving/) reads the same artifact layout.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
# jax 0.4.x: the `jax.export` ATTRIBUTE raises (accelerated deprecation
# shim) while the submodule imports fine — bind the module directly
from jax import export as jax_export

from paddle_tpu.core.module import Module, Variables
from paddle_tpu.io.checkpoint import load_checkpoint, save_checkpoint

_SIG = "signature.json"
_HLO = "model.stablehlo"
_PARAMS = "params"


def _prune_empty(tree):
    """Drop empty sub-dicts (e.g. a stateless model's empty `state`
    collection) so the exported pytree structure matches what a checkpoint
    round-trip reconstructs."""
    if isinstance(tree, dict):
        out = {k: _prune_empty(v) for k, v in tree.items()}
        return {k: v for k, v in out.items()
                if not (isinstance(v, dict) and not v)}
    return tree


def save_inference_model(path: str, module_or_fn, variables: Variables,
                         example_inputs: Sequence[Any],
                         input_names: Optional[Sequence[str]] = None,
                         serve_meta: Optional[Dict] = None) -> str:
    """Export a servable model directory.

    module_or_fn: a Module (its apply in eval mode is exported) or a pure
    fn(variables, *inputs). The exported computation closes over nothing —
    params are explicit inputs so the same artifact serves any checkpoint
    with the same structure.

    serve_meta: optional dict recorded as the manifest's `serve` block
    (model.serve_metadata() of a served model: max seq length, KV
    head count/dim, vocab size, layer config) so
    `ServeEngine.from_saved_model` can rebuild the module and size its
    KV pools without re-deriving shapes from the checkpoint. Manifests
    written without it stay loadable everywhere (readers use .get).
    """
    if isinstance(module_or_fn, Module):
        module = module_or_fn

        def fn(variables, *inputs):
            return module.apply(variables, *inputs, training=False)
    else:
        fn = module_or_fn

    variables = _prune_empty(variables)
    # Gather to host first: training variables may be mesh-sharded, and
    # jax.export would bake the training device count into the artifact —
    # a served model must load on any topology (≈ the reference's pruned
    # inference ProgramDesc being executor-agnostic, io.py:859).
    variables = jax.tree.map(np.asarray, variables)
    example_inputs = tuple(jnp.asarray(x) for x in example_inputs)
    exported = jax_export.export(jax.jit(fn))(variables, *example_inputs)
    blob = exported.serialize()

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, _HLO), "wb") as f:
        f.write(blob)
    save_checkpoint(os.path.join(path, _PARAMS), variables)
    sig = {
        "version": 1,
        "input_names": list(input_names or
                            [f"x{i}" for i in range(len(example_inputs))]),
        "inputs": [{"shape": list(x.shape), "dtype": str(x.dtype)}
                   for x in example_inputs],
    }
    if serve_meta is not None:
        sig["serve"] = dict(serve_meta)
    with open(os.path.join(path, _SIG), "w") as f:
        json.dump(sig, f, indent=1)
    return path


def load_inference_model(path: str) -> Tuple[Callable, Variables, Dict]:
    """Returns (callable(variables, *inputs), variables, signature)."""
    with open(os.path.join(path, _HLO), "rb") as f:
        exported = jax_export.deserialize(f.read())
    variables = load_checkpoint(os.path.join(path, _PARAMS))
    with open(os.path.join(path, _SIG)) as f:
        sig = json.load(f)
    return exported.call, variables, sig


class InferencePredictor:
    """Compiled predictor over an exported model (≈ AnalysisPredictor).

    run(feed) accepts positional list or name-keyed dict; outputs come back
    as numpy. The first call compiles; afterwards it's a single dispatch.
    """

    def __init__(self, model_dir: str):
        fn, self.variables, self.signature = load_inference_model(model_dir)
        self._fn = jax.jit(fn)
        self._input_names = self.signature["input_names"]

    def run(self, feed) -> List[np.ndarray]:
        if isinstance(feed, dict):
            inputs = [feed[n] for n in self._input_names]
        else:
            inputs = list(feed)
        out = self._fn(self.variables, *[jnp.asarray(x) for x in inputs])
        leaves = jax.tree_util.tree_leaves(out)
        return [np.asarray(x) for x in leaves]
