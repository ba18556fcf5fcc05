"""Sharding planner: map parameter trees to PartitionSpecs.

Capability-equivalent of the reference's program "transpilers":
- DistributeTranspiler (transpiler/distribute_transpiler.py:280): decides,
  per parameter, where it lives and how updates flow. Here: a rule table
  from parameter path → PartitionSpec, applied over the pytree.
- MultiDevSSAGraphBuilder's per-gradient collective insertion
  (details/multi_devices_graph_pass.cc:393): XLA's SPMD partitioner inserts
  the collectives; the planner only declares placements.

Rules are (regex, spec) pairs, first match wins — the idiom used by large
JAX codebases for assigning tp/fsdp axes by parameter name.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


class ShardingRules:
    """Ordered (path-regex → PartitionSpec) table.

    Paths are '/'-joined tree paths (same notation as checkpoints). A spec
    entry may be: None (replicate dim), an axis name, or a tuple of axis
    names. Unmatched params fall back to `default`, or — when `fsdp_axis`
    is set — to ZeRO-style sharding of the largest dim of any parameter
    with prod(shape) >= fsdp_min_size and rank >= fsdp_min_rank. The
    fallback is a constructor feature so rule tables compose (an earlier
    design patched spec_for per instance).
    """

    def __init__(self, rules: Sequence[Tuple[str, Sequence]] = (),
                 default: Optional[Sequence] = None,
                 fsdp_axis: Optional[str] = None,
                 fsdp_min_size: int = 0, fsdp_min_rank: int = 1):
        self._rules = [(re.compile(pat), tuple(spec)) for pat, spec in rules]
        self.default = tuple(default) if default is not None else None
        self.fsdp_axis = fsdp_axis
        self.fsdp_min_size = fsdp_min_size
        self.fsdp_min_rank = fsdp_min_rank

    def add(self, pattern: str, spec: Sequence) -> "ShardingRules":
        self._rules.append((re.compile(pattern), tuple(spec)))
        return self

    def spec_for(self, path: str, shape: Sequence[int]) -> P:
        for pat, spec in self._rules:
            if pat.search(path):
                return P(*_fit_spec(spec, shape))
        if self.default is not None:
            return P(*_fit_spec(self.default, shape))
        if (self.fsdp_axis is not None
                and len(shape) >= self.fsdp_min_rank
                and shape and int(np.prod(shape)) >= self.fsdp_min_size):
            entries: List = [None] * len(shape)
            entries[int(np.argmax(shape))] = self.fsdp_axis
            return P(*entries)
        return P()

    def tree_specs(self, tree: Pytree) -> Pytree:
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for path, leaf in flat:
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in path)
            specs.append(self.spec_for(key, np.shape(leaf)))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def tree_shardings(self, mesh: Mesh, tree: Pytree) -> Pytree:
        return jax.tree.map(lambda s: NamedSharding(mesh, s),
                            self.tree_specs(tree),
                            is_leaf=lambda x: isinstance(x, P))


def _fit_spec(spec: Sequence, shape: Sequence[int]) -> Tuple:
    """Trim/pad a spec to the rank of `shape` (trailing dims replicate)."""
    spec = tuple(spec)[: len(shape)]
    return spec + (None,) * (len(shape) - len(spec))


def fsdp_rules(axis: str = "fsdp", min_size: int = 2 ** 16) -> ShardingRules:
    """ZeRO-style default: shard the largest dim of big params over `axis`.

    ≈ reference ReduceStrategy::kReduce (params round-robined across
    devices, build_strategy.h:55) — but deterministic by-dim instead of
    round-robin by-param, which is what XLA shards well.
    """
    return ShardingRules(fsdp_axis=axis, fsdp_min_size=min_size)


def shard_variables(mesh: Mesh, tree: Pytree,
                    rules: Optional[ShardingRules] = None) -> Pytree:
    """Place a pytree onto the mesh per rules (replicate by default).

    ≈ ParallelExecutor::BCastParamsToDevices (parallel_executor.cc:73): the
    initial broadcast of parameters to all devices — here a device_put with
    NamedShardings, so replicated and sharded params are handled uniformly.
    """
    rules = rules or ShardingRules()
    shardings = rules.tree_shardings(mesh, tree)
    return jax.tree.map(jax.device_put, tree, shardings)


# Ready-made rule sets for the model zoo ------------------------------------

def transformer_tp_rules(tp_axis: str = "tp",
                         fsdp_axis: Optional[str] = "fsdp") -> ShardingRules:
    """Megatron-style TP for the transformer family:
    - attention qkv/out and mlp in/out projections split on the feature dim;
    - embeddings split on vocab;
    - everything else fsdp-sharded or replicated.
    """
    return ShardingRules([
        (r"(q_proj|k_proj|v_proj|qkv|kv)/weight$", (None, tp_axis)),
        (r"(out_proj|o_proj)/weight$", (tp_axis, None)),
        (r"(fc1|w_in|up|gate)/weight$", (None, tp_axis)),
        (r"(fc2|w_out|down)/weight$", (tp_axis, None)),
        (r"embed[^/]*/weight$", (tp_axis, None)),
        (r"bias$", (None,)),
    ], fsdp_axis=fsdp_axis, fsdp_min_rank=2)


def serve_tp_rules(tp_axis: str = "tp") -> ShardingRules:
    """Megatron TP for the SERVING step (engine/engine.py tp_size knob).

    Differs from transformer_tp_rules where serving constraints demand
    it: embeddings and the LM head stay REPLICATED (the ragged step
    gathers last_idx rows and samples host-side — a vocab-sharded head
    would force an extra collective per step), column-parallel biases
    shard WITH their features (fc1/qkv output columns live per-shard),
    and row-parallel biases (fc2/out_proj) replicate — they are added
    after the reduce, once. Attention q/k/v shard on the head dim
    (head-major qkv packing keeps each head's q/k/v on one shard; KV
    pools shard the same way, PagedKVCache pool_shape), out_proj is
    row-parallel. Everything unmatched replicates (default=()).
    """
    return ShardingRules([
        (r"(q_proj|k_proj|v_proj|qkv)/weight$", (None, tp_axis)),
        (r"(q_proj|k_proj|v_proj|qkv)/bias$", (tp_axis,)),
        (r"fc1/weight$", (None, tp_axis)),
        (r"fc1/bias$", (tp_axis,)),
        (r"(out_proj|fc2)/weight$", (tp_axis, None)),
    ], default=())
