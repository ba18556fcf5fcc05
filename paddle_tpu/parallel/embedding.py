"""Sharded sparse embedding — the parameter-server capability, TPU-first.

Reference: the distributed lookup table — sparse parameters sharded across
pserver processes, rows prefetched by id over RPC, gradients pushed as
SelectedRows (operators/lookup_table_op.cc:75 `is_distributed`/
`remote_prefetch`; distributed/parameter_prefetch.h:26;
framework/selected_rows.h:32; split_ids/merge_ids ops).

TPU-native design: the table lives row-sharded over a mesh axis (each
device owns `vocab/axis_size` contiguous rows — the analog of one
pserver's block). A lookup is a shard_map over the mesh:

    local = ids - my_first_row          (split_ids capability)
    emb   = take(my_rows, clamp(local)) masked to my range
    out   = psum(emb, axis)             (merge_ids + prefetch reply)

so each device reads only its own rows and the combine is ONE psum over
ICI — no all-gather of the table, no RPC. The backward of this program is
a masked scatter-add into the local shard only: gradients stay sparse and
sharded (SelectedRows capability) without any wire format.

Optimizer state sharding falls out for free: MeshTrainer's rule table
shards Adam moments like their parameters, so the full pserver memory
story (params + accumulators distributed) holds.

True async-SGD is deliberately not reproduced — it contradicts SPMD; the
capability (CTR-scale sparse models) is delivered by sync sharded lookup
+ gradient accumulation (SURVEY §7 "Async/PS semantics on TPU").
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.module import Context, Module
from paddle_tpu.nn import initializers as I
from paddle_tpu.parallel.sharding import ShardingRules


def _round_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


class ShardedEmbedding(Module):
    """Row-sharded embedding table over `axis` (default "fsdp").

    Drop-in for nn.layers.Embedding (same forward signature), usable as
    DeepFM's `embedding_cls`. Two execution paths:

    - `mesh` given: explicit shard_map lookup (masked local gather + one
      psum) — the guaranteed-efficient pattern described in the module
      docstring. `batch_axes` must name how the ids' leading dim is
      sharded (MeshTrainer's DistStrategy.batch_axes).
    - `mesh=None`: plain take under a sharding constraint; XLA's SPMD
      partitioner derives the same program from the table's sharding.

    The table is padded up to a multiple of the axis size so every device
    owns an equal block of rows (the reference pads pserver blocks the
    same way, distribute_transpiler.py:84 slice_variable).
    """

    def __init__(self, num_embeddings: int, features: int,
                 axis: str = "fsdp", mesh: Optional[Mesh] = None,
                 batch_axes: Sequence[str] = ("dp",),
                 padding_idx: Optional[int] = None, embedding_init=None,
                 dtype=jnp.float32, param_dtype=jnp.float32):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.features = features
        self.axis = axis
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.padding_idx = padding_idx
        self.embedding_init = embedding_init or I.normal(0.0, 0.02)
        self.dtype = dtype
        self.param_dtype = param_dtype

    # Sharding rule for this table (feed to MeshTrainer rules): row dim on
    # `axis`, features replicated.
    @property
    def partition_spec(self) -> P:
        return P(self.axis, None)

    def _padded_vocab(self) -> int:
        n = self.mesh.shape[self.axis] if self.mesh is not None else 1
        return _round_up(self.num_embeddings, max(n, 1))

    def forward(self, cx: Context, ids):
        vocab = self._padded_vocab()
        table = cx.param("weight", (vocab, self.features),
                         self.embedding_init, self.param_dtype)
        # Clamp into the real vocab BEFORE dispatch so both paths agree:
        # without this, the mesh path could return an uninitialized padding
        # row for ids in [num_embeddings, padded_vocab) and zeros for
        # negative ids, while the dense path clamps — same model, different
        # outputs. Clamping matches jnp.take's (and the dense Embedding's)
        # out-of-range semantics everywhere.
        lookup_ids = jnp.clip(ids, 0, self.num_embeddings - 1)
        if self.mesh is not None and self.mesh.shape[self.axis] > 1:
            out = self._shard_map_lookup(table, lookup_ids)
        else:
            out = jnp.take(table, lookup_ids, axis=0)
        out = out.astype(self.dtype)
        if self.padding_idx is not None:
            mask = (ids != self.padding_idx)[..., None]
            out = jnp.where(mask, out, jnp.zeros_like(out))
        return out

    def _shard_map_lookup(self, table, ids):
        from jax import shard_map

        mesh, axis = self.mesh, self.axis
        batch_axes = tuple(a for a in self.batch_axes if a in mesh.shape
                           and mesh.shape[a] > 1)
        n_shards = mesh.shape[axis]
        rows_per = table.shape[0] // n_shards

        def lookup(table_shard, ids_blk):
            # my row range (split_ids): shard k owns [k*rows_per, ...)
            first = jax.lax.axis_index(axis) * rows_per
            local = ids_blk - first
            ok = (local >= 0) & (local < rows_per)
            emb = jnp.take(table_shard, jnp.where(ok, local, 0), axis=0)
            emb = jnp.where(ok[..., None], emb, 0)
            # merge_ids: exactly one shard contributed each row
            return jax.lax.psum(emb, axis)

        ids_spec = P(batch_axes if batch_axes else None)
        out_spec = P(*( (batch_axes if batch_axes else None),
                        *(None,) * (ids.ndim - 1), None))
        return shard_map(
            lookup, mesh=mesh,
            in_specs=(P(axis, None), ids_spec),
            out_specs=out_spec,
            check_vma=False)(table, ids)


def embedding_rules(axis: str = "fsdp",
                    pattern: str = r"(table|embed[^/]*|w1)/weight$"
                    ) -> ShardingRules:
    """Rule table sharding embedding-style params row-wise over `axis`
    (matches DeepFM's `table`/`w1` and any `embed*` module). Combine with
    fsdp rules via `.add()` for the dense tower."""
    return ShardingRules([(pattern, (axis, None))])


def shard_table(mesh: Mesh, table: jax.Array, axis: str = "fsdp"):
    """Place an existing [V, E] table row-sharded on the mesh (the initial
    'send blocks to pservers' step, distribute_transpiler get_startup)."""
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


# -- checkpoint guards -------------------------------------------------------
# The padded table ([num_embeddings, padded_vocab) rows) is saved in
# checkpoints; if num_embeddings or the shard axis size changes between save
# and load, the same on-disk shape can hold differently-aligned rows. These
# helpers stamp/verify the logical geometry in the checkpoint manifest.

def checkpoint_meta(*embeddings: "ShardedEmbedding") -> dict:
    """Metadata dict for io.checkpoint.save_checkpoint(metadata=...)."""
    return {"sharded_embeddings": [
        {"num_embeddings": e.num_embeddings,
         "padded_vocab": e._padded_vocab(),
         "features": e.features} for e in embeddings]}


def validate_checkpoint_meta(metadata: dict,
                             *embeddings: "ShardedEmbedding") -> None:
    """Raise if a checkpoint's embedding geometry mismatches the modules.

    Pass io.checkpoint.read_metadata(path). Checkpoints saved without the
    stamp (older or foreign) validate trivially.
    """
    saved = (metadata or {}).get("sharded_embeddings")
    if saved is None:
        return
    if len(saved) != len(embeddings):
        raise ValueError(
            f"checkpoint has {len(saved)} sharded embeddings, model has "
            f"{len(embeddings)}")
    for i, (meta, emb) in enumerate(zip(saved, embeddings)):
        want = {"num_embeddings": emb.num_embeddings,
                "padded_vocab": emb._padded_vocab(),
                "features": emb.features}
        if meta != want:
            raise ValueError(
                f"sharded embedding {i} geometry changed since save: "
                f"checkpoint {meta} vs model {want}; padded rows would "
                "silently misalign — re-export the table instead")
