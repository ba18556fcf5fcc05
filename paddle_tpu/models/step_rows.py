"""The serving step's contract between the engine and a served model,
its two row widths, and the moves between them.

The engine's compiled step calls `serve_step` with the operands it
packed. That builds the step's `StepBatch` once, hands it to the
model's `trunk` (its own loop over its layers: x [T_c, D], the pools
updated, and the tokens each expert took or None), gathers the rows the
engine samples, and hands them to the model's `logits` (the final norm
and the head). Everything else the engine knows of a model is what it
declares (`ServedModel`): its cache layout, its pool row, its expert
layers, its snapshot spacing and its manifest block.

The engine packs a step into T flat rows (kernels/paged_attention.py,
`ragged_paged_attention`): the chunk budget rounded up to whole query
tiles, then a tile-aligned segment of `roundup(spec_len, tile_q)` rows
for each of the B batch rows. A decode row of one token thereby takes a
whole tile, so most of T is padding in a decode step. The tile kernels
need that layout (a tile is a grid cell, a row's tokens are a prefix of
its tiles); nothing else does. Every product, norm and elementwise
chain works per token, so it runs on the step's real tokens alone, in
the order they lie in the packing, at the COMPACT width

    T_c = roundup(budget, tile_q) + B . spec_len
        = T - B . roundup(spec_len, tile_q) + B . spec_len,

read from the shapes the step already has (`last_idx` is [B, spec_len]).
A step never holds more real tokens than that: a chunk is at most the
budget, a decode row at most spec_len tokens, and there are at most B
rows (`engine.pack` raises where a plan would break it).

`step_rows` computes ONE map a step from the packing's own operands: a
flat position is a token where it lies below its tile's count of real
positions. Before a tile kernel the compact rows are laid out in a
`[T, ...]` with zeros at the padding (`StepRows.expand`), after it the
kernel's output is gathered back (`StepRows.compact`). Both moves are
gathers of whole rows: on a TPU v5e a scatter of float32 rows into
zeros took eight times as long as the gather that fills the same array.
The compact rows past the step's tokens gather zeros (token 0 at
position 0, slot 0: the pad convention, so a write of theirs lands in
the scratch block). Where T_c is T (tile_q 1) both moves are the
identity.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import selective_scan as scan


class StepRows(NamedTuple):
    """A step's compaction map. `flat_of` [T_c] int32 is a compact row's
    flat position, T (out of range) past the real ones, and `compact_of`
    [T] a flat position's compact row, T_c (out of range) at the
    padding; both None where T_c is T. `real` [T_c] bool marks the
    compact rows that are tokens, `flat_real` [T] the flat positions
    that are; `last` is `last_idx` as compact rows; `width` is T."""
    flat_of: Optional[jax.Array]
    compact_of: Optional[jax.Array]
    real: jax.Array
    flat_real: jax.Array
    last: jax.Array
    width: int

    def compact(self, x):
        """x [T, ...] -> [T_c, ...]: the real positions in order, zeros
        after them."""
        if self.flat_of is None:
            return x
        return jnp.take(x, self.flat_of, axis=0, mode="fill", fill_value=0)

    def expand(self, x):
        """x [T_c, ...] -> [T, ...]: each real row at its flat position,
        zeros at the padding."""
        if self.compact_of is None:
            return x
        return jnp.take(x, self.compact_of, axis=0, mode="fill",
                        fill_value=0)


def step_rows(tile_rows, tile_offs, q_starts, context_lens, last_idx,
              width: int) -> StepRows:
    """The map of a step of `width` flat rows from its operands: a tile
    holds min(context - start, tile_q) real positions of its row, none
    on the null row (the last of the metadata). `last_idx` [B] or
    [B, spec_len] gives B and spec_len."""
    nt = tile_rows.shape[0]
    tq = width // nt
    b, s = (tuple(last_idx.shape) + (1,))[:2]
    t_c = width - b * (-(-s // tq) * tq) + b * s
    row = tile_rows.astype(jnp.int32)
    count = jnp.clip(context_lens[row] - q_starts[row] - tile_offs, 0, tq)
    count = jnp.where(row == context_lens.shape[0] - 1, 0, count)
    flat_real = (jnp.arange(width, dtype=jnp.int32) % tq
                 < jnp.repeat(count, tq))
    idx = last_idx.astype(jnp.int32)
    if t_c >= width:
        return StepRows(None, None, flat_real, flat_real, idx, width)
    flat_of = jnp.nonzero(flat_real, size=t_c, fill_value=width)[0]
    rank = jnp.cumsum(flat_real, dtype=jnp.int32) - 1
    return StepRows(flat_of.astype(jnp.int32),
                    jnp.where(flat_real, rank, t_c), flat_of < width,
                    flat_real, jnp.maximum(rank[idx], 0), width)


class StepBatch(NamedTuple):
    """One step as a model's `trunk` reads it. The engine's flat
    operands as it packs them: `flat_positions` and `flat_slots` [T],
    `block_tables`, `context_lens` and `q_starts` a row,
    `tile_rows` and `tile_offs` a query tile of `tq` rows. The map
    between the widths (`packing`), and the step's tokens at the
    compact width: `tokens`, `positions`, `slots` [T_c]. Only
    `CausalLM` reads `tp` (parallel.serve_collective.ServeTP or None)
    and `qpools` / `qscales`, the in-device int8 tier's pools and
    scales a layer (empty lists when it is off)."""
    flat_positions: jax.Array
    flat_slots: jax.Array
    block_tables: jax.Array
    context_lens: jax.Array
    q_starts: jax.Array
    tile_rows: jax.Array
    tile_offs: jax.Array
    packing: StepRows
    tokens: jax.Array
    positions: jax.Array
    slots: jax.Array
    tq: int
    tp: Any
    qpools: List
    qscales: List

    def ring_rows(self, ring, block_size: int):
        """(table, rows_at) of a window pool: the block table of a
        step row's ring by logical block (logical block b lives in ring
        place b mod ring; `ring` [R, places] the pool blocks of a step
        row's ring, the ROWS table's), and the pool row each of the
        step's tokens is written to [T_c] (padding: scratch block 0)."""
        mb = self.block_tables.shape[1]
        places = jnp.arange(mb, dtype=jnp.int32) % ring.shape[1]
        table = ring[:, places]
        row_of = jnp.repeat(self.tile_rows, self.tq)
        positions = self.flat_positions
        block = jnp.take_along_axis(
            table[row_of], (positions // block_size)[:, None], axis=1)[:, 0]
        rows_at = self.packing.compact(jnp.where(
            self.packing.flat_real,
            block * block_size + positions % block_size, 0))
        return table, rows_at

    def tile_meta(self, row_slots):
        """(slot, real, fresh, last) a flat position for the recurrent
        kernels (kernels/selective_scan.py `tile_meta`), `row_slots`
        [R] a step row's state slot."""
        return scan.tile_meta(row_slots, self.context_lens, self.q_starts,
                              self.tile_rows, self.tile_offs, self.tq)


def serve_step(model, cx: Context, tokens, positions, pools, qpools,
               qscales, block_tables, context_lens, q_starts, tile_rows,
               tile_offs, slots, last_idx, tp=None):
    """ONE mixed prefill+decode step over the flat ragged packing (the
    engine's `_step_fn`, kernels/paged_attention.py
    `ragged_paged_attention` for the operands). Returns (logits
    [*last_idx.shape, V], the pools updated) and, for a model that
    counts its experts, the step's tokens per expert int32
    [expert layers, E]. `last_idx` [B] or [B, S] gathers the rows the
    engine samples by flat index; a share of an expert-parallel layer
    counts [expert layers, E + 1], its last column the pairs sent
    away."""
    packing = step_rows(tile_rows, tile_offs, q_starts, context_lens,
                        last_idx, tokens.shape[0])
    positions = positions.astype(jnp.int32)
    batch = StepBatch(
        positions, slots, block_tables, context_lens, q_starts, tile_rows,
        tile_offs, packing, *map(packing.compact, (tokens, positions, slots)),
        tokens.shape[0] // tile_rows.shape[0], tp, qpools, qscales)
    x, pools, counts = model.trunk(cx, batch, pools)
    logits = model.logits(cx, jnp.take(x, packing.last.reshape(-1), axis=0))
    logits = logits.reshape(packing.last.shape + (logits.shape[-1],))
    if counts is None:
        return logits, pools
    return logits, pools, (jnp.stack(counts) if counts else
                           jnp.zeros((0, model.num_experts), jnp.int32))


class ServedModel(Module):
    """What the engine reads off a model it serves, declared, with the
    defaults of a model that has no such thing. A served model sets
    `model_type` (its manifest's name), `cache_layout` (what each layer
    keeps between steps, engine/paged_cache.py `CacheLayout`) and its
    pool's row: `kv_row` = (kv heads, head width) of a [k | v] row, or
    `latent_row` = (k_dim, v_dim) of a latent pool. `expert_layers` and
    `num_experts` (the experts a layer holds) size the engine's
    tokens-per-expert count; a model whose expert layers are one chip's
    share of an expert-parallel deployment says over how many chips
    (`expert_shards` > 1), and its count has one column more, the pairs
    sent to experts held elsewhere;
    `snapshot_tokens` / `snapshot_slots` are what it asks of the cache
    for prefix reuse over state (an engine's own arguments override
    them); `sparse_counts(start, length)` is what a step row reads of
    one sparse layer. It implements `trunk(cx, batch, pools)` and
    `logits(cx, rows)` (`serve_step`)."""
    model_type: str
    cache_layout: list
    kv_row = None
    latent_row = None
    expert_layers = 0
    num_experts = 0
    expert_shards = 1
    snapshot_tokens = 0
    snapshot_slots = 0
    sparse_counts = None

    def serve_metadata(self) -> dict:
        """The manifest's `serve` block (io/inference.py
        `save_inference_model(..., serve_meta=...)`): what
        `from_serve_metadata` rebuilds the model from."""
        return {"model_type": self.model_type, "config": dict(self.config),
                "max_len": self.max_len,
                "dtype": jnp.dtype(self.dtype).name,
                "param_dtype": self.param_dtype.name}

    @classmethod
    def from_serve_metadata(cls, meta: dict):
        return cls(**meta["config"], dtype=jnp.dtype(meta["dtype"]),
                   param_dtype=jnp.dtype(meta["param_dtype"]))

    def trunk(self, cx: Context, batch: StepBatch, pools):
        raise NotImplementedError

    def logits(self, cx: Context, rows):
        raise NotImplementedError
