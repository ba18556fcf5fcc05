"""The serving step's two row widths, and the moves between them.

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

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


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
