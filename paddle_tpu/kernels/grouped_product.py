"""The routed experts' grouped matrix products: rows sorted by expert,
each expert's rows times that expert's weights, one Pallas call that
reads each touched expert's weights once, and its XLA reference.

A serving step hands an expert only a few rows (a decode step's pairs
over E experts), so the products are bound by the walk over the experts'
weights, not by the rows multiplied. The kernel is built for that:

- the grid is (column block j, visit v). A VISIT is one (expert, row
  tile) pair with rows in it, in expert order; the visits and their row
  ranges are reckoned in XLA from `counts` (`_visits`) and prefetched as
  scalars. An expert with no row has no visit, so its weights are never
  fetched; consecutive visits of one expert keep its weight block's
  index, so the pipeline fetches nothing new for them.
- a weight block is [in, tn] with tn as wide as the VMEM budget allows
  (`_tiles`: the whole width at the serving cells' shapes), so an
  expert's matrices arrive as one large copy each, back to back.
- a row tile that holds several experts' rows is visited once an
  expert, each visit writing only its expert's rows; the tile's first
  visit clears the rest. Row tiles past the last real row get one visit
  each that writes zeros and fetches nothing (the static grid's spare
  visits); the visits left after those compute nothing and repeat the
  last block indices.
- `gated_grouped_product` reads an expert's rows once against its gate
  and up matrices (two operands, as the parameters are stored) and
  writes silu(x gate) * (x up) in the input's dtype, accumulated in
  float32; `grouped_product` is one matrix (the down projection).

Rows past counts.sum() come out as zeros. The kernel runs on the TPU and
`jax.lax.ragged_dot` elsewhere (`paged_attention._resolve_dispatch`);
the gradient is always the reference's (a `custom_vjp` whose backward is
the reference's VJP).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.paged_attention import _resolve_dispatch

# rows a tile: an MXU pass costs about the same up to 128 rows, and a
# decode step's few rows an expert fit one tile (tiles of 64 and 256
# rows were no faster on a v5e at the expert cells' shapes)
ROW_TILE = 128
# VMEM for the weight blocks, double-buffered: the width of a block is
# the widest that fits (the whole [2048, 1792] gate and up of an expert
# at 29 MB)
WEIGHT_VMEM_BYTES = 40 << 20


def grouped_product_reference(x, w, counts):
    """x [M, in] sorted by group, w [G, in, out], counts [G] int32 ->
    [M, out]: rows of group g times w[g]. Rows past the groups' sum
    come out as zeros."""
    y = jax.lax.ragged_dot(x, w, counts.astype(jnp.int32))
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    return jnp.where((rows < counts.sum())[:, None], y, 0)


def _reference(x, weights, counts):
    if len(weights) == 1:
        return grouped_product_reference(x, weights[0], counts)
    gate, up = weights
    return (jax.nn.silu(grouped_product_reference(x, gate, counts))
            * grouped_product_reference(x, up, counts))


def _tiles(m: int, k: int, n: int, operands: int, itemsize: int) -> tuple:
    """(rows a tile, the weight block's width) from the shapes: x [m, k],
    `operands` weights of [k, n]. The width is the widest divisor of n
    in whole 128-lane tiles (or n itself) whose blocks, double-buffered,
    fit `WEIGHT_VMEM_BYTES`."""
    tm = ROW_TILE if m >= ROW_TILE else -(-m // 16) * 16
    widths = [n] + [w for w in range(n - 128, 0, -128)
                    if n % w == 0 and w % 128 == 0]
    tn = next((w for w in widths
               if 2 * operands * k * w * itemsize <= WEIGHT_VMEM_BYTES),
              widths[-1])
    return tm, tn


def _visits(counts, tiles: int, tm: int):
    """The grid's visits from the rows a group: (weight block, x tile,
    output tile, first row, row past the last, first visit of its
    output tile), each int32 [tiles + G - 1]. Real visits first, in
    group order; then one visit a row tile past the last real row (it
    writes zeros); then visits that repeat the last indices and do
    nothing."""
    g = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    n = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(n)
    real = vend[-1]
    idx = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    # a visit's group: the groups whose visits all lie before it
    grp = jnp.minimum(jnp.sum(vend[None, :] <= idx[:, None], axis=1,
                              dtype=jnp.int32), g - 1)
    tile = first[grp] + idx - (vend - n)[grp]
    lo = jnp.maximum(starts[grp], tile * tm)
    hi = jnp.minimum(ends[grp], (tile + 1) * tm)
    is_real = idx < real
    last = jnp.maximum(real - 1, 0)
    last_grp = jnp.where(real > 0, grp[last], 0)
    last_tile = jnp.where(real > 0, tile[last], 0)
    used = -(-ends[-1] // tm)
    zero_tile = used + idx - real          # the spare visits' tiles
    o_tile = jnp.where(is_real, tile, jnp.minimum(zero_tile, tiles - 1))
    opens = jnp.where(
        is_real,
        tile != jnp.concatenate([tile[:1] - 1, tile[:-1]]),
        zero_tile < tiles)
    return (jnp.where(is_real, grp, last_grp),
            jnp.where(is_real, tile, last_tile), o_tile,
            jnp.where(is_real, lo, 0), jnp.where(is_real, hi, 0),
            opens.astype(jnp.int32))


def _kernel(grp_ref, xt_ref, ot_ref, lo_ref, hi_ref, opens_ref, x_ref, *refs):
    """One visit: x_ref [tm, in], one or two weight blocks [in, tn],
    o_ref [tm, tn]. Writes the visit's rows; a tile's first visit
    clears the others, a later one keeps them."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)
    lo, hi, opens = lo_ref[v], hi_ref[v], opens_ref[v] > 0
    tm, tn = o_ref.shape

    @pl.when(hi > lo)
    def _visit():
        x = x_ref[...]
        y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
        if len(w_refs) == 2:
            up = jnp.dot(x, w_refs[1][...],
                         preferred_element_type=jnp.float32)
            y = y / (1.0 + jnp.exp(-y)) * up
        rows = ot_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                         (tm, tn), 0)
        mine = (rows >= lo) & (rows < hi)
        rest = jnp.where(opens, jnp.zeros((tm, tn), o_ref.dtype), o_ref[...])
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), rest)

    @pl.when((hi <= lo) & opens)
    def _zeros():
        o_ref[...] = jnp.zeros((tm, tn), o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "name", "interpret"))
def _call(x, weights, counts, tm: int, tn: int, name: str, interpret: bool):
    m, k = x.shape
    g, _, n = weights[0].shape
    rows = -(-m // tm) * tm
    xp = x if rows == m else jnp.pad(x, ((0, rows - m), (0, 0)))
    tiles = rows // tm
    meta = _visits(counts, tiles, tm)
    x_spec = pl.BlockSpec((tm, k), lambda j, v, w, xt, *_: (xt[v], 0))
    w_spec = pl.BlockSpec((None, k, tn), lambda j, v, w, *_: (w[v], 0, j))
    o_spec = pl.BlockSpec((tm, tn), lambda j, v, w, xt, ot, *_: (ot[v], j))
    size = x.dtype.itemsize
    vmem = (2 * (tm * k + len(weights) * k * tn) * size + 2 * tm * tn * size
            + (len(weights) + 2) * tm * tn * 4)
    y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            # a column block at a time, its visits in group order
            grid=(n // tn, tiles + g - 1),
            in_specs=[x_spec] + [w_spec] * len(weights),
            out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name=name,
    )(*meta, xp, *weights)
    return y[:m]


def _kernel_product(interpret, x, weights, counts):
    tm, tn = _tiles(x.shape[0], x.shape[1], weights[0].shape[2],
                    len(weights), x.dtype.itemsize)
    name = "grouped_gate_up" if len(weights) == 2 else "grouped_product"
    return _call(x, weights, counts, tm=tm, tn=tn, name=name,
                 interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _product(interpret, x, weights, counts):
    return _kernel_product(interpret, x, weights, counts)


def _product_fwd(interpret, x, weights, counts):
    return _kernel_product(interpret, x, weights, counts), (x, weights,
                                                            counts)


def _product_bwd(interpret, res, g):
    return jax.vjp(_reference, *res)[1](g)


_product.defvjp(_product_fwd, _product_bwd)


def _dispatch(x, weights, counts, use_kernel, interpret):
    use_kernel, interpret = _resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return _reference(x, weights, counts)
    return _product(interpret, x, weights, counts)


def grouped_product(x, w, counts, use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """x [M, in] sorted by group, w [G, in, out] of x's dtype, counts
    [G] -> [M, out]: rows of group g times w[g], rows past counts.sum()
    zeros. Kernel on the TPU (the call `grouped_product`), the
    reference elsewhere."""
    return _dispatch(x, (w,), counts, use_kernel, interpret)


def gated_grouped_product(x, gate, up, counts,
                          use_kernel: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """silu(x gate[g]) * (x up[g]) for the rows of group g: x [M, d]
    sorted by group, gate and up [G, d, f] of x's dtype, counts [G] ->
    [M, f], rows past counts.sum() zeros. Kernel on the TPU (ONE call,
    `grouped_gate_up`, reading each row tile once against both
    matrices), the reference elsewhere."""
    return _dispatch(x, (gate, up), counts, use_kernel, interpret)
