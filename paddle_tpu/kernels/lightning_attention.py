"""Ragged linear recurrences with a decay: lightning attention and the
SSD (Mamba-2) scan over the serving step's flat packing, one Pallas body
for both, and its XLA reference.

A layer of either kind keeps, a sequence and a head, one state
S [Dk, Dv] (float32) whatever the context, so the cache manager holds it
in SLOTS beside the selective scan's (engine/paged_cache.py, "Cache
kinds"; slot 0 the null slot). A step advances each row's state by that
row's real tokens:

    S_t = exp(l_t,h) . S_{t-1} + k_t,g^T v_t,h        o_t,h = q_t,g S_t

with head h reading the keys and queries of its group g = h // (H / G).
The two callers differ only in what they hand in:

- lightning attention (Lightning Attention-2, arXiv:2401.04658): a
  group a head (G = H), Dk = Dv, and one constant decay a head,
  l_t,h = log lambda_h at every token; q arrives scaled, no softmax,
  no normaliser;
- SSD, the scan of Mamba-2 (arXiv:2405.21060): keys B and queries C of
  width N shared by the H / G heads of a group, values x of width P,
  a decay that depends on the token, l_t,h = delta_t,h A_h, the value
  weighed by delta (v = delta x), and the skip D_h x_t added to the
  output: S_t = exp(delta_t A_h) S_{t-1} + delta_t x_t (x) B_t,
  y_t = C_t S_t + D_h x_t. The state is [N, P] a head, keys first, as
  lightning's is.

The packing is the ragged attention kernel's and the view of it
`selective_scan.tile_meta`'s: a tile's slot, how many of its positions
are tokens (a prefix), whether it opens a sequence (the state starts
from zeros whatever the slot held) and, here too, whether it opens its
row's segment of the step.

- `ragged_lightning_attention` and `ragged_ssd` — the entry points:
  Pallas kernel on the TPU (named by its caller,
  `ragged_lightning_attention` or `ragged_ssd`: a grid cell is one tile
  of the packing and one block of heads, the heads side by side in the
  lanes, with its slot's state of those heads brought in by the slot
  table and written back in place; a decode row's tile is one rank-1
  update and one read of the state a head, on the vector unit; a
  chunk's tile is the block form: the tile's masked, decayed q k^T
  against its v, and q against the state the tile started from,
  exp(l_i) C_i S_0), the reference elsewhere
  (`paged_attention._resolve_dispatch`).
- `ragged_recurrence_reference` — a `lax.scan` over the flat positions.

Padding positions are never walked: they leave the state bit for bit as
it was and their output is 0.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.paged_attention import _resolve_dispatch


def ragged_recurrence_reference(q, k, v, log_decay, state, slots, real,
                                fresh):
    """The recurrence a position at a time, float32: q, k [T, G, Dk],
    v [T, H, Dv], log_decay [T, H] (negative), state [S, H, Dk, Dv].
    Returns (o [T, H, Dv] float32, new state)."""
    t, h = v.shape[:2]
    per = h // q.shape[1]
    nt = slots.shape[0]
    tq = t // nt
    idx = jnp.tile(jnp.arange(tq, dtype=jnp.int32), nt)
    live = idx < jnp.repeat(real, tq)
    opens = (jnp.repeat(fresh, tq) > 0) & (idx == 0)

    def step(st, x):
        q_t, k_t, v_t, ld_t, slot, live_t, opens_t = x
        q_t, k_t = (jnp.repeat(a, per, axis=0) for a in (q_t, k_t))
        s = jnp.where(opens_t, 0.0, st[slot])
        s_new = jnp.exp(ld_t)[:, None, None] * s \
            + k_t[:, :, None] * v_t[:, None, :]
        o = jnp.sum(q_t[:, :, None] * s_new, axis=1)
        st = st.at[slot].set(jnp.where(live_t, s_new, st[slot]))
        return st, jnp.where(live_t, o, 0.0)

    state, o = jax.lax.scan(
        step, state,
        (q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
         log_decay.astype(jnp.float32), jnp.repeat(slots, tq), live, opens))
    return o, state


def _recurrence_kernel(slot_ref, real_ref, fresh_ref, first_ref, q_ref,
                       k_ref, v_ref, cum_ref, st_in_ref, o_ref, st_ref,
                       carry):
    """One tile of the packing and one block of heads: q_ref, k_ref
    [TQ, Gb * Dk], v_ref, o_ref [TQ, Hb * Dv], the heads (groups) side
    by side in the lanes as the projections leave them; cum_ref
    [1, Hb, TQ], the tile's log-decay summed up to each position, a
    head; st_in_ref / st_ref [1, Hb, Dk, Dv], the tile's slot, aliased;
    `carry` [Hb, Dk, Dv] holds the state between a row's consecutive
    tiles (the slot's block is fetched once a row and written back
    once)."""
    t = pl.program_id(1)
    tq = q_ref.shape[0]
    _, heads, dk, dv = st_ref.shape
    groups = q_ref.shape[1] // dk
    per = heads // groups
    n = real_ref[t]

    @pl.when(n == 0)
    def _pad():     # the null slot, or nothing to walk: as it was
        st_ref[...] = st_in_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _walk():
        opens = fresh_ref[t] > 0
        begins = first_ref[t] > 0
        row = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
        idx = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        at_end = jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1) == n - 1

        def start(h):
            s0 = jnp.where(begins, st_in_ref[0, h], carry[h])
            return jnp.where(opens, 0.0, s0)

        def keys(g):
            return slice(g * dk, (g + 1) * dk)

        def values(h):
            return slice(h * dv, (h + 1) * dv)

        @pl.when(n == 1)
        def _decode():      # one rank-1 update, one read of the state
            for g in range(groups):     # a group's lanes: a static slice
                kcol = jnp.transpose(k_ref[:, keys(g)])[:, 0:1]   # [Dk, 1]
                qcol = jnp.transpose(q_ref[:, keys(g)])[:, 0:1]
                for h in range(g * per, (g + 1) * per):
                    # [1, 1] -> [1, Dv] -> [Dk, Dv]: one axis at a time
                    decay = jnp.exp(jnp.broadcast_to(
                        cum_ref[0, h:h + 1, 0:1], (1, dv)))
                    s = decay * start(h) + kcol * v_ref[0:1, values(h)]
                    o0 = jnp.sum(qcol * s, axis=0, keepdims=True)  # [1, Dv]
                    o_ref[:, values(h)] = jnp.where(idx == 0, o0, 0.0)
                    st_ref[0, h] = s
                    carry[h] = s

        @pl.when(n > 1)
        def _chunk():       # the block form over the tile's n tokens
            seen = (col <= row) & (col < n)
            live = idx < n
            for g in range(groups):
                q8, k8 = q_ref[:, keys(g)], k_ref[:, keys(g)]
                qk = jax.lax.dot_general(
                    q8, k8, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)           # [TQ, TQ]
                for h in range(g * per, (g + 1) * per):
                    lrow = cum_ref[0, h:h + 1, :]                 # [1, TQ]
                    # the same sums as a column: l_i on row i
                    lcol = jnp.sum(jnp.where(row == col, lrow, 0.0), axis=1,
                                   keepdims=True)                 # [TQ, 1]
                    lend = jnp.sum(jnp.where(at_end, lrow, 0.0), axis=1,
                                   keepdims=True)                 # [1, 1]
                    s0 = start(h)
                    v8 = v_ref[:, values(h)]
                    dec = jnp.where(seen, jnp.exp(
                        jnp.where(seen, lcol - lrow, 0.0)), 0.0)
                    o = jnp.dot(qk * dec, v8,
                                preferred_element_type=jnp.float32)
                    o = o + jnp.dot(q8 * jnp.exp(lcol), s0,
                                    preferred_element_type=jnp.float32)
                    o_ref[:, values(h)] = jnp.where(live, o, 0.0)
                    kd = k8 * jnp.where(
                        live, jnp.exp(jnp.minimum(lend - lcol, 0.0)), 0.0)
                    s = jnp.exp(jnp.broadcast_to(lend, (1, dv))) * s0 \
                        + jnp.dot(
                        jnp.transpose(kd), v8,
                        preferred_element_type=jnp.float32)       # [Dk, Dv]
                    st_ref[0, h] = s
                    carry[h] = s


@functools.partial(jax.jit, static_argnames=("cells", "name", "interpret"))
def _recurrence_call(q, k, v, cum, state, slots, real, fresh, first,
                     cells: int, name: str, interpret: bool):
    t, g, dk = q.shape
    h, dv = v.shape[1:]
    nt = slots.shape[0]
    tq = t // nt
    hb, gb = h // cells, g // cells

    def flat(x):    # [T, n, D] -> [T, n * D]: a tile's heads in its lanes
        return x.astype(jnp.float32).reshape(t, -1)

    qk_tile = pl.BlockSpec((tq, gb * dk), lambda c, i, *_: (i, c))
    v_tile = pl.BlockSpec((tq, hb * dv), lambda c, i, *_: (i, c))
    cum_tile = pl.BlockSpec((1, hb, tq), lambda c, i, *_: (i, c, 0))
    slot = pl.BlockSpec((1, hb, dk, dv),
                        lambda c, i, s, *_: (s[i], c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # a block of heads at a time, its tiles in order
        grid=(cells, nt),
        in_specs=[qk_tile, qk_tile, v_tile, cum_tile, slot],
        out_specs=[v_tile, slot],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
    )
    o, state = pl.pallas_call(
        _recurrence_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 8 (four prefetched scalars, then q, k, v, cum) is the
        # state
        input_output_aliases={8: 1},
        # tiles in order: a row's tiles hand the state on
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(slots.astype(jnp.int32), real.astype(jnp.int32),
      fresh.astype(jnp.int32), first.astype(jnp.int32),
      flat(q), flat(k), flat(v),
      jnp.swapaxes(cum.reshape(nt, tq, h), 1, 2), state)
    return o.reshape(t, h, dv), state


def _recurrence(q, k, v, log_decay, state, slots, real, fresh, tile_offs,
                cells: int, name: str, use_kernel, interpret):
    """The dispatch both entry points share: q, k [T, G, Dk], v
    [T, H, Dv], log_decay [T, H]."""
    if state.dtype != jnp.float32:
        raise ValueError(f"the state stays float32, got {state.dtype}")
    use_kernel, interpret = _resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return ragged_recurrence_reference(q, k, v, log_decay, state, slots,
                                           real, fresh)
    nt = slots.shape[0]
    t, h = log_decay.shape
    # a tile's log-decay summed up to each of its positions
    cum = jnp.cumsum(log_decay.astype(jnp.float32).reshape(nt, t // nt, h),
                     axis=1)
    first = (tile_offs == 0).astype(jnp.int32)
    return _recurrence_call(q, k, v, cum, state, slots, real, fresh, first,
                            cells, name, interpret)


def ragged_lightning_attention(q, k, v, log_decay, state, slots, real, fresh,
                               tile_offs, use_kernel: Optional[bool] = None,
                               interpret: Optional[bool] = None):
    """The step's lightning attention of ONE layer. q, k, v [T, H, D]
    over the flat packing (q already scaled, q and k already normed and
    rotated); log_decay [H], log lambda_h; state [S, H, D, D] float32;
    slots, real, fresh [NT] from `selective_scan.tile_meta`; tile_offs
    [NT], a tile's offset in its row's segment (0: the slot's state is
    fetched, else the tile before hands it on). Returns (o [T, H, D]
    float32, 0 at padding; the new state: on a donated state the update
    is in place). Kernel on the TPU, reference elsewhere. One block of
    heads: every head's state in one grid cell."""
    decay = jnp.broadcast_to(log_decay.astype(jnp.float32), q.shape[:2])
    return _recurrence(q, k, v, decay, state, slots, real, fresh, tile_offs,
                       1, "ragged_lightning_attention", use_kernel,
                       interpret)


def ragged_ssd(x, delta, a, b, c, d, state, slots, real, fresh, tile_offs,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None):
    """The step's SSD scan of ONE Mamba-2 layer. x [T, H, P] (the
    convolution's output, heads of P), delta [T, H] (after the
    softplus), a [H] (A_h = -exp(A_log), negative), b, c [T, G, N],
    d [H] the skip; state [S, H, N, P] float32; the packing's operands
    as `ragged_lightning_attention`'s. Returns (y [T, H, P] float32, 0
    at padding; the new state). A grid cell holds the heads of one
    group, [H / G, N, P] of state."""
    delta = delta.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    o, state = _recurrence(c, b, delta[..., None] * xf,
                           delta * a.astype(jnp.float32)[None, :], state,
                           slots, real, fresh, tile_offs, b.shape[1],
                           "ragged_ssd", use_kernel, interpret)
    nt = slots.shape[0]
    tq = x.shape[0] // nt
    live = jnp.tile(jnp.arange(tq, dtype=jnp.int32), nt) \
        < jnp.repeat(real, tq)
    return o + jnp.where(live[:, None, None],
                         d.astype(jnp.float32)[None, :, None] * xf, 0.0), \
        state
