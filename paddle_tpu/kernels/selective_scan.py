"""Ragged selective scan: a state-space layer's recurrence over the
serving step's flat packing, its XLA reference, and the causal
convolution that feeds it.

A state-space (Mamba-1) layer keeps, a sequence, a state s [N, D]
(N = d_state on the sublanes, D = d_inner on the lanes, float32) and the
last K-1 inputs of its depthwise convolution (the TAIL). Both have a
fixed size whatever the context, so the cache manager holds them in
SLOTS, one a running sequence (engine/paged_cache.py, "Cache kinds"),
slot 0 the null slot of padding. A step advances each row's state by
that row's real tokens:

    s_t = exp(delta_t (x) A) . s_{t-1} + (delta_t . u_t) (x) B_t
    y_t = s_t^T C_t + D . u_t

The packing is the ragged attention kernel's (kernels/paged_attention.py):
a row's tokens lie in order in consecutive tiles of `tile_q` positions,
its real tokens a prefix of them. What the scan needs of it is three
numbers a TILE, which `tile_meta` derives from the step's own operands:

- `slots` [NT]: the state slot of the tile's row (0: the null row);
- `real` [NT]: how many of the tile's positions are tokens (a prefix;
  0 for a pad tile; 1 for a decode row's tile);
- `fresh` [NT]: 1 where the tile opens a sequence (its first position
  is position 0): the state and the tail start from zeros, whatever the
  slot held. A slot is thereby zeroed at admission by construction.

Positions that are padding (7 of a decode tile's 8, a chunk's slack, the
null row) are never walked: they leave state and tail bit for bit as
they were, and their output is 0.

- `ragged_selective_scan` — the entry point: Pallas kernel on the TPU
  (named `ragged_selective_scan`: a grid cell holds a block of channels,
  copies every slot's state of those channels into VMEM, walks the
  step's tiles in order and its real tokens one by one, the state never
  leaving VMEM between two tokens), the reference elsewhere; the
  dispatch is `paged_attention._resolve_dispatch`'s.
- `ragged_selective_scan_reference` — a `lax.scan` over the flat
  positions.
- `ragged_causal_conv` — the convolution over the packing, reading a
  row's first K-1 lags from its slot's tail and writing the tail back.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.paged_attention import _resolve_dispatch

# channels (lanes) a grid cell holds: with 33 slots of 16 states that is
# 1 MB of state a buffer, and [16, 512] float32 is 8 vregs an operation
_CHANNEL_BLOCK = 512


def tile_meta(row_slots, context_lens, q_starts, tile_rows, tile_offs,
              tile_q: int):
    """The scan's view of the flat packing, from the step's operands:
    (slots, real, fresh, last) [NT] int32. `row_slots` [R] is the state
    slot of each metadata row, 0 for rows that carry nothing (the null
    row, the last, always). `last` marks the tile holding its row's
    last real token: the one whose tail the convolution writes back."""
    row = tile_rows.astype(jnp.int32)
    null = row_slots.shape[0] - 1
    start = q_starts[row] + tile_offs
    real = jnp.clip(context_lens[row] - start, 0, tile_q)
    real = jnp.where(row == null, 0, real).astype(jnp.int32)
    fresh = ((start == 0) & (real > 0)).astype(jnp.int32)
    last = ((real > 0) & (start + real == context_lens[row])
            ).astype(jnp.int32)
    slots = jnp.where(real > 0, row_slots[row], 0).astype(jnp.int32)
    return slots, real, fresh, last


def _per_token(per_tile, tile_q: int):
    return jnp.repeat(per_tile, tile_q)


def ragged_causal_conv(x, tails, weight, bias, slots, real, fresh, last,
                       tile_offs):
    """Depthwise causal convolution of width K over the flat packing.
    x [T, D]; tails [S, (K-1) * D], a slot's last K-1 inputs, oldest
    first, flat (whole rows gather and scatter in place); weight [K, D]
    (weight[K-1] meets the token itself), bias [D] or None (no bias).
    Returns (x conv w + bias [T, D], float32; new tails).

    A tile's tokens are preceded by the K-1 inputs before them: the
    previous tile's last ones (a row's tiles are consecutive) or, in
    the tile that opens the row's segment, the slot's tail — zeros where
    it opens the sequence. The convolution then slides over each tile's
    K-1 + TQ inputs, and the tail after a tile's last real token is the
    K-1 inputs that end there."""
    t, d = x.shape
    nt = slots.shape[0]
    tq = t // nt
    k = weight.shape[0]
    xt = x.astype(jnp.float32).reshape(nt, tq, d)
    held = tails[slots].reshape(nt, k - 1, d).astype(jnp.float32)
    held = jnp.where(fresh[:, None, None] > 0, 0.0, held)
    before = jnp.roll(xt[:, tq - (k - 1):], 1, axis=0)
    first = (tile_offs == 0)[:, None, None]
    seq = jnp.concatenate([jnp.where(first, held, before), xt], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(w[j] * seq[:, j:j + tq] for j in range(k))
    if bias is not None:
        out = bias.astype(jnp.float32) + out
    # the K-1 inputs ending at the tile's last real token: seq[real:]
    at = real[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    new = jnp.take_along_axis(seq, at[:, :, None], axis=1)
    # only a row's last real tile writes; the others' targets fall
    # outside and are dropped
    target = jnp.where(last > 0, slots, tails.shape[0])
    tails = tails.at[target].set(
        new.reshape(nt, (k - 1) * d).astype(tails.dtype), mode="drop")
    return out.reshape(t, d), tails


def ragged_selective_scan_reference(u, delta, a, b, c, d, state, slots, real,
                                    fresh):
    """The recurrence a position at a time, float32: u, delta [T, D];
    a [N, D]; b, c [T, N]; d [D]; state [S, N, D]. Returns (y [T, D] in
    u's dtype, new state)."""
    t = u.shape[0]
    nt = slots.shape[0]
    tq = t // nt
    idx = jnp.tile(jnp.arange(tq, dtype=jnp.int32), nt)
    live = idx < _per_token(real, tq)
    opens = (_per_token(fresh, tq) > 0) & (idx == 0)
    a = a.astype(jnp.float32)
    d = d.astype(jnp.float32)

    def step(st, x):
        u_t, dt_t, b_t, c_t, slot, live_t, opens_t = x
        s = jnp.where(opens_t, 0.0, st[slot])
        s_new = (jnp.exp(dt_t[None, :] * a) * s
                 + (dt_t * u_t)[None, :] * b_t[:, None])
        y = jnp.sum(s_new * c_t[:, None], axis=0) + d * u_t
        st = st.at[slot].set(jnp.where(live_t, s_new, st[slot]))
        return st, jnp.where(live_t, y, 0.0)

    state, y = jax.lax.scan(
        step, state,
        (u.astype(jnp.float32), delta.astype(jnp.float32),
         b.astype(jnp.float32), c.astype(jnp.float32),
         _per_token(slots, tq), live, opens))
    return y.astype(u.dtype), state


def _scan_kernel(slot_ref, real_ref, fresh_ref, u_ref, dt_ref, a_ref, b_ref,
                 c_ref, d_ref, st_in_ref, y_ref, st_ref, u32, dt32, y32,
                 s_scr):
    """One block of channels: u_ref, dt_ref, y_ref [T, DB]; a_ref
    [N, DB]; b_ref, c_ref [NT, N, TQ] (a tile's B and C, states on the
    sublanes, so a token's column broadcasts over the lanes); d_ref
    [1, DB]; st_in_ref / st_ref [S, N, DB], every slot, aliased."""
    nt = slot_ref.shape[0]
    tq = u_ref.shape[0] // nt
    st_ref[...] = st_in_ref[...]
    u32[...] = u_ref[...].astype(jnp.float32)
    dt32[...] = dt_ref[...].astype(jnp.float32)
    y32[...] = jnp.zeros_like(y32)
    a = a_ref[...]
    skip = d_ref[...]

    def token(s, u8, d8, bt, ct, i):
        """State after the tile's token i, and its output row."""
        d_i, u_i = d8[i:i + 1, :], u8[i:i + 1, :]
        s = jnp.exp(d_i * a) * s + (d_i * u_i) * bt[:, i:i + 1]
        y = jnp.sum(s * ct[:, i:i + 1], axis=0, keepdims=True) + skip * u_i
        return s, y

    def tile(t, carry):
        n = real_ref[t]

        @pl.when(n > 0)
        def _walk():
            slot = slot_ref[t]
            base = pl.multiple_of(t * tq, tq)
            u8 = u32[pl.ds(base, tq), :]
            d8 = dt32[pl.ds(base, tq), :]
            bt, ct = b_ref[t], c_ref[t]
            s0 = jnp.where(fresh_ref[t] > 0, 0.0, st_ref[slot])

            @pl.when(n == tq)
            def _full():        # a chunk's tile: no branch between tokens
                s = s0
                rows = []
                for i in range(tq):
                    s, y = token(s, u8, d8, bt, ct, i)
                    rows.append(y)
                y32[pl.ds(base, tq), :] = jnp.concatenate(rows, axis=0)
                st_ref[slot] = s

            @pl.when(n < tq)
            def _partial():     # a decode row's tile, a chunk's last
                s_scr[...] = s0
                for i in range(tq - 1):
                    @pl.when(i < n)
                    def _one():
                        s, y = token(s_scr[...], u8, d8, bt, ct, i)
                        s_scr[...] = s
                        y32[pl.ds(base + i, 1), :] = y
                st_ref[slot] = s_scr[...]

        return carry

    jax.lax.fori_loop(0, nt, tile, 0)
    y_ref[...] = y32[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_kernel_call(u, delta, a, b, c, d, state, slots, real, fresh,
                      interpret: bool):
    t, dn = u.shape
    nt = slots.shape[0]
    tq = t // nt
    ns, n, _ = state.shape
    db = next((x for x in (_CHANNEL_BLOCK, 256, 128) if dn % x == 0), dn)
    # a tile's B and C with the states leading: [NT, N, TQ]
    b = b.astype(jnp.float32).reshape(nt, tq, n).transpose(0, 2, 1)
    c = c.astype(jnp.float32).reshape(nt, tq, n).transpose(0, 2, 1)

    def chan(*lead):
        zeros = (0,) * len(lead)
        return pl.BlockSpec(lead + (db,), lambda j, *_: zeros + (j,))

    whole = pl.BlockSpec((nt, n, tq), lambda j, *_: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(dn // db,),
        in_specs=[chan(t), chan(t), chan(n), whole, whole, chan(1),
                  chan(ns, n)],
        out_specs=[chan(t), chan(ns, n)],
        scratch_shapes=[pltpu.VMEM((t, db), jnp.float32),
                        pltpu.VMEM((t, db), jnp.float32),
                        pltpu.VMEM((t, db), jnp.float32),
                        pltpu.VMEM((n, db), jnp.float32)],
    )
    y, state = pl.pallas_call(
        _scan_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, dn), u.dtype),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 9 (three prefetched scalars, then six) is the state
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ragged_selective_scan",
    )(slots.astype(jnp.int32), real.astype(jnp.int32),
      fresh.astype(jnp.int32), u, delta, a.astype(jnp.float32), b, c,
      d.astype(jnp.float32).reshape(1, dn), state)
    return y, state


def ragged_selective_scan(u, delta, a, b, c, d, state, slots, real, fresh,
                          use_kernel: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """The step's selective scan of ONE state-space layer. u [T, D] (the
    convolved, activated input), delta [T, D] (after its softplus), a
    [N, D] (negative), b, c [T, N], d [D], state [S, N, D] float32;
    slots, real, fresh [NT] from `tile_meta`. Returns (y [T, D] in u's
    dtype, 0 at padding; the new state: on a donated state the update is
    in place). Kernel on the TPU, reference elsewhere
    (`paged_attention._resolve_dispatch`)."""
    if state.dtype != jnp.float32:
        raise ValueError(f"the scan's state stays float32, got {state.dtype}")
    use_kernel, interpret = _resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return ragged_selective_scan_reference(u, delta, a, b, c, d, state,
                                               slots, real, fresh)
    return _scan_kernel_call(u, delta, a, b, c, d, state, slots, real, fresh,
                             interpret)
