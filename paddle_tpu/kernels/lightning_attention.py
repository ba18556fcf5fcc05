"""Ragged lightning attention: a linear-attention layer's recurrence
over the serving step's flat packing, and its XLA reference.

A lightning-attention layer (Lightning Attention-2, arXiv:2401.04658)
keeps, a sequence and a head, one state S [D, D] (float32) whatever the
context, so the cache manager holds it in SLOTS beside the selective
scan's (engine/paged_cache.py, "Cache kinds"; slot 0 the null slot). A
step advances each row's state by that row's real tokens:

    S_t = lambda_h . S_{t-1} + k_t^T v_t        o_t = q_t S_t

(q arrives scaled; no softmax and no normaliser). The packing is the
ragged attention kernel's and the view of it `selective_scan.tile_meta`'s:
a tile's slot, how many of its positions are tokens (a prefix), whether
it opens a sequence (the state starts from zeros whatever the slot held)
and, here too, whether it opens its row's segment of the step.

- `ragged_lightning_attention` — the entry point: Pallas kernel on the
  TPU (named `ragged_lightning_attention`: a grid cell is one tile of
  the packing, its heads side by side in the lanes, with its slot's
  state of every head, [H, D, D], brought in by the slot table and
  written back in place; a decode row's tile is
  one rank-1 update and one read of the state a head, on the vector
  unit; a chunk's tile is the block form: the tile's masked, decayed
  q k^T against its v, and q against the state the tile started from),
  the reference elsewhere (`paged_attention._resolve_dispatch`).
- `ragged_lightning_attention_reference` — a `lax.scan` over the flat
  positions.

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


def ragged_lightning_attention_reference(q, k, v, log_decay, state, slots,
                                         real, fresh):
    """The recurrence a position at a time, float32: q, k, v [T, H, D]
    (q scaled), log_decay [H] (log lambda_h, negative), state
    [S, H, D, D]. Returns (o [T, H, D] float32, new state)."""
    t = q.shape[0]
    nt = slots.shape[0]
    tq = t // nt
    idx = jnp.tile(jnp.arange(tq, dtype=jnp.int32), nt)
    live = idx < jnp.repeat(real, tq)
    opens = (jnp.repeat(fresh, tq) > 0) & (idx == 0)
    lam = jnp.exp(log_decay.astype(jnp.float32))[:, None, None]

    def step(st, x):
        q_t, k_t, v_t, slot, live_t, opens_t = x
        s = jnp.where(opens_t, 0.0, st[slot])
        s_new = lam * s + k_t[:, :, None] * v_t[:, None, :]
        o = jnp.sum(q_t[:, :, None] * s_new, axis=1)
        st = st.at[slot].set(jnp.where(live_t, s_new, st[slot]))
        return st, jnp.where(live_t, o, 0.0)

    state, o = jax.lax.scan(
        step, state,
        (q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
         jnp.repeat(slots, tq), live, opens))
    return o, state


def _lightning_kernel(slot_ref, real_ref, fresh_ref, first_ref, decay_ref,
                      q_ref, k_ref, v_ref, st_in_ref, o_ref, st_ref, carry):
    """One tile of the packing: q_ref, k_ref, v_ref, o_ref [TQ, H * D],
    the heads side by side in the lanes as the projections leave them;
    st_in_ref / st_ref [1, H, D, D], the tile's slot, aliased; `carry`
    [H, D, D] holds the state between a row's consecutive tiles (the
    slot's block is fetched once a row and written back once)."""
    t = pl.program_id(0)
    tq = q_ref.shape[0]
    _, heads, d, _ = st_ref.shape
    n = real_ref[t]

    @pl.when(n == 0)
    def _pad():     # the null slot, or nothing to walk: as it was
        st_ref[...] = st_in_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _walk():
        opens = fresh_ref[t] > 0
        begins = first_ref[t] > 0
        nf = n.astype(jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
        gap = (row - col).astype(jnp.float32)
        idx = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        idxf = idx.astype(jnp.float32)

        def start(h):
            s0 = jnp.where(begins, st_in_ref[0, h], carry[h])
            return jnp.where(opens, 0.0, s0)

        @pl.when(n == 1)
        def _decode():      # one rank-1 update, one read of the state
            for h in range(heads):      # a head's lanes: a static slice
                at = slice(h * d, (h + 1) * d)
                kcol = jnp.transpose(k_ref[:, at])[:, 0:1]       # [D, 1]
                qcol = jnp.transpose(q_ref[:, at])[:, 0:1]
                s = jnp.exp(decay_ref[h]) * start(h) \
                    + kcol * v_ref[0:1, at]
                o0 = jnp.sum(qcol * s, axis=0, keepdims=True)    # [1, D]
                o_ref[:, at] = jnp.where(idx == 0, o0, 0.0)
                st_ref[0, h] = s
                carry[h] = s

        @pl.when(n > 1)
        def _chunk():       # the block form over the tile's n tokens
            seen = (col <= row) & (col < n)
            for h in range(heads):
                at = slice(h * d, (h + 1) * d)
                ld = decay_ref[h]
                s0 = start(h)
                q8, k8, v8 = q_ref[:, at], k_ref[:, at], v_ref[:, at]
                dec = jnp.where(seen, jnp.exp(gap * ld), 0.0)
                a = jax.lax.dot_general(
                    q8, k8, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * dec     # [TQ, TQ]
                o = jnp.dot(a, v8, preferred_element_type=jnp.float32)
                o = o + jnp.dot(q8 * jnp.exp((idxf + 1.0) * ld), s0,
                                preferred_element_type=jnp.float32)
                o_ref[:, at] = jnp.where(idx < n, o, 0.0)
                kd = k8 * jnp.where(idx < n,
                                    jnp.exp((nf - 1.0 - idxf) * ld), 0.0)
                s = jnp.exp(nf * ld) * s0 + jnp.dot(
                    jnp.transpose(kd), v8,
                    preferred_element_type=jnp.float32)           # [D, D]
                st_ref[0, h] = s
                carry[h] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lightning_kernel_call(q, k, v, log_decay, state, slots, real, fresh,
                           first, interpret: bool):
    t, h, d = q.shape
    nt = slots.shape[0]
    tq = t // nt

    def flat(x):    # [T, H, D] -> [T, H * D]: a tile's heads in its lanes
        return x.astype(jnp.float32).reshape(t, h * d)

    tile = pl.BlockSpec((tq, h * d), lambda i, *_: (i, 0))
    slot = pl.BlockSpec((1, h, d, d), lambda i, s, *_: (s[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nt,),
        in_specs=[tile, tile, tile, slot],
        out_specs=[tile, slot],
        scratch_shapes=[pltpu.VMEM((h, d, d), jnp.float32)],
    )
    o, state = pl.pallas_call(
        _lightning_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, h * d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 8 (five prefetched scalars, then q, k, v) is the state
        input_output_aliases={8: 1},
        # tiles in order: a row's tiles hand the state on
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ragged_lightning_attention",
    )(slots.astype(jnp.int32), real.astype(jnp.int32),
      fresh.astype(jnp.int32), first.astype(jnp.int32),
      log_decay.astype(jnp.float32), flat(q), flat(k), flat(v), state)
    return o.reshape(t, h, d), state


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
    is in place). Kernel on the TPU, reference elsewhere."""
    if state.dtype != jnp.float32:
        raise ValueError(f"the state stays float32, got {state.dtype}")
    use_kernel, interpret = _resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return ragged_lightning_attention_reference(
            q, k, v, log_decay, state, slots, real, fresh)
    first = (tile_offs == 0).astype(jnp.int32)
    return _lightning_kernel_call(q, k, v, log_decay, state, slots, real,
                                  fresh, first, interpret)
