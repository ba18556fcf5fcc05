"""Expert parallelism: mixture-of-experts FFN sharded over the "ep" axis.

The reference has no expert parallelism (SURVEY §2.6 "not present"); the
closest capability is its sparse parameter-prefetch path, which moves only
the rows a worker needs (parameter_prefetch.h:26) — the all_to_all dispatch
here is the same only-move-what's-needed idea applied to MoE tokens. This
module completes the advertised mesh axes (parallel/mesh.py "ep") with two
dispatch strategies over the same routed-FFN semantics:

- `moe_ffn` — masked dispatch: every device runs its local experts over
  the full token set with non-owned tokens zeroed, and the cross-device
  combine is a single psum over ICI. EXACT (no dropped tokens), program
  shape static, but costs E× the dense FFN FLOPs — the right choice for
  small E or correctness baselines.
- `moe_ffn_a2a` — GShard/Switch-style all_to_all dispatch: tokens are
  sharded over "ep"; each device packs its tokens into per-expert
  capacity-bounded buffers, one `lax.all_to_all` ships them to the expert
  owners, experts run on only their own tokens, and a reverse all_to_all
  brings outputs home. Compute per device is O(k·T·cf/E · E/n) = the
  scale-real path; tokens beyond capacity are dropped (contribute zero),
  the standard capacity-factor trade.

Both support top-k routing (k=1 = Switch, k=2 = GShard default) with
output-side prob weighting: experts are nonlinear, so inputs are masked
{0,1} and the router prob scales the *output* — this keeps masked and a2a
paths exactly equal when capacity is ample, which the tests assert.

- `load_balancing_loss` implements the standard Switch auxiliary loss.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

Pytree = Any


def init_moe_params(rng, num_experts: int, d_model: int, d_hidden: int,
                    dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Stacked expert weights (leading dim = experts; shard over "ep")."""
    k1, k2, k3 = jax.random.split(rng, 3)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts), dtype) * s1,
        "w1": jax.random.normal(
            k2, (num_experts, d_model, d_hidden), dtype) * s1,
        "w2": jax.random.normal(
            k3, (num_experts, d_hidden, d_model), dtype) * s2,
    }


def moe_partition_specs() -> Dict[str, P]:
    """PartitionSpecs for init_moe_params output (experts over "ep")."""
    return {"gate": P(), "w1": P("ep", None, None), "w2": P("ep", None, None)}


def _expert_ffn(w1, w2, x):
    return jax.nn.relu(x @ w1) @ w2


def _route(gate, x, k: int):
    """Router: top-k probs/indices + per-(token,expert) selection masks.

    Returns (probs [T,E] f32, top_p [T,k], top_i [T,k],
    sel [T,E] {0,1} chosen-mask, wgt [T,E] prob-if-chosen-else-0)."""
    e = gate.shape[-1]
    logits = x @ gate.astype(x.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = lax.top_k(probs, k)                    # [T,k]
    onehots = jax.nn.one_hot(top_i, e, dtype=probs.dtype)  # [T,k,E]
    sel = jnp.sum(onehots, axis=1)                        # [T,E] in {0,1}
    wgt = jnp.einsum("tke,tk->te", onehots, top_p)        # [T,E]
    return probs, top_p, top_i, sel, wgt


def moe_ffn(params: Dict[str, jax.Array], x: jax.Array,
            mesh: Optional[Mesh] = None, axis: str = "ep", k: int = 1
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k MoE FFN, masked dispatch. x: [tokens, D] -> (y [tokens, D], aux).

    aux carries `router_probs` [tokens, E] and `expert_index` [tokens]
    (top-1, for the load-balancing loss). With `mesh`, expert compute runs
    under shard_map with experts sharded over `axis`; without, a dense
    vmap (single-device / XLA-partitioned path). Exact: every routed token
    reaches its expert (no capacity drops), at E× dense-FFN FLOPs.
    """
    e = params["w1"].shape[0]
    probs, _, top_i, sel, wgt = _route(params["gate"], x, k)
    sel = sel.astype(x.dtype)
    wgt = wgt.astype(x.dtype)

    if mesh is not None and mesh.shape[axis] > 1:
        n = mesh.shape[axis]
        per = e // n

        def local(w1_l, w2_l, x_full, sel_full, wgt_full):
            # w1_l/w2_l: [E/ep, ...] local experts; masked compute + psum
            first = lax.axis_index(axis) * per
            y = jnp.zeros_like(x_full)
            for j in range(per):                     # static tiny loop
                m = sel_full[:, first + j][:, None]
                w = wgt_full[:, first + j][:, None]
                y = y + w * _expert_ffn(w1_l[j], w2_l[j], x_full * m)
            return lax.psum(y, axis)

        y = shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None),
                      P(), P(), P()),
            out_specs=P(), check_vma=False)(
                params["w1"].astype(x.dtype), params["w2"].astype(x.dtype),
                x, sel, wgt)
    else:
        def one_expert(w1, w2, m, w):
            return _expert_ffn(w1, w2, x * m[:, None]) * w[:, None]
        ys = jax.vmap(one_expert, in_axes=(0, 0, 1, 1))(
            params["w1"].astype(x.dtype), params["w2"].astype(x.dtype),
            sel, wgt)
        y = jnp.sum(ys, axis=0)

    return y, {"router_probs": probs, "expert_index": top_i[:, 0]}


def _route_slots(gate, x, k: int, cap: int):
    """Shared capacity-dispatch bookkeeping for the a2a and local paths:
    top-k route, slot flattening, per-expert cumsum positions, and the
    keep mask (pos < cap). One home for the capacity convention, so the
    documented exact-parity between dispatch paths cannot drift.

    Returns (probs [T,E], top_i [T,k], flat_e [T·k], flat_p [T·k],
    tok [T·k] slot→token row, pos [T·k] position within expert,
    keep [T·k] bool)."""
    e = gate.shape[-1]
    t = x.shape[0]
    probs, top_p, top_i, _, _ = _route(gate, x, k)
    flat_e = top_i.reshape(-1)
    flat_p = top_p.reshape(-1).astype(x.dtype)
    tok = jnp.repeat(jnp.arange(t), k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = pos < cap
    return probs, top_i, flat_e, flat_p, tok, pos, keep


def moe_ffn_a2a(params: Dict[str, jax.Array], x: jax.Array, mesh: Mesh,
                axis: str = "ep", k: int = 2, capacity_factor: float = 1.25
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k MoE FFN, all_to_all token dispatch (GShard-style).

    Tokens sharded over `axis` (T divisible by its size n); experts
    sharded over `axis` (E divisible by n). Per device, per expert,
    capacity C = ceil(T/n · k / E · capacity_factor): each device packs at
    most C of its tokens per expert into a [E, C, D] buffer, one tiled
    `lax.all_to_all` regroups it as [E/n, n·C, D] on the expert's owner,
    experts run on ONLY their tokens, and the reverse all_to_all +
    local combine scatter outputs back — compute and ICI bytes scale with
    routed tokens, not E× the batch. Tokens routed past capacity are
    DROPPED (output contribution zero; `dropped_fraction` in aux reports
    the rate). With ample capacity this matches `moe_ffn` exactly
    (tests assert it); under pressure it trades exactness for speed, the
    standard MoE capacity contract.
    """
    e = params["w1"].shape[0]
    d = x.shape[-1]
    n = mesh.shape[axis]
    if e % n or x.shape[0] % n:
        raise ValueError(f"experts ({e}) and tokens ({x.shape[0]}) must "
                         f"divide the '{axis}' axis size {n}")
    t_l = x.shape[0] // n
    cap = max(1, math.ceil(t_l * k / e * capacity_factor))

    def local(gate, w1_l, w2_l, x_l):
        # x_l: [T/n, D] this device's tokens
        probs, top_i, flat_e, flat_p, tok, pos, keep = _route_slots(
            gate, x_l, k, cap)
        # OOB rows (dropped tokens) fall out via scatter mode="drop"
        pos_c = jnp.where(keep, pos, cap)

        # pack: [E, C, D] send buffer
        buf = jnp.zeros((e, cap, d), x_l.dtype)
        buf = buf.at[flat_e, pos_c].add(x_l[tok], mode="drop")

        # ship tokens to expert owners: [E, C, D] -> [E/n, n·C, D]
        recv = lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                              tiled=True)
        h = jax.vmap(_expert_ffn)(w1_l.astype(x_l.dtype),
                                  w2_l.astype(x_l.dtype), recv)
        # home again: [E/n, n·C, D] -> [E, C, D]
        out_buf = lax.all_to_all(h, axis, split_axis=1, concat_axis=0,
                                 tiled=True)

        # combine: gather each kept slot's expert output, prob-weighted
        slot_out = out_buf[flat_e, pos_c] * (flat_p * keep)[:, None]
        y_l = jnp.zeros_like(x_l).at[tok].add(slot_out)
        dropped = jnp.mean(1.0 - keep.astype(jnp.float32))
        return y_l, probs, top_i[:, 0], dropped[None]

    y, probs, idx, dropped = shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None, None),
                  P(axis, None)),
        out_specs=(P(axis, None), P(axis, None), P(axis), P(axis)),
        check_vma=False)(params["gate"], params["w1"], params["w2"], x)
    return y, {"router_probs": probs, "expert_index": idx,
               "dropped_fraction": jnp.mean(dropped),
               "capacity": jnp.asarray(cap)}


def moe_ffn_local(params: Dict[str, jax.Array], x: jax.Array,
                  axis: Optional[str] = None, k: int = 2,
                  capacity_factor: float = 1.25
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k MoE FFN for callers ALREADY inside a shard_map (e.g. a
    pipeline stage body): `params["w1"]/["w2"]` are the local expert
    slices ([E/n, ...]; full stacks when axis is None), `params["gate"]`
    is replicated [D, E-total], and x [T, D] is replicated across `axis`.

    Because activations are replicated, no all_to_all is needed: every
    member routes identically, packs capacity-bounded buffers for ITS
    experts only (compute O(k·cf·T/E · E/n), the same economics as
    `moe_ffn_a2a`), and one psum over `axis` combines. Returns
    (y [T, D] post-psum, aux with router_probs/expert_index/
    load_balance/dropped_fraction) — `load_balance` is the Switch aux
    loss, computed in-body so pipeline stages can surface it as their
    stage-aux scalar.
    """
    e_local, d = params["w1"].shape[0], x.shape[-1]
    e = params["gate"].shape[-1]
    t_l = x.shape[0]
    cap = max(1, math.ceil(t_l * k / e * capacity_factor))
    # identical global slot math on every member (x is replicated)
    probs, top_i, flat_e, flat_p, tok, pos, keep = _route_slots(
        params["gate"], x, k, cap)

    first = lax.axis_index(axis) * e_local if axis is not None else 0
    mine = (flat_e >= first) & (flat_e < first + e_local)
    le = jnp.clip(flat_e - first, 0, e_local - 1)
    pos_c = jnp.where(keep & mine, pos, cap)     # OOB rows drop

    buf = jnp.zeros((e_local, cap, d), x.dtype)
    buf = buf.at[le, pos_c].add(x[tok], mode="drop")
    h = jax.vmap(_expert_ffn)(params["w1"].astype(x.dtype),
                              params["w2"].astype(x.dtype), buf)
    slot_out = h[le, jnp.minimum(pos_c, cap - 1)] \
        * (flat_p * (keep & mine))[:, None]
    y = jnp.zeros_like(x).at[tok].add(slot_out)
    if axis is not None:
        y = lax.psum(y, axis)
    aux = {"router_probs": probs, "expert_index": top_i[:, 0],
           "dropped_fraction": jnp.mean(1.0 - keep.astype(jnp.float32))}
    aux["load_balance"] = load_balancing_loss(aux)
    return y, aux


def load_balancing_loss(aux: Dict[str, jax.Array]) -> jax.Array:
    """Switch-transformer auxiliary loss: E * sum_e f_e * P_e, where f_e =
    fraction of tokens routed to e, P_e = mean router prob of e. Minimised
    (=1) at uniform routing."""
    probs = aux["router_probs"]                           # [T, E]
    e = probs.shape[-1]
    f = jnp.mean(jax.nn.one_hot(aux["expert_index"], e), axis=0)
    p = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p)
