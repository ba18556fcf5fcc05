"""Collective communication primitives.

Capability-equivalent of the reference's communication op-handles and raw
NCCL ops, reformulated as XLA collectives (they compile to ICI/DCN traffic):

| reference                                              | here            |
|--------------------------------------------------------|-----------------|
| AllReduceOpHandle (details/all_reduce_op_handle.cc:103) | all_reduce      |
| ReduceOpHandle (reduce_op_handle.cc:296)                | reduce_scatter  |
| BroadcastOpHandle (broadcast_op_handle.cc:114)          | broadcast       |
| allgather (collective_server "monomer" gathers)         | all_gather      |
| send/recv RPC pair (distributed_ops/send/recv)          | ppermute        |
| gen_nccl_id bootstrap (gen_nccl_id_op.cc:31)            | jax.distributed |

These are used inside `shard_map`-decorated functions; under plain pjit, XLA
derives the same collectives from shardings without explicit calls.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

AxisName = Union[str, Tuple[str, ...]]


def all_reduce(x, axis_name: AxisName, op: str = "sum"):
    """≈ ncclAllReduce (all_reduce_op_handle.cc:103)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op!r}")


def all_gather(x, axis_name: AxisName, axis: int = 0, tiled: bool = True):
    """≈ collective allgather (collective_client.h:49)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName, axis: int = 0, op: str = "sum"):
    """≈ ReduceOpHandle sharded-reduce (reduce_op_handle.cc:296); the
    building block of ZeRO gradient sharding."""
    if op not in ("sum", "mean"):
        raise ValueError(f"unknown reduce op {op!r}")
    out = lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)
    if op == "mean":
        out = out / lax.psum(1, axis_name)
    return out


def broadcast(x, axis_name: AxisName, root: int = 0):
    """≈ ncclBcast (broadcast_op_handle.cc:114): every member gets root's
    value. Implemented as a masked psum (XLA lowers to a broadcast)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def ppermute(x, axis_name: AxisName, perm: Sequence[Tuple[int, int]]):
    """≈ point-to-point send/recv pairs; the ring primitive for ring
    attention and pipeline parallelism."""
    return lax.ppermute(x, axis_name, perm)


def ring_perm(n: int, shift: int = 1) -> Tuple[Tuple[int, int], ...]:
    return tuple((i, (i + shift) % n) for i in range(n))


def axis_index(axis_name: AxisName):
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName):
    return lax.psum(1, axis_name)


def barrier(axis_name: AxisName):
    """≈ send_barrier/fetch_barrier ops: a collective that orders phases.
    On TPU a tiny psum is a full synchronization point on the axis."""
    return lax.psum(jnp.zeros((), jnp.float32), axis_name)


def shard_fn(mesh: Mesh, in_specs, out_specs,
             check_vma: bool = False) -> Callable:
    """Decorator: run fn SPMD over `mesh` with explicit per-arg layouts.

    ≈ building a per-device SSA subgraph by hand (details/) when automatic
    partitioning isn't precise enough — the escape hatch used by ring
    attention and the sharded embedding.
    """
    def deco(fn):
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)
    return deco
