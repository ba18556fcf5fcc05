"""Serving collectives: the decode-step allreduce, quantized.

Tensor-parallel serving splits each transformer block's MLP
column-then-row, leaving exactly ONE allreduce per block (the fc2
row-parallel reduction). At decode batch sizes that allreduce is
latency-bound, not bandwidth-bound — the payload per step is tiny, so
wire bytes ARE the cost (EQuARX, PAPERS.md). This module implements
the EQuARX-style answer: quantize the payload to int8 blockwise
(per-chunk abs-max scale), ship int8 + fp32 scales, accumulate in
fp32. A `PTPU_SERVE_ALLREDUCE=fp` escape hatch swaps in `lax.psum`
for the parity gates that need tp>1 byte-identical to tp=1.

Everything here is trace-pure: the mode is resolved HOST-SIDE once at
engine construction (resolve_mode) and closed over as a Python
constant — no env reads, no branches on traced values inside the
compiled step.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

#: blockwise-quantization granularity: one fp32 scale per CHUNK scalars.
#: 256 keeps the scale overhead at 1/64 of the fp payload while staying
#: fine-grained enough that one outlier activation cannot wash out a
#: whole row's precision.
DEFAULT_CHUNK = 256

_MODES = ("int8", "fp")


def resolve_mode(env: Optional[str] = None) -> str:
    """Host-side mode resolution (call at ENGINE CONSTRUCTION, never
    inside a traced function): PTPU_SERVE_ALLREDUCE selects the decode
    allreduce wire format. "int8" (default) is the quantized
    collective; "fp" is the exact-identity fallback the parity gates
    run under."""
    mode = (env if env is not None
            else os.environ.get("PTPU_SERVE_ALLREDUCE", "int8")).lower()
    if mode not in _MODES:
        raise ValueError(
            f"PTPU_SERVE_ALLREDUCE={mode!r} not in {_MODES}: 'int8' is the "
            "quantized collective, 'fp' the exact-identity fallback")
    return mode


class ServeTP:
    """Static tensor-parallel serving context, closed over by the one
    compiled step: the mesh, the tp degree, and the collective wire
    format. Holds no tensors — safe to capture in a jit closure."""

    __slots__ = ("mesh", "size", "mode", "chunk")

    def __init__(self, mesh: Mesh, size: int, mode: str = "int8",
                 chunk: int = DEFAULT_CHUNK):
        if mode not in _MODES:
            raise ValueError(f"mode {mode!r} not in {_MODES}")
        self.mesh = mesh
        self.size = int(size)
        self.mode = mode
        self.chunk = int(chunk)

    def __repr__(self) -> str:  # shows up in debug_state()
        return f"ServeTP(size={self.size}, mode={self.mode!r})"


def quantized_all_reduce(x, axis_name: str, chunk: int = DEFAULT_CHUNK):
    """EQuARX-style blockwise-int8 allreduce over `axis_name`.

    Per shard: flatten, pad to a chunk multiple, compute one fp32
    abs-max scale per chunk, quantize to int8. All-gather the int8
    payload + scales (wire bytes ≈ N + 4N/chunk per peer vs 2·4N for
    a ring fp allreduce), then accumulate the dequantized shards in
    fp32. Symmetric round-to-nearest with clamp at ±127; all-zero
    chunks get a floor scale so 0 stays exactly 0.
    """
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    ch = flat.reshape(-1, chunk)                          # [nc, chunk]
    scale = jnp.maximum(jnp.max(jnp.abs(ch), axis=1, keepdims=True),
                        jnp.float32(1e-30))               # [nc, 1]
    q = jnp.clip(jnp.round(ch * (127.0 / scale)),
                 -127.0, 127.0).astype(jnp.int8)
    qg = lax.all_gather(q, axis_name)                     # [tp, nc, chunk]
    sg = lax.all_gather(scale, axis_name)                 # [tp, nc, 1]
    acc = jnp.sum(qg.astype(jnp.float32) * (sg * (1.0 / 127.0)), axis=0)
    out = acc.reshape(-1)[:n].reshape(orig_shape)
    return out.astype(orig_dtype)


def serve_all_reduce(x, axis_name: str, mode: str,
                     chunk: int = DEFAULT_CHUNK):
    """The decode-MLP reduction: `mode` picks the wire format. "fp" is
    lax.psum — bit-identical to the unsharded matmul up to reduction
    order; "int8" trades documented quant error for ~1/8 wire bytes."""
    if mode == "fp":
        return lax.psum(x, axis_name)
    return quantized_all_reduce(x, axis_name, chunk=chunk)


def row_parallel_matmul(x, w, tp: ServeTP):
    """y = x @ w with the CONTRACTION dim sharded over "tp" — the
    row-parallel half of a Megatron MLP. x [..., K] (K tp-sharded on
    its last dim by the upstream column-parallel fc1), w [K, N]
    row-sharded; each shard contributes a partial [..., N] product and
    serve_all_reduce combines them. Bias must be added OUTSIDE (after
    the reduce) — adding it inside would multiply it by tp."""
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))

    def body(xs, ws):
        part = jnp.matmul(xs, ws)
        return serve_all_reduce(part, "tp", tp.mode, tp.chunk)

    y = shard_map(body, mesh=tp.mesh,
                  in_specs=(P(None, "tp"), P("tp", None)),
                  out_specs=P(None, None), check_vma=False)(x2, w)
    return y.reshape(lead + (w.shape[-1],))


def allreduce_probe_ms(mesh: Mesh, mode: str,
                       shape: Tuple[int, ...] = (64, 512),
                       dtype=jnp.float32,
                       chunk: int = DEFAULT_CHUNK) -> float:
    """One-shot wall-clock microprobe of the serving allreduce on
    `mesh` — feeds the ptpu_serve_allreduce_ms histogram at engine
    construction so a scrape can compare fp vs int8 wire cost without
    instrumenting the compiled step (host timers inside the step would
    violate trace purity). The first call is discarded as compile."""
    x = jnp.ones(shape, dtype)
    f = shard_map(lambda v: serve_all_reduce(v, "tp", mode, chunk),
                  mesh=mesh, in_specs=(P(),), out_specs=P(),
                  check_vma=False)
    f(x).block_until_ready()          # compile, untimed
    t0 = time.perf_counter()
    f(x).block_until_ready()
    return (time.perf_counter() - t0) * 1e3


def allreduce_wire_bytes(model_dim: int, mode: str,
                         tp_size: int, chunk: int = DEFAULT_CHUNK,
                         dtype_bytes: int = 4) -> int:
    """Analytic wire bytes PER TOKEN PER BLOCK for the decode MLP
    reduction (tools/paged_roofline.py's allreduce column): a ring fp
    allreduce moves 2·(tp-1)/tp · dtype_bytes·D; the int8 all-gather
    moves (tp-1)·(D + 4·D/chunk) — payload plus scales."""
    if tp_size <= 1:
        return 0
    if mode == "fp":
        return int(2 * (tp_size - 1) / tp_size * dtype_bytes * model_dim)
    return int((tp_size - 1) * (model_dim + 4 * model_dim / chunk))
