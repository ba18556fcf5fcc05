"""Where JAX's persistent compilation cache lives.

A cold 24-layer serve step plus a train step is minutes of compile, and
a chip machine keeps nothing between calls except what its caller
arranges — so the entry points that run on the chip (chip_smoke.py,
bench.py, `python -m paddle_tpu.serve.replica`, `python -m
paddle_tpu.benchmark`, the examples) call `enable_compile_cache()`
first. `import paddle_tpu` never does: a library import must not decide
where a process writes.

The directory is part of every cache key, so it never moves:
`JAX_COMPILATION_CACHE_DIR` if the caller set it (JAX reads that
variable itself; nothing is set in code then), else `.jax_cache/` at
the root of this checkout.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
