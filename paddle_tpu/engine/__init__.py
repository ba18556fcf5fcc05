"""Online inference engine: paged KV cache + continuous batching.

The serving-runtime counterpart of io/inference.py's static predictor
(ENGINE.md): `ServeEngine` runs a served model under iteration-level
scheduling — requests join and leave the batch every step, KV state
lives in a block-pool `PagedKVCache`, and decode attention gathers
through block tables (kernels/paged_attention.py).
"""

from paddle_tpu.engine.draft import NgramDrafter
from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.engine.kvtier import HostKVTier, prefix_digest
from paddle_tpu.engine.paged_cache import CacheExhausted, PagedKVCache
from paddle_tpu.engine.scheduler import (PrefillChunk, Request, Scheduler,
                                         StepRow)

__all__ = ["ServeEngine", "PagedKVCache",
           "CacheExhausted", "Scheduler", "Request", "StepRow",
           "PrefillChunk", "NgramDrafter", "HostKVTier", "prefix_digest"]
