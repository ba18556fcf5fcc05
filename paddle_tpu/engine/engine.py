"""ServeEngine: the online inference serve loop.

Ties the subsystem together (ENGINE.md): a refcounted `PagedKVCache`
holds KV state in block pools (prefix-shared, copy-on-write), a
`Scheduler` plans one MIXED batch per step (decode rows + prefill
chunks), and this engine compiles + executes the steps (which pick
their own greedy tokens), samples the rows at a temperature host-side,
streams tokens to per-request callbacks, and emits structured
`serve_event` JSON (utils/log.py) for observability.

Shape discipline — the one-compilation rule: continuous batching
mutates batch membership every step, which naively means a fresh XLA
compile every step. Instead every device call runs at a FIXED shape:

- EVERY step is one flat ragged launch: the step's rows — decode rows
  (a 1-token window) and prefill chunks (a budget-bounded window of
  the prompt) — are packed into a single [T] token array, T =
  round_up(chunk_budget, tile_q) + max_batch_size * tile_q, with each
  row's tokens in a tile_q-aligned segment. Per-tile metadata maps
  tiles back to rows (kernels/paged_attention.py
  `ragged_paged_attention`). Row membership, chunk boundaries and
  prefix-cache hits only change int32 operands, never the shape: ONE
  compile, ever — no more pow2 chunk buckets and no separate decode
  step. Pad positions scatter to the reserved scratch block 0
  (context_len 1, slot 0) so they can never touch a live sequence.
  Only the tile kernels see those T rows: every product, norm and
  elementwise chain runs on the step's tokens alone, at the compact
  width `product_rows` = round_up(chunk_budget, tile_q) +
  max_batch_size * spec_len (models/step_rows.py, ENGINE.md "Two
  widths").
- COW block copies run through one fixed-width compiled
  gather/scatter (`_copy_blocks`); unused lanes copy scratch block 0
  onto itself.

Padding rows cost FLOPs but rows of a batch are computed independently
by every op in the model, so a request's logits are bit-identical
whether it shares the batch or runs alone — this is what makes
continuous batching safe to verify token-for-token against sequential
decode (tests/test_engine.py), not just "close". Prefix sharing keeps
the same guarantee: a shared block's KV was computed from the same
tokens at the same positions by the same compiled chunk step, and
masked attention lanes underflow to exact zero, so reusing it is
bit-identical to recomputing it (tests/test_prefix_cache.py).

A greedy row is picked by the step itself: beside the [B, spec_len, V]
logits it returns each row's best id, that id's logit and the row's
log-sum-exp, and only those three numbers a row come to the host; the
logits stay on the chip. A step in which a row samples at a temperature
(`_needs_logits`) downloads the logits too, and that row is sampled on
the host (temperature / top-k). Stochastic sampling derives its rng stream from
(request seed, absolute position), never from batch composition, so
scheduling decisions can't change a request's output.

Two features ride that determinism with zero new compiled paths:

- SPECULATIVE DECODING (spec_k > 0, engine/draft.py): a model-free
  prompt-lookup drafter proposes up to k tokens per decode-ready
  sequence; the scheduler widens that row's window to 1 + k tokens (the
  same multi-token shape a prefill chunk uses) so the ONE compiled step
  scores all positions in a single launch. Verification accepts the
  longest draft prefix where draft[j] equals what _sample would have
  produced anyway — exact under greedy AND temperature, because a
  deterministic point-mass proposal degenerates rejection sampling to a
  token-identity test. Rejected positions roll back by simply not
  advancing the cache: the stale KV past _lens is re-reserved and
  overwritten by later appends.
- PARALLEL SAMPLING (add_request(n=...)): a finished prefill forks into
  n candidates sharing every prompt block (refcount bump + COW), each
  decoding under seed + i; candidate streams are bit-identical to solo
  runs with those seeds.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.module import Context, _CtxCore
from paddle_tpu.engine.kvtier import HostKVTier, prefix_digest
from paddle_tpu.engine.paged_cache import (CacheLayout, PagedKVCache,
                                            refuse_latent, refuse_slots)
from paddle_tpu.engine.scheduler import (RUNNING, Request, Scheduler,
                                         StepRow)
from paddle_tpu.kernels.paged_attention import (pack_kv, ragged_span,
                                                unpack_kv)
from paddle_tpu.models.step_rows import serve_step
from paddle_tpu.obs.metrics import MetricsRegistry, default_registry
from paddle_tpu.obs.tracing import RequestTracer
from paddle_tpu.profiler.profiler import annotate, gc_total_us, now_us, \
    watch_gc
from paddle_tpu.quant.int8_compute import dequantize_block, quantize_block
from paddle_tpu.utils.log import serve_event

_COPY_LANES = 8     # COW copies flushed through one fixed-shape call
_TIER_LANES = 8     # host-tier revivals flushed per fixed-shape write
# in-device KV compression: a committed block untouched this many steps
# is cold enough for the proactive quantize sweep (compress_cold)
_COMPRESS_IDLE_STEPS = 4


def _fresh_cx(variables) -> Context:
    return Context(_CtxCore(mode="apply", variables=variables, mutated={},
                            rng=None, rng_count=0, training=False))


def compile_steps(model, variables, compress: bool, serve_tp, kinds):
    """The engine's two compiled entry points, `(step, copy_blocks)`:
    the ONE ragged step for all traffic (`models/step_rows.py`
    `serve_step` over the model's `trunk` and `logits`) and the
    fixed-width COW replay. Both take the pools DONATED: the call writes
    the buffers it was handed and returns them, so a step holds one
    pool, not two, and the handles passed in are dead afterwards (the
    engine assigns the returned ones back before anything else runs).
    `variables` may be shapes; `compress` says whether the int8 pools
    ride along; `kinds` names each entry of `pools`
    (`PagedKVCache.kinds`): the block copy moves blocks of the paged and
    index ones only.

    Under tensor parallelism (`serve_tp`) the operand shardings are
    pinned so every call reuses the same executable (TP004 / the
    one-compile invariant): weights per serve_tp_rules, KV pools
    sharded over their rows' kv-heads, int32 packing operands
    replicated. Model code sees GLOBAL shapes; XLA partitions the ops,
    and the explicit islands (sharded attention, the quantized fc2
    reduce) run inside."""
    step_sh, copy_sh = {}, {}
    if serve_tp is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.parallel.sharding import serve_tp_rules
        mesh = serve_tp.mesh
        rep = NamedSharding(mesh, P())
        nl = len(model.cache_layout)
        pools_sh = [NamedSharding(mesh, P(None, None, "tp"))] * nl
        # int8 pools shard like the fp pools; the per-block scales are
        # head-independent scalars, replicated. Compression off -> empty
        # lists, a stable pytree prefix.
        step_sh = dict(
            in_shardings=(serve_tp_rules().tree_shardings(mesh, variables),
                          rep, rep, pools_sh, pools_sh if compress else [],
                          [(rep, rep)] * nl if compress else [],
                          rep, rep, rep, rep, rep, rep, rep),
            out_shardings=(rep, pools_sh))
        copy_sh = dict(in_shardings=(pools_sh, rep, rep),
                       out_shardings=pools_sh)

    @functools.partial(jax.jit, donate_argnums=(3,), **step_sh)
    def _step_fn(variables, tokens, positions, pools, qpools, qscales,
                 block_tables, context_lens, q_starts, tile_rows,
                 tile_offs, slots, last_idx):
        # ((logits, their log-sum-exp, the greedy pick, its logit),
        # pools); a model with expert layers adds the step's tokens per
        # expert, int32 [expert layers, experts] (a share of an
        # expert-parallel layer one column more: the pairs it sent
        # away, `ServedModel.expert_shards`). The three numbers a
        # row are taken here, where the rows lie, so that a greedy
        # token costs the host one subtraction and the logits need not
        # leave the device (`_pick`): the first best id, as np.argmax,
        # and the maximum in the logits' own dtype (widened exactly)
        logits, pools, *rest = serve_step(
            model, _fresh_cx(variables), tokens, positions, pools, qpools,
            qscales, block_tables, context_lens, q_starts, tile_rows,
            tile_offs, slots, last_idx, tp=serve_tp)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        top = jnp.max(logits, axis=-1).astype(jnp.float32)
        return (logits, lse, ids, top), pools, *rest

    @functools.partial(jax.jit, donate_argnums=(0,), **copy_sh)
    def _copy_blocks(pools, src, dst):
        # COW replay: dst blocks take src blocks' contents, every
        # layer; padding lanes are (0, 0) — scratch onto itself. An
        # index pool's rows go with their block
        return [pool.at[dst].set(pool[src])
                if kind in ("paged", "index") else pool
                for kind, pool in zip(kinds, pools)]

    return _step_fn, _copy_blocks


def compile_merge(serve_tp=None):
    """The small compiled call beside the step that makes its `tokens`
    operand: the packed host tokens, with the positions whose token is a
    pick of the step before taken from that step's `ids` on the device.
    `src` maps a flat position to the row of the step before, or -1 for
    a token the host packed. It runs before EVERY step, a step that
    takes nothing too (a map of -1), so the step always sees the same
    kind of operand and keeps its one cache entry."""
    sh = {}
    if serve_tp is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(serve_tp.mesh, P())
        sh = dict(in_shardings=(rep, rep, rep), out_shardings=rep)

    @functools.partial(jax.jit, **sh)
    def _merge_picks(tokens, ids, src):
        return jnp.where(src >= 0, ids[jnp.maximum(src, 0), 0], tokens)

    return _merge_picks


@dataclass
class _Flight:
    """A step that was launched and is not yet collected: its plan, what
    it asked of the device, and the handles of what it brings back."""
    step: int
    rows: List[StepRow]
    chunks: List[StepRow]
    decodes: List[StepRow]
    computed: int               # prefill tokens of the chunks
    asked: Dict[str, int]       # `_publish`'s addends
    wants: List[bool]           # rows that sample from their own logits
    # what `engine.fetch` brings to the host, on the device: each row's
    # (lse, ids, top), an expert model's tokens per expert, the logits
    # or None
    down: tuple
    row_of: Dict[int, int]      # req_id -> row, for the next step's merge
    overlapped: bool            # launched while the step before was out
    void: bool = False          # the pools were lost: no row counts
    collected: bool = False
    discarded: int = 0          # rows whose request had ended meanwhile
    drafted: int = 0
    accepted: int = 0


def compile_snapshot_moves(places, kinds, ring_blocks: int):
    """The cache's two state-snapshot copies, `(take, restore)`, each
    `_COPY_LANES` (slot, snapshot place) pairs wide (padding lanes move
    the null slot onto the scratch place, and back): `take(pools, snaps,
    slots, places)` copies the slots' state arrays and window rings into
    the snapshot pool, donated; `restore(pools, snaps, places, slots)`
    copies them back into the pools, donated. `places[i]` is where
    `snaps[i]`'s array lies in `pools`."""
    def ring(ids):     # a slot's (a place's) ring blocks; 0: scratch
        first = 1 + (ids - 1) * ring_blocks
        return jnp.where(
            ids[:, None] > 0,
            first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32), 0
        ).reshape(-1)

    def move(dst, src, dst_ids, src_ids, kind):
        if kind == "window":
            dst_ids, src_ids = ring(dst_ids), ring(src_ids)
        return dst.at[dst_ids].set(src[src_ids])

    @functools.partial(jax.jit, donate_argnums=(1,))
    def take(pools, snaps, slots, ids):
        return [move(snap, pools[at], ids, slots, kinds[at])
                for at, snap in zip(places, snaps)]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def restore(pools, snaps, ids, slots):
        pools = list(pools)
        for at, snap in zip(places, snaps):
            pools[at] = move(pools[at], snap, slots, ids, kinds[at])
        return pools

    return take, restore


def _sample(logits: np.ndarray, req: Request, pos: int
            ) -> "tuple[int, Optional[float]]":
    """Host-side sampling for one row: (token, log-probability of that
    token under the sampling distribution). Greedy scores against the
    plain softmax, whose log-sum-exp the step has already taken on the
    device: its log-probability comes back None and `_pick` fills it
    in. Deterministic in (req.seed, pos): the same request
    samples the same token at the same position no matter what batch
    it rode in — which is ALSO what makes speculative verification
    exact (a draft is accepted iff it equals this function's output at
    its position) and best-of-n forks reproducible (candidate i ==
    a solo run with seed + i). The logprob accumulates into
    Request.logprob_sum, the best_of ranking signal."""
    if req.temperature <= 0.0:
        return int(np.argmax(logits)), None
    z = logits.astype(np.float64) / req.temperature
    if 0 < req.top_k < z.size:
        kth = np.partition(z, -req.top_k)[-req.top_k]
        z = np.where(z < kth, -np.inf, z)
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    rng = np.random.default_rng([req.seed & 0x7FFFFFFF, pos])
    tok = int(rng.choice(z.size, p=p))
    return tok, float(np.log(p[tok]))


def _needs_logits(req: Request) -> bool:
    """Whether a row of `req` that samples in a step needs its logits
    on the host. A greedy row does not: the step picked its token. A
    fork's siblings sample as their primary does, so a final chunk with
    `n_candidates > 1` is covered by its own temperature."""
    return req.temperature > 0.0


def _pick(logits: Optional[np.ndarray], best, top, lse, req: Request,
          pos: int) -> "tuple[int, float]":
    """One row's (token, log-probability). `logits` is None where the
    row's logits stayed on the device: the token is the step's own
    pick `best`, scored as its logit `top` less the row's log-sum-exp
    `lse`, both as the step took them (the subtraction the host made on
    the downloaded row before, on the same values). With the row's
    logits it is `_sample`'s, a greedy token scored the same way."""
    if logits is None:
        return int(best), float(top) - float(lse)
    tok, lp = _sample(logits, req, pos)
    if lp is None:
        lp = float(logits[tok]) - float(lse)
    return tok, lp


class ServeEngine:
    """Continuous-batching serve loop over a served model (one of
    `models/`, declared by `models/step_rows.py` `ServedModel`).

    add_request() enqueues; step() advances the world by one scheduler
    plan — ONE mixed batch of decode rows and prefill chunks through a
    single compiled call; run() drains the queue. Token callbacks fire
    as tokens are sampled — streaming falls out of iteration-level
    scheduling for free.

    `max_prefill_tokens` is the per-step CHUNK budget: prompts longer
    than it are admitted anyway and prefilled across several steps,
    with decode rows riding the same steps. Budgets above the model's
    usable context are clamped (a chunk can never exceed max_seq_len
    anyway); budgets < 1 are rejected. `tile_q` is the ragged
    packing's query-tile granularity: every row occupies a
    tile_q-aligned segment of the flat step, so each planned row
    wastes at most tile_q - 1 query slots. `enable_prefix_cache=False`
    turns off block sharing (the serve_bench baseline)."""

    def __init__(self, model, variables, max_batch_size: int = 4,
                 block_size: int = 16, num_blocks: int = 256,
                 max_seq_len: Optional[int] = None,
                 max_prefill_tokens: int = 512,
                 tile_q: int = 8,
                 enable_prefix_cache: Optional[bool] = None,
                 spec_k: int = 0,
                 drafter=None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[RequestTracer] = None,
                 host_tier_bytes: int = 0,
                 kv_tier_int8: bool = False,
                 tier_spill_dir: Optional[str] = None,
                 kv_compress_blocks: int = 0,
                 kv_promote_hits: int = 0,
                 tp_size: int = 1,
                 demote_finished: bool = False,
                 snapshot_tokens: Optional[int] = None,
                 snapshot_slots: Optional[int] = None):
        self.model = model
        # telemetry (OBSERVABILITY.md): None -> the process registry /
        # a fresh tracer. serve_bench passes a private registry per
        # engine so its A/B cells don't pollute each other.
        self.obs = registry if registry is not None else default_registry()
        self.tracer = tracer if tracer is not None else RequestTracer()
        # what each layer keeps between steps, as the model declares it
        # (`CacheLayout`, ENGINE.md "Cache kinds"). Prefix reuse over
        # slots goes by state snapshots: how far apart and how many is
        # the engine's to say, else the model's, else the layout's
        # default where the prefix cache is asked for
        if snapshot_tokens is None:
            snapshot_tokens = model.snapshot_tokens
        if snapshot_slots is None:
            snapshot_slots = model.snapshot_slots
        layout = CacheLayout(model.cache_layout, block_size, max_batch_size,
                             min(max_prefill_tokens,
                                 max_seq_len or model.max_len),
                             snapshot_tokens or None, snapshot_slots or None)
        # what one cached row is: kv_heads x [k | v], or one latent
        # entry a token ("The pool's row", kernels/paged_attention.py)
        latent = model.latent_row
        kv_heads, head_dim = model.kv_row or (1, latent[0])
        # what the cache cannot hold, said once, here
        if layout.has_slots:
            refuse_slots(max(spec_k, drafter.k if drafter else 0),
                         host_tier_bytes, kv_compress_blocks, tp_size,
                         demote_finished)
        if latent is not None:
            refuse_latent(int(tp_size), int(kv_compress_blocks))
        if enable_prefix_cache is None:     # on wherever the cache can
            enable_prefix_cache = (bool(snapshot_tokens) if layout.has_slots
                                   else True)
        # tensor-parallel serving (ENGINE.md "Tensor-parallel serving"):
        # tp_size > 1 builds a tp mesh over the first tp_size devices,
        # shards the weights (parallel.sharding.serve_tp_rules) and KV
        # pools over it, and pins the ONE compiled step's operand
        # shardings — model code runs at GLOBAL shapes throughout, so
        # tp=1 is exactly today's engine, bit for bit.
        self.tp_size = int(tp_size)
        self._serve_tp = None
        self._mesh = None
        if self.tp_size > 1:
            from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
            from paddle_tpu.parallel.serve_collective import (ServeTP,
                                                              resolve_mode)
            from paddle_tpu.parallel.sharding import (serve_tp_rules,
                                                      shard_variables)
            devs = jax.devices()
            if len(devs) < self.tp_size:
                raise ValueError(
                    f"tp_size={self.tp_size} needs that many devices, "
                    f"have {len(devs)} — on CPU set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=<n> before "
                    "jax initializes (serve/replica.py --tp-size does "
                    "this for you)")
            # the heads and the FFN width the model declares: KV pools
            # shard over kv-heads so GQA groups stay device-local
            meta = model.serve_metadata()
            for name in ("num_heads", "num_kv_heads", "ffn_dim"):
                if meta.get(name) is None or meta[name] % self.tp_size:
                    raise ValueError(
                        f"{name}={meta.get(name)} not divisible by "
                        f"tp_size={self.tp_size}")
            self._mesh = make_mesh(MeshConfig(tp=self.tp_size),
                                   devices=devs[:self.tp_size])
            self._serve_tp = ServeTP(self._mesh, self.tp_size,
                                     mode=resolve_mode())
            variables = shard_variables(self._mesh, variables,
                                        serve_tp_rules())
        else:
            # weights live on the device: from_saved_model hands over
            # the checkpoint's host arrays, and a host operand would be
            # uploaded again by every call of the compiled step
            variables = jax.device_put(variables)
        self.variables = variables
        self.max_seq_len = min(max_seq_len or model.max_len, model.max_len)
        self.max_batch_size = max_batch_size
        if max_prefill_tokens < 1:
            raise ValueError(
                f"max_prefill_tokens {max_prefill_tokens} < 1: the chunk "
                "budget must admit at least one prompt token per step")
        if tile_q < 1:
            raise ValueError(f"tile_q {tile_q} < 1")
        if max_prefill_tokens > self.max_seq_len:
            # a single chunk can never exceed the usable context, so a
            # larger budget only inflates the compiled step shape —
            # clamp loudly instead of silently paying for dead tiles
            serve_event("serve_config_clamp", field="max_prefill_tokens",
                        requested=max_prefill_tokens,
                        clamped_to=self.max_seq_len)
            max_prefill_tokens = self.max_seq_len
        self.tile_q = tile_q
        # speculative decoding (engine/draft.py): spec_k > 0 turns
        # decode rows into multi-token verification windows of up to
        # 1 + spec_k tokens. The ONE compiled step absorbs that by
        # sizing each row's worst-case decode segment to the rounded
        # window (spec_k = 0 reproduces the old B * tile_q exactly) and
        # gathering spec_len logit positions per row instead of 1 —
        # draft length changes are int32-operand changes, never shape
        # changes.
        if spec_k < 0:
            raise ValueError(f"spec_k {spec_k} < 0")
        if drafter is None and spec_k > 0:
            from paddle_tpu.engine.draft import NgramDrafter
            drafter = NgramDrafter(k=spec_k)
        if drafter is not None:
            # the compiled shape must fit the drafter's longest window
            spec_k = max(spec_k, drafter.k)
        self.spec_k = spec_k
        self.spec_len = spec_k + 1          # logit positions per row
        self.drafter = drafter
        # flat step sizing: every row's segment is tile-aligned, so the
        # worst case is max_batch_size rows each wasting tile_q - 1
        # slots on top of the chunk budget (decode windows grow to
        # 1 + spec_k tokens under speculation)
        self.flat_tokens = (
            -(-max_prefill_tokens // tile_q) * tile_q
            + max_batch_size * (-(-self.spec_len // tile_q) * tile_q))
        self.num_tiles = self.flat_tokens // tile_q
        # what the step's products run on: its tokens alone, in as many
        # rows as a step can hold, the chunk budget and a whole window a
        # row (models/step_rows.py reads the same from the step's shapes)
        self.product_rows = (-(-max_prefill_tokens // tile_q) * tile_q
                             + max_batch_size * self.spec_len)
        # host-RAM KV tier (engine/kvtier.py): a byte budget > 0 hangs
        # a second tier behind the pool — cached-free evictions and
        # preemptions demote block KV to host (int8-quantized when
        # kv_tier_int8), and admission revives it by DMA instead of
        # re-prefill. All tier traffic is host-side numpy plus eager
        # .at[].set() pool writes: the one-compile invariant holds.
        self.host_tier = (
            HostKVTier(host_tier_bytes, int8=kv_tier_int8,
                       registry=self.obs)
            if host_tier_bytes > 0 else None)
        # warm restart (RESILIENCE.md §fleet): a spill dir warm-starts
        # the tier from the previous process's drain spill — the blocks
        # are advertised on /kvprefixes again within one scrape
        # interval, so the router's fleet directory finds them. A
        # missing/partial/foreign spill loads 0 blocks and the tier
        # simply starts cold.
        # disaggregated serving (serve/kvxfer.py): a prefill-phase
        # replica demotes every finished request's committed blocks
        # into the host tier at _finish, so the prefix is advertised on
        # /kvprefixes and PULLABLE over GET /kvblocks/<digest> by the
        # decode replica that continues the stream. No-op without a
        # tier; demotion is host-side numpy (one-compile safe).
        self.demote_finished = bool(demote_finished)
        self.tier_spill_dir = tier_spill_dir
        if self.host_tier is not None and tier_spill_dir:
            loaded = self.host_tier.load_spill(tier_spill_dir)
            if loaded:
                serve_event("tier_warm_start", dir=tier_spill_dir,
                            blocks=loaded)
        # in-device KV compression (ENGINE.md "In-device KV
        # compression"): kv_compress_blocks > 0 gives the cache a
        # parallel int8 block pool cold prefix blocks quantize into at
        # ~half the bytes — the rung between device-fp and the host
        # tier. 0 reproduces today's behavior bit for bit. Compressed
        # hits are read IN PLACE by the mixed ragged step by default;
        # kv_promote_hits opts back into fp promotion (1 = always, the
        # PR-19 behavior; N > 1 = warm-up threshold).
        self.cache = PagedKVCache(
            num_layers=len(model.cache_layout), num_blocks=num_blocks,
            block_size=block_size, num_kv_heads=kv_heads,
            head_dim=head_dim, dtype=model.dtype,
            enable_prefix_cache=enable_prefix_cache, registry=self.obs,
            host_tier=self.host_tier,
            compress_blocks=kv_compress_blocks,
            promote_hits=kv_promote_hits, tp_size=self.tp_size,
            mesh=self._mesh, latent=latent, layout=layout)
        if self.host_tier is not None:
            # prime the eager kernels tier traffic dispatches — the
            # demote gather (pool[block] device_get) and the revival
            # scatter (_TIER_LANES-wide .at[].set) — with no-op writes
            # to scratch block 0, so the first real demotion/revival
            # never pays their one-time XLA compile mid-request.
            pool0 = self.cache.pools[0]
            lanes = jnp.zeros((_TIER_LANES,), jnp.int32)
            np.asarray(pool0[0])      # the demote gather's signature
            self.cache.pools[0] = pool0.at[lanes].set(
                jnp.zeros((_TIER_LANES,) + pool0.shape[1:], pool0.dtype))
        if self.cache.compress_enabled:
            # prime the compressed tier's fixed-lane eager kernels —
            # the quantize scatter (compress), the dequantize scatter
            # (promote), and the host-spill gather — with no-op scratch
            # traffic (fp block 0 <-> int8 slot 0), so the first real
            # compression/promotion never pays a mid-request compile.
            # Eager fixed-shape ops like the _TIER_LANES revival path:
            # no new jit entry points, the step's cache stays at 1.
            lanes = jnp.zeros((_TIER_LANES,), jnp.int32)
            self._quantize_lanes(0, lanes, lanes)
            self._dequantize_lanes(0, lanes, lanes)
            np.asarray(self.cache.qpools[0][0])   # the host-spill gathers
            float(self.cache.qscales[0][0][0])
        self.max_blocks_per_seq = self.cache.blocks_for(self.max_seq_len)
        # keys a span of the ragged kernel covers (kernels/
        # paged_attention.py `ragged_span`, on a chip's own pool rows):
        # what `attn_cells` counts spans by; a call's tiles x its table's
        # spans, what a grid of spans would have stepped
        pool = self.cache.pools[self.cache.kinds.index("paged")]
        span = ragged_span(block_size, pool.shape[2] // self.tp_size,
                           pool.dtype.itemsize, self.max_blocks_per_seq)
        self._cell_keys = block_size * span
        self._grid_cells = self.num_tiles * -(-self.max_blocks_per_seq
                                               // span)
        self.scheduler = Scheduler(
            self.cache, max_batch_size=max_batch_size,
            max_prefill_tokens=max_prefill_tokens,
            max_seq_len=self.max_seq_len - 1,  # leave room for >=1 new token
            drafter=self.drafter)
        self.scheduler.on_preempt = self._on_preempt
        self.scheduler.on_admit = self._on_admit
        self.scheduler.on_starved = self._make_room
        self.finished: Dict[int, Request] = {}
        # `steps` numbers the step whose tokens were last emitted,
        # `_launched` the one last sent to the device; they differ while
        # `_flight`, a step launched and not yet collected, is out
        self.steps = 0
        self._launched = 0
        self._flight: Optional[_Flight] = None
        self._plan_us = 0.0     # `engine.plan`'s opening stamp (step())
        # the collector's stamps (the profiler's; one callback a
        # process), and what of their sum the last step had seen
        watch_gc()
        self._gc_seen_us = gc_total_us()
        # tokens per (expert layer, expert) since construction
        self.expert_tokens = np.zeros(
            (model.expert_layers, model.num_experts), np.int64)
        self.prefill_tokens_computed = 0
        self.peak_occupancy = 0.0
        self.max_chunk_tokens = 0       # largest prefill step actually run
        self._register_metrics()
        self._m_tp_size.set(float(self.tp_size))
        if self._serve_tp is not None:
            # one-shot collective microprobe at construction (host-side;
            # the compiled step itself is never host-timed) — gives a
            # scrape the fp-vs-int8 wire-cost comparison up front
            from paddle_tpu.parallel.serve_collective import \
                allreduce_probe_ms
            self._allreduce_probe_ms = allreduce_probe_ms(
                self._mesh, self._serve_tp.mode,
                shape=(1, model.model_dim))
            self._m_allreduce.labels(mode=self._serve_tp.mode).observe(
                self._allreduce_probe_ms)

        self._step_fn, self._copy_blocks = compile_steps(
            model, self.variables, self.cache.compress_enabled,
            self._serve_tp, self.cache.kinds)
        self._merge = compile_merge(self._serve_tp)
        # the picks of the step before, on the device: `_merge`'s second
        # operand (zeros before the first step, whose map takes none)
        self._last_ids = jnp.zeros((max_batch_size, self.spec_len),
                                   jnp.int32)
        # what the model counts of its own sparse attention a row
        self._sparse_counts = model.sparse_counts
        self._snapshots_seen = (0, 0, 0)
        if self.cache.snapshot_every:
            self._snapshot_take, self._snapshot_restore = \
                compile_snapshot_moves(self.cache.snap_places,
                                       self.cache.kinds, layout.ring_blocks)
            # both compiled before the first request: the null slot
            # onto the scratch place, and back
            self._move_snapshots([(0, 0)], take=True)
            self._move_snapshots([(0, 0)], take=False)

    # -- construction from an exported artifact ---------------------------
    @classmethod
    def from_saved_model(cls, model_dir: str, **engine_kwargs):
        """Build model + engine from a save_inference_model() directory
        whose manifest carries the `serve` block (the model's
        `serve_metadata()`): the class its `model_type` names rebuilds
        itself from it (a manifest older than the field is a CausalLM's)."""
        import json
        import os

        from paddle_tpu.io.checkpoint import load_checkpoint
        from paddle_tpu.models.conv_moe_lm import ConvMoELM
        from paddle_tpu.models.hybrid_lm import HybridLM
        from paddle_tpu.models.latent_moe import LatentMoELM
        from paddle_tpu.models.parallel_hybrid_lm import ParallelHybridLM
        from paddle_tpu.models.sparse_linear_lm import SparseLinearLM
        from paddle_tpu.models.transformer import CausalLM
        from paddle_tpu.models.window_moe_lm import WindowMoELM

        with open(os.path.join(model_dir, "signature.json")) as f:
            sig = json.load(f)
        meta = sig.get("serve")
        if meta is None:
            raise ValueError(
                f"{model_dir} has no `serve` metadata in its manifest; "
                "re-export with save_inference_model(..., "
                "serve_meta=model.serve_metadata())")
        served = {c.model_type: c for c in (
            CausalLM, LatentMoELM, HybridLM, SparseLinearLM,
            ParallelHybridLM, ConvMoELM, WindowMoELM)}
        model = served[meta.get("model_type", CausalLM.model_type)
                       ].from_serve_metadata(meta)
        variables = load_checkpoint(os.path.join(model_dir, "params"))
        engine_kwargs.setdefault("max_seq_len", meta["max_len"])
        return cls(model, variables, **engine_kwargs)

    # -- telemetry --------------------------------------------------------
    def _register_metrics(self) -> None:
        """Metric families this engine records (OBSERVABILITY.md has
        the catalog). Families are get-or-create: engines sharing a
        registry share series. Everything here is host-side bookkeeping
        — instrumentation can never add a compile or device sync."""
        m = self.obs
        self._m_ttft = m.histogram(
            "ptpu_serve_ttft_ms", "Enqueue to first token (ms)")
        self._m_tpot = m.histogram(
            "ptpu_serve_tpot_ms",
            "Per-request mean decode latency per output token (ms)")
        self._m_queue_wait = m.histogram(
            "ptpu_serve_queue_wait_ms", "Enqueue to first admission (ms)")
        self._m_e2e = m.histogram(
            "ptpu_serve_e2e_ms", "Enqueue to finish (ms)")
        self._m_step = m.histogram(
            "ptpu_serve_step_ms", "Engine step wall time (ms)",
            labelnames=("kind",))        # kind=decode|prefill|mixed|spec
        self._m_reqs = m.counter(
            "ptpu_serve_requests_total", "Finished requests",
            labelnames=("reason",))      # reason=eos|length|cancelled
        self._m_tokens = m.counter(
            "ptpu_serve_tokens_total", "Token flow through the engine",
            labelnames=("kind",))        # kind=prefill|cached|generated
        self._m_steps = m.counter(
            "ptpu_engine_steps_total", "Compiled mixed steps executed")
        self._m_product_rows = m.counter(
            "ptpu_engine_product_rows_total",
            "Rows the steps' products ran on: the steps' real tokens "
            "(real), and the rest of each step's compact width (pad)",
            labelnames=("kind",))        # kind=real|pad
        self._m_overlapped = m.counter(
            "ptpu_engine_steps_overlapped_total",
            "Steps launched while the step before them was still "
            "uncollected (counted, like ptpu_engine_steps_total, when "
            "the step is collected)")
        self._m_discarded = m.counter(
            "ptpu_engine_rows_discarded_total",
            "Rows of a collected step thrown away because their request "
            "had ended (end-of-sequence, cancel) after the step was "
            "launched")
        self._m_logit_downloads = m.counter(
            "ptpu_engine_logit_downloads_total",
            "Steps that downloaded their logits: a row that samples at "
            "a temperature was among those that sampled. A greedy "
            "step moves each row's pick, its logit and its log-sum-exp")
        self._m_kv_read = m.counter(
            "ptpu_attn_kv_tokens_read_total",
            "Context lengths summed over the steps' real rows: the cached "
            "tokens the attention of a step has to read")
        self._m_attn_keys = m.counter(
            "ptpu_attn_keys_attended_total",
            "Keys attended (position + 1) summed over the steps' real "
            "query tokens")
        self._m_attn_cells = m.counter(
            "ptpu_attn_cells_total",
            "Spans of pool blocks the ragged kernel walks a paged layer: "
            "summed over the steps' query tiles, the spans the tile "
            "reaches (a pad tile none)")
        self._m_attn_skipped = m.counter(
            "ptpu_attn_cells_skipped_total",
            "Spans a paged layer's ragged kernel does not walk: the "
            "steps' query tiles x their tables' spans, less the walked")
        self._m_ssm_tokens = m.counter(
            "ptpu_ssm_tokens_scanned_total",
            "Real tokens through the selective scan, a state-space layer")
        self._m_kv_rows = m.counter(
            "ptpu_attn_kv_rows_total",
            "Cached rows a layer's attention has to read, by the kind of "
            "pool: the whole context (full) or clipped to the window",
            labelnames=("kind",))        # kind=full|window
        self._m_keys_kind = m.counter(
            "ptpu_attn_keys_total",
            "Keys attended by the steps' real query tokens, a layer, by "
            "the kind of pool", labelnames=("kind",))
        self._m_index_rows = m.counter(
            "ptpu_attn_index_rows_total",
            "Compressed-key rows of the index pool a sparse layer's "
            "selection scores, a kv head")
        self._m_blocks_selected = m.counter(
            "ptpu_attn_blocks_selected_total",
            "Blocks kept by the selection, summed over the steps' real "
            "query tokens past the dense length, a sparse layer and kv head")
        self._m_la_tokens = m.counter(
            "ptpu_la_tokens_total",
            "Real tokens through lightning attention, a layer")
        self._m_ring_released = m.counter(
            "ptpu_kv_window_blocks_released_total",
            "Blocks that fell wholly behind the window and were given "
            "back while their sequence lived, a window layer")
        self._m_state_slots = m.gauge(
            "ptpu_state_slots_in_use",
            "State slots (recurrent state and window ring) held by "
            "running sequences")
        self._m_moe_assign = m.counter(
            "ptpu_moe_assignments_total",
            "Real (row, choice) pairs routed to an expert, summed over "
            "the expert layers")
        self._m_moe_active = m.counter(
            "ptpu_moe_active_experts_total",
            "(layer, expert) pairs that received at least one token in "
            "a step")
        self._m_moe_pairs = m.counter(
            "ptpu_moe_pairs_total",
            "Real (row, choice) pairs over the expert layers, by where "
            "their expert is held: here, or on another chip of an "
            "expert-parallel deployment (its share of the work is not "
            "this chip's)", labelnames=("where",))    # where=held|away
        self._m_compiles = m.gauge(
            "ptpu_engine_compiles",
            "jit cache size of the unified step (the one-compile "
            "invariant: stays at 1 across arbitrary traffic)")
        self._m_occ = m.gauge(
            "ptpu_kv_occupancy", "Fraction of allocatable blocks in use")
        self._m_hit = m.gauge(
            "ptpu_kv_hit_rate",
            "Cumulative fraction of prompt tokens served from the "
            "prefix cache")
        self._m_shared = m.gauge(
            "ptpu_kv_shared_blocks", "Blocks with refcount > 1")
        self._m_compressed = m.gauge(
            "ptpu_kv_compressed_blocks",
            "Prefix blocks resident in the device int8 compressed pool")
        self._m_pool_eff = m.gauge(
            "ptpu_kv_pool_effective_bytes",
            "fp-equivalent KV bytes the device holds: the fp pool plus "
            "every compressed entry at the fp bytes it stands in for")
        self._m_queue_depth = m.gauge(
            "ptpu_sched_queue_depth", "Requests waiting for admission")
        self._m_running = m.gauge(
            "ptpu_sched_running", "Requests in the running set")
        self._m_decode_rows = m.gauge(
            "ptpu_sched_decode_rows", "Decode rows in the last step")
        self._m_prefill_rows = m.gauge(
            "ptpu_sched_prefill_rows", "Prefill chunks in the last step")
        self._m_budget_util = m.gauge(
            "ptpu_sched_chunk_budget_util",
            "Chunk tokens / max_prefill_tokens of the last "
            "prefill-bearing step")
        self._m_preempts = m.counter(
            "ptpu_sched_preemptions_total", "Recompute preemptions")
        # speculative decoding (acceptance telemetry; the step-latency
        # comparison rides ptpu_serve_step_ms{kind="spec"} vs "decode")
        self._m_spec_drafted = m.counter(
            "ptpu_spec_drafted_tokens_total",
            "Draft tokens proposed for batched verification")
        self._m_spec_accepted = m.counter(
            "ptpu_spec_accepted_tokens_total",
            "Draft tokens accepted (emitted beyond the base token)")
        self._m_spec_rejected = m.counter(
            "ptpu_spec_rejected_tokens_total",
            "Draft tokens rejected (their written KV rolled back)")
        self._m_spec_ratio = m.histogram(
            "ptpu_spec_acceptance_ratio",
            "Per-speculative-row accepted/drafted ratio")
        # tensor-parallel serving (engine tp_size knob)
        self._m_tp_size = m.gauge(
            "ptpu_serve_tp_size",
            "Tensor-parallel degree of the serving mesh (1 = "
            "single-device)")
        self._m_allreduce = m.histogram(
            "ptpu_serve_allreduce_ms",
            "Decode-MLP allreduce microprobe wall time at engine "
            "construction (ms)",
            labelnames=("mode",))        # mode=fp|int8

    def _on_admit(self, req: Request) -> None:
        """Scheduler hook: a request left the wait queue. Queue-wait is
        observed only on FIRST admission (a preemption re-admission is
        a scheduling artifact, not arrival latency). The scheduler
        admits inside `engine.plan`, whose opening stamp is this
        boundary's one clock reading."""
        now = self._plan_us / 1e6
        if req.admit_time == 0.0:
            self._m_queue_wait.observe((now - req.enqueue_time) * 1e3)
        req.admit_time = now
        self.tracer.on_admit(req.req_id, self._plan_us, self._launched + 1,
                             req.cached_tokens)
        self._set_sched_gauges()

    def _set_sched_gauges(self) -> None:
        """Refresh queue-depth/running on EVERY membership change
        (admit, finish, cancel, preempt, enqueue) — not only at step
        end. The replica router scrapes between steps; a gauge that
        lags until the next step() would route traffic on stale
        depth."""
        self._m_queue_depth.set(self.scheduler.queue_depth)
        self._m_running.set(len(self.scheduler.running))

    def metrics_text(self) -> str:
        """Prometheus exposition of this engine's registry (the
        /metrics body when no scrape server is mounted)."""
        return self.obs.render_prometheus()

    # -- intake -----------------------------------------------------------
    def add_request(self, prompt: List[int], max_new_tokens: int = 32,
                    temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                    eos_id: Optional[int] = None,
                    callback: Optional[Callable[[int], None]] = None,
                    deadline_ms: Optional[float] = None,
                    n: int = 1,
                    fork_callback: Optional[Callable] = None,
                    arrival_us: Optional[float] = None) -> Request:
        """Enqueue one completion. `arrival_us` is the front door's
        `now_us` stamp of the request's arrival: the tracer's `queued`
        span starts there (queue-wait and TTFT histograms still start
        here, at the enqueue). `n > 1` is parallel sampling: when
        this request's prefill finishes, the engine forks n - 1 sibling
        candidates off its prompt blocks (refcount bump, zero copies —
        PagedKVCache.fork_sequence), each sampling with seed + i, and
        all n decode concurrently. The returned primary is candidate 0;
        its `forks` list holds the siblings. fork_callback(i) -> token
        callback (or None for a silent candidate) wires sibling
        streams."""
        if not prompt:
            raise ValueError("empty prompt")
        if not 1 <= n <= self.max_batch_size:
            raise ValueError(
                f"n {n} not in [1, max_batch_size={self.max_batch_size}]: "
                "every candidate needs a batch slot to decode")
        if n > 1 and self.cache.layout.has_slots:
            raise ValueError(
                f"n={n} over recurrent state or a window ring: the "
                "candidates would fork one prefill, and a slot's state and "
                "ring have no copy to hand a sibling (serve it with n=1)")
        if len(prompt) + 1 > self.max_seq_len:
            raise ValueError(f"prompt len {len(prompt)} leaves no room to "
                             f"generate under max_seq_len {self.max_seq_len}")
        if self.cache.blocks_for(len(prompt) + 1) > self.cache.num_blocks - 1:
            raise ValueError(
                f"prompt len {len(prompt)} cannot fit the KV pool even "
                f"alone ({self.cache.num_blocks - 1} blocks of "
                f"{self.cache.block_size}); raise num_blocks")
        req = Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                      temperature=temperature, top_k=top_k, seed=seed,
                      eos_id=eos_id, callback=callback,
                      n_candidates=n, fork_callback=fork_callback)
        ts_us = now_us()
        req.enqueue_time = ts_us / 1e6
        if deadline_ms is not None:
            # absolute completion deadline: the scheduler preempts the
            # slackest request first, so a tight deadline shields KV
            # state under pool pressure
            req.deadline = req.enqueue_time + deadline_ms / 1e3
        self.scheduler.add(req)
        self.tracer.on_enqueue(req.req_id, ts_us, arrival_us, len(prompt))
        self._set_sched_gauges()
        serve_event("serve_admit", req_id=req.req_id,
                    prompt_len=len(prompt),
                    queue_depth=self.scheduler.queue_depth)
        return req

    def cancel(self, req: Request, reason: str = "cancelled") -> bool:
        """Tear a request down mid-flight (client disconnect): frees
        its KV blocks (shared prefix blocks drop one refcount), counts
        it under requests{reason=...}, and closes its trace. Returns
        False when it already finished. Engine-thread only, between
        calls of `step()` — the HTTP front-end marshals disconnects
        through the serve loop (serve/frontend.py). A row it has in the
        step in flight is thrown away when that step is collected."""
        if not self.scheduler.cancel(req):
            return False
        ts_us = now_us()
        req.finish_time = ts_us / 1e6
        req.finish_reason = reason
        self.finished[req.req_id] = req
        self._m_reqs.labels(reason=reason).inc()
        self._set_sched_gauges()
        self._m_occ.set(self.cache.occupancy())
        self.tracer.on_finish(req.req_id, reason, ts_us)
        serve_event("serve_cancel", req_id=req.req_id, reason=reason,
                    tokens=req.num_generated,
                    occupancy=round(self.cache.occupancy(), 4))
        return True

    def cancel_group(self, req: Request, reason: str = "cancelled") -> int:
        """Cancel a parallel-sampling group: the primary and every fork
        it spawned (a client disconnect must drop ALL n candidates'
        block references, returning shared-prompt refcounts to
        baseline). Safe for n == 1 (forks is empty) and before the fork
        happened (cancelling the still-prefilling primary means the
        siblings are simply never created). Returns how many candidates
        were actually cancelled."""
        return sum(1 for r in [req] + req.forks
                   if self.cancel(r, reason))

    # -- serve loop --------------------------------------------------------
    def step(self) -> bool:
        """Emit one step's tokens. Returns False when idle. In the
        steady state the call plans and launches step N+1 while step N
        runs on the device, then collects N (ENGINE.md "A second step in
        flight"); `engine.step` carries N, each of its seven children
        (OBSERVABILITY.md "Host spans") the step it works for; an idle
        call leaves no span."""
        return self._step(ahead=True)

    def _step(self, ahead: bool) -> bool:
        flight = self._flight
        with annotate("engine.step", step=flight.step if flight
                      else self._launched + 1) as span:
            if flight is None:
                flight = self._launch()
                if flight is None:
                    span.discard()
                    return False
            if ahead and self._runs_ahead(flight):
                # the next step behind it, if there is one to plan. The
                # plan or its flush may collect `flight` themselves
                # first (`_make_room`, `_launch`)
                self._launch()
            if not flight.collected:
                self._collect(flight)
            # every collection since the last step closed, on whichever
            # thread: each held the interpreter, so each stopped the loop
            gc_seen, self._gc_seen_us = self._gc_seen_us, gc_total_us()
            span.set(decode_rows=len(flight.decodes),
                     chunk_rows=len(flight.chunks),
                     chunk_tokens=flight.computed,
                     product_rows=self.product_rows,
                     queue_depth=self.scheduler.queue_depth,
                     used_blocks=self.cache.used_blocks,
                     gc_us=self._gc_seen_us - gc_seen,
                     overlapped=int(flight.overlapped),
                     discarded_rows=flight.discarded, **flight.asked)
        # "spec" wins over mixed/decode so the speculation-on latency
        # distribution is separable from plain decode's
        kind = ("spec" if flight.drafted
                else "mixed" if flight.chunks and flight.decodes
                else "prefill" if flight.chunks else "decode")
        self._m_step.labels(kind=kind).observe(span.dur / 1e3)
        return True

    def _runs_ahead(self, flight: _Flight) -> bool:
        """Whether the step after `flight` may be planned and launched
        before `flight` is collected: its greedy rows' next input tokens
        are on the device already. Not where the host has to see this
        step's results first: a row that samples from its own logits, a
        drafter (it proposes from the tokens so far, and acceptance
        decides the length), a request that forks when its prompt ends."""
        if self.drafter is not None:
            return False
        for row, want in zip(flight.rows, flight.wants):
            r = row.req
            if want or (row.samples and r.n_candidates > 1 and not r.forks):
                return False
        return True

    def _make_room(self) -> bool:
        """Scheduler hook (`on_starved`): the plan is about to preempt.
        A step still in flight is collected first: a victim's `prompt +
        generated` has to be whole, and a request that ends with these
        tokens gives its blocks back. True if one was."""
        flight = self._flight
        if flight is None:
            return False
        self._collect(flight)
        return True

    def _publish(self, flight: _Flight) -> None:
        """Per-step telemetry of a collected step: its `serve_event`
        lines and host-side counter and gauge writes. `asked` is what
        the step's attention and experts were asked to do (`_launch`),
        each entry the addend of the counter of its name."""
        chunks, decodes, computed = (flight.chunks, flight.decodes,
                                     flight.computed)
        drafted, accepted, asked = (flight.drafted, flight.accepted,
                                    flight.asked)
        self._m_kv_read.inc(asked["kv_tokens_read"])
        self._m_attn_keys.inc(asked["attn_keys"])
        self._m_attn_cells.inc(asked["attn_cells"])
        self._m_attn_skipped.inc(asked["attn_cells_skipped"])
        if "moe_assignments" in asked:
            self._m_moe_assign.inc(asked["moe_assignments"])
            self._m_moe_active.inc(asked["moe_active_experts"])
            self._m_moe_pairs.labels(where="held").inc(
                asked["moe_assignments"])
            self._m_moe_pairs.labels(where="away").inc(
                asked["moe_assignments_away"])
        if "ssm_tokens" in asked:
            self._m_ssm_tokens.inc(asked["ssm_tokens"])
            for kind in ("full", "window"):
                self._m_kv_rows.labels(kind=kind).inc(
                    asked["kv_rows_" + kind])
                self._m_keys_kind.labels(kind=kind).inc(
                    asked["attn_keys_" + kind])
            self._m_ring_released.inc(asked["window_blocks_released"])
        if "sparse_keys" in asked:
            self._m_kv_rows.labels(kind="sparse").inc(
                asked["sparse_rows_read"])
            self._m_keys_kind.labels(kind="sparse").inc(asked["sparse_keys"])
            self._m_index_rows.inc(asked["index_rows_read"])
            self._m_blocks_selected.inc(asked["blocks_selected"])
            self._m_la_tokens.inc(asked["la_tokens"])
        if "state_slots" in asked:
            self._m_state_slots.set(self.cache.slots_in_use)
        if chunks:
            # per-event field: a request's prefix-hit tokens are
            # attributed to the step its FIRST chunk runs
            # (start == cached_tokens) and 0 on later chunks, so summing
            # `cached` over a drain equals hit_tokens; cumulative rates
            # ride `hit_rate`/stats()
            cached = sum(w.req.cached_tokens for w in chunks
                         if w.start == w.req.cached_tokens)
            self.prefill_tokens_computed += computed
            self.max_chunk_tokens = max(self.max_chunk_tokens, computed)
            self._m_tokens.labels(kind="prefill").inc(computed)
            if cached:
                self._m_tokens.labels(kind="cached").inc(cached)
            serve_event("serve_prefill", batch=len(chunks),
                        flat_t=self.flat_tokens, tokens=computed,
                        cached=cached,
                        step=self.steps, cow=self.cache.cow_copies,
                        shared_blocks=self.cache.shared_blocks,
                        hit_rate=round(self.cache.hit_rate(), 4),
                        occupancy=round(self.cache.occupancy(), 4),
                        queue_depth=self.scheduler.queue_depth)
        if decodes:
            serve_event("serve_decode", batch=len(decodes),
                        step=self.steps, drafted=drafted,
                        accepted=accepted,
                        occupancy=round(self.cache.occupancy(), 4),
                        queue_depth=self.scheduler.queue_depth)
        self.peak_occupancy = max(self.peak_occupancy,
                                  self.cache.occupancy())
        self._m_steps.inc()
        real = sum(row.length for row in flight.rows)
        self._m_product_rows.labels(kind="real").inc(real)
        self._m_product_rows.labels(kind="pad").inc(self.product_rows - real)
        if flight.overlapped:
            self._m_overlapped.inc()
        if flight.discarded:
            self._m_discarded.inc(flight.discarded)
        self._m_compiles.set(self._step_fn._cache_size())
        self._m_occ.set(self.cache.occupancy())
        self._m_hit.set(self.cache.hit_rate())
        self._m_shared.set(self.cache.shared_blocks)
        self._m_compressed.set(float(self.cache.compressed_resident))
        self._m_pool_eff.set(float(self.cache.effective_pool_bytes()))
        self._m_queue_depth.set(self.scheduler.queue_depth)
        self._m_running.set(len(self.scheduler.running))
        self._m_decode_rows.set(len(decodes))
        self._m_prefill_rows.set(len(chunks))
        if chunks:
            self._m_budget_util.set(
                computed / self.scheduler.max_prefill_tokens)

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue, the step in flight with it; returns
        {req_id: generated token ids}."""
        while self.step():
            pass
        return {rid: self._generated_of(r)
                for rid, r in self.finished.items()}

    # -- internals ---------------------------------------------------------
    def _flush_cow(self) -> int:
        """Replay queued copy-on-write block copies on the device pools
        BEFORE the step that writes the fresh blocks, through one
        fixed-shape compiled call per _COPY_LANES batch."""
        copies = self.cache.drain_copies()
        for i in range(0, len(copies), _COPY_LANES):
            batch = copies[i:i + _COPY_LANES]
            src = np.zeros((_COPY_LANES,), np.int32)
            dst = np.zeros((_COPY_LANES,), np.int32)
            for j, (s, d) in enumerate(batch):
                src[j], dst[j] = s, d
            self.cache.pools = self._donating(
                self._copy_blocks, self.cache.pools, jnp.asarray(src),
                jnp.asarray(dst))
        return len(copies)

    def _move_snapshots(self, pairs, take: bool) -> int:
        """Make the cache's staged state-snapshot copies, `_COPY_LANES`
        a compiled call: (slot, place) pairs into the snapshot pool
        (`take`), or (place, slot) pairs back into the pools."""
        for i in range(0, len(pairs), _COPY_LANES):
            a = np.zeros((_COPY_LANES,), np.int32)
            b = np.zeros((_COPY_LANES,), np.int32)
            for j, (x, y) in enumerate(pairs[i:i + _COPY_LANES]):
                a[j], b[j] = x, y
            if take:    # (slot, place)
                self.cache.snaps = self._snapshot_take(
                    self.cache.pools, self.cache.snaps, jnp.asarray(a),
                    jnp.asarray(b))
            else:       # (place, slot)
                self.cache.pools = self._donating(
                    self._snapshot_restore, self.cache.pools,
                    self.cache.snaps, jnp.asarray(a), jnp.asarray(b))
        return len(pairs)

    def _donating(self, compiled, *operands):
        """Call a compiled function that takes the pools donated. A
        call that raises after it consumed them leaves deleted handles
        and no KV on the device: rebuild the pools and send every
        running request back to the queue to re-prefill (the preemption
        path), then let the error through — the engine behind it serves
        on (RESILIENCE.md "A failed donated step")."""
        try:
            return compiled(*operands)
        except Exception:
            if any(pool.is_deleted() for pool in self.cache.pools):
                self.cache.reset_pools()
                # a step still in flight counts for nothing: its
                # requests re-prefill and pick those tokens again
                flight = self._flight
                if flight is not None:
                    flight.void = True
                    for row in flight.rows:
                        row.req.in_flight = 0
                for req in reversed(list(self.scheduler.running)):
                    self.scheduler.preempt(req)
                serve_event("serve_pools_rebuilt", step=self.steps,
                            requeued=self.scheduler.queue_depth)
            raise

    def _flush_tier_loads(self) -> int:
        """Write staged host-tier revivals into the device pools —
        BEFORE _flush_cow (a just-revived block can be a same-plan COW
        src) and before the step reads them. Eager functional
        .at[blocks].set(...) writes in FIXED-WIDTH _TIER_LANES batches
        (unused lanes write zeros to scratch block 0, the _flush_cow
        idiom) — the shape signature never varies with revival size,
        so XLA compiles the scatter exactly once. No new jit entry
        points: the jit cache stays at 1."""
        loads = self.cache.drain_host_loads()
        for i in range(0, len(loads), _TIER_LANES):
            batch = loads[i:i + _TIER_LANES]
            idx = np.zeros((_TIER_LANES,), np.int32)
            for j, (b, _) in enumerate(batch):
                idx[j] = b       # pad lanes write zeros to scratch block 0
            blocks = jnp.asarray(idx)
            for li, pool in enumerate(self.cache.pools):
                rows = np.zeros((_TIER_LANES,) + pool.shape[1:], np.float32)
                for j, (_, layers) in enumerate(batch):
                    rows[j] = self.cache.pack_block(
                        *map(np.asarray, layers[li]))
                self.cache.pools[li] = pool.at[blocks].set(
                    jnp.asarray(rows, pool.dtype))
        return len(loads)

    def _flush_compress(self) -> int:
        """Quantize staged cold fp blocks into the int8 pool — FIRST
        among the pre-step flushes, so the quantize lanes read every
        src block's content before promotions, host loads, or COW
        copies can overwrite it. Fixed _TIER_LANES-wide eager
        gather-quantize-scatter per batch (pad lanes read fp scratch
        block 0 and write int8 scratch slot 0), primed at construction:
        no new jit entry points, the step's compile cache stays at 1."""
        jobs = self.cache.drain_compress()
        for i in range(0, len(jobs), _TIER_LANES):
            batch = jobs[i:i + _TIER_LANES]
            src = np.zeros((_TIER_LANES,), np.int32)   # fp blocks
            dst = np.zeros((_TIER_LANES,), np.int32)   # int8 slots
            for j, (b, s) in enumerate(batch):
                src[j], dst[j] = b, s
            bsrc, bdst = jnp.asarray(src), jnp.asarray(dst)
            for li in range(len(self.cache.pools)):
                self._quantize_lanes(li, bsrc, bdst)
        return len(jobs)

    def _quantize_lanes(self, li: int, blocks, slots) -> None:
        """Layer `li`: fp blocks `blocks` -> int8 slots `slots`, k and v
        each under its own per-block scale (eager, fixed width)."""
        ks, vs = self.cache.qscales[li]
        k, v = unpack_kv(self.cache.pools[li][blocks], self.cache.head_dim)
        kq8, ksc = quantize_block(k)
        vq8, vsc = quantize_block(v)
        self.cache.qpools[li] = self.cache.qpools[li].at[slots].set(
            pack_kv(kq8, vq8))
        self.cache.qscales[li] = (ks.at[slots].set(ksc),
                                  vs.at[slots].set(vsc))

    def _dequantize_lanes(self, li: int, slots, blocks) -> None:
        """Layer `li`: int8 slots `slots` -> fp blocks `blocks`, the
        inverse of _quantize_lanes."""
        pool = self.cache.pools[li]
        ks, vs = self.cache.qscales[li]
        kq, vq = unpack_kv(self.cache.qpools[li][slots],
                           self.cache.head_dim)
        self.cache.pools[li] = pool.at[blocks].set(pack_kv(
            dequantize_block(kq, ks[slots], pool.dtype),
            dequantize_block(vq, vs[slots], pool.dtype)))

    def _flush_promote(self) -> int:
        """Dequantize staged compressed-tier hits into their claimed fp
        blocks — after _flush_compress (a promotion may read a slot the
        same plan just filled) and BEFORE host loads, COW copies, and
        the step read: the same staging contract as tier revivals. Pad
        lanes read int8 scratch slot 0 and write fp scratch block 0."""
        jobs = self.cache.drain_promotes()
        for i in range(0, len(jobs), _TIER_LANES):
            batch = jobs[i:i + _TIER_LANES]
            src = np.zeros((_TIER_LANES,), np.int32)   # int8 slots
            dst = np.zeros((_TIER_LANES,), np.int32)   # fp blocks
            for j, (b, s) in enumerate(batch):
                dst[j], src[j] = b, s
            bsrc, bdst = jnp.asarray(src), jnp.asarray(dst)
            for li in range(len(self.cache.pools)):
                self._dequantize_lanes(li, bsrc, bdst)
        return len(jobs)

    @property
    def kv_direct_int8(self) -> bool:
        """Whether this replica's compiled step reads int8-resident
        blocks in place (no promote round-trip). Advertised as the
        `direct_int8` capability field on /kvprefixes so the router can
        re-price this replica's device_int8 directory rung to near
        device-fp; older replicas never send the field."""
        return self.cache.compress_enabled and self.cache.direct_read_enabled

    def kv_prefix_directory(self, limit: int = 512) -> List[dict]:
        """This replica's fleet-directory advertisement: the warm
        prefixes it can serve without re-prefill, as
        {len, digest, tier} rows (device = prefix-index entries,
        device_int8 = in-device compressed entries, host = tier
        entries). Digests are crc32 over little-endian u32 token
        ids — the same encoding the router's prefix_shard hashes.
        Engine-loop thread only (reads the unlocked prefix index); the
        serve front-end snapshots it between steps for /kvprefixes."""
        out = [{"len": ln, "digest": digest, "tier": "device"}
               for ln, digest in self.cache.prefix_rows(limit)]
        if self.cache.compress_enabled:
            out.extend({"len": len(key), "digest": prefix_digest(key),
                        "tier": "device_int8"}
                       for key in self.cache.compressed_keys(limit))
        if self.host_tier is not None:
            out.extend({"len": ln, "digest": dg, "tier": "host"}
                       for ln, dg in self.host_tier.advertised(limit))
        return out

    @staticmethod
    def _request_summary(req: Request) -> dict:
        return {
            "req_id": req.req_id,
            "state": req.state,
            "prompt_len": len(req.prompt),
            "generated": req.num_generated,
            "prefill_pos": req.prefill_pos,
            "cached_tokens": req.cached_tokens,
            "preemptions": req.preemptions,
            "deadline": None if req.deadline == float("inf")
            else req.deadline,
            "n_candidates": req.n_candidates,
        }

    def debug_state(self) -> dict:
        """Introspection snapshot for /debug and the flight recorder:
        the wait queue and running set as request summaries, block-pool
        occupancy, and the host-tier LRU summary. Engine-loop thread
        for a CONSISTENT view (the serve front-end refreshes it between
        steps); the flight recorder may also call it best-effort from a
        watchdog thread when the engine loop is wedged — reads only,
        never mutates, so a torn read is the worst case."""
        pool = {
            "num_blocks": self.cache.num_blocks,
            "block_size": self.cache.block_size,
            "free_blocks": self.cache.free_blocks,
            "used_blocks": self.cache.used_blocks,
            "shared_blocks": self.cache.shared_blocks,
            "occupancy": round(self.cache.occupancy(), 4),
        }
        out = {
            "steps": self.steps,
            "queue_depth": self.scheduler.queue_depth,
            "waiting": [self._request_summary(r)
                        for r in self.scheduler.waiting],
            "running": [self._request_summary(r)
                        for r in self.scheduler.running],
            "pool": pool,
            "cache": self.cache.stats(),
        }
        if self.host_tier is not None:
            out["host_tier"] = self.host_tier.stats()
        return out

    def _launch(self) -> Optional[_Flight]:
        """Plan the next step and send it to the device: `engine.plan`,
        `engine.flush`, `engine.pack`, `engine.dispatch`. Returns the
        step in flight, or None where there is nothing to plan.

        The plan's rows — decode rows AND prefill chunks — are packed
        into the flat ragged layout and run as ONE compiled step. Row
        i's token window [start, start+length) lands in a tile_q-aligned
        segment of the [T] arrays; per-row metadata (block table,
        chunk-end context, start position) sits at index i, and the null
        row at index max_batch_size backs pad tiles (ctx 1, scratch
        table). For a plain decode row the window is [seq_len,
        seq_len+1) of req.tokens — exactly the last generated token at
        its next-token position, which is what the old decode step fed;
        where that token is the pick of the step still in flight the
        host does not have it, and `_merge` takes it from that step's
        `ids` on the device. A SPECULATIVE row widens that window to
        [seq_len, seq_len+1+k): the base token followed by k drafted
        tokens (scheduler StepRow.draft) — the same multi-token shape a
        prefill chunk uses, so the ragged kernel scores all k+1
        positions in the one launch (each window position scatters its
        own k/v before attention reads it, exactly as chunk rows already
        do). last_idx is [B, spec_len]: speculative rows gather one
        hidden state per window position for verification; every other
        row repeats its single real index across the columns.

        What has to run ahead of the picks' values does so here, behind
        the dispatch: a decode row's length (the next plan reserves the
        slot behind it), a chunk's commit, and a boundary's state
        snapshot, whose copy has to stand on the device's queue before
        the next step moves the slot on."""
        step = self._launched + 1
        with annotate("engine.plan", step=step) as plan:
            # admissions and preemptions inside the plan are
            # stamped with its opening reading (_on_admit)
            self._plan_us = plan.ts
            rows = self.scheduler.next_batch()
            if rows is None:
                plan.discard()
                return None
            # publish the coldness clock, then sweep: blocks the
            # plan just admitted are hot (touched at step_now), so
            # only genuinely idle prefix content stages quantize
            # lanes for this step's _flush_compress
            self.cache.step_now = step
            if self.cache.compress_enabled:
                self.cache.compress_cold(_COMPRESS_IDLE_STEPS)
        # the step before, unless the plan had to collect it (_make_room)
        before = self._flight
        if before is not None and self.cache.tier_flush_pending:
            # tier loads, compress and promote lanes go out behind a
            # collected step. Whether one is pending is known only now,
            # so the plan was made with `before` still out: a request
            # that ends with its tokens (an end-of-sequence token) has
            # given its table back and loses its row, as it does where
            # the plan itself collected (`Scheduler.next_batch`)
            self._collect(before)
            before = None
            rows = [w for w in rows if w.req.state == RUNNING]
            if not rows:
                return None     # the flush waits for the next plan
        self._launched = step
        with annotate("engine.flush", step=step) as span:
            span.set(compress=self._flush_compress(),
                     promote=self._flush_promote(),
                     loads=self._flush_tier_loads(),
                     cow=self._flush_cow(),
                     restores=self._move_snapshots(
                         self.cache.drain_snapshot_restores(), take=False))
        with annotate("engine.pack", step=step):
            chunks = [w for w in rows if not w.decode]
            decodes = [w for w in rows if w.decode]
            computed = sum(w.length for w in chunks)
            t_flat, tq, nt = self.flat_tokens, self.tile_q, self.num_tiles
            b = self.max_batch_size
            mb = self.max_blocks_per_seq
            tokens = np.zeros((t_flat,), np.int32)
            # a position that takes the pick of `before`'s row: which
            src = np.full((t_flat,), -1, np.int32)
            positions = np.zeros((t_flat,), np.int32)
            # pad positions scatter into scratch block 0 (slot < bs)
            slots = np.zeros((t_flat,), np.int32)
            block_tables = np.zeros((b + 1, mb), np.int32)
            # null/pad rows: scratch, and no key: a pad tile walks nothing
            context_lens = np.zeros((b + 1,), np.int32)
            q_starts = np.zeros((b + 1,), np.int32)
            tile_rows = np.full((nt,), b, np.int32)  # pad tiles -> null row
            tile_offs = np.zeros((nt,), np.int32)
            last_idx = np.zeros((b, self.spec_len), np.int32)
            cursor = kv_read = attn_keys = cells = 0
            slotted = self.cache.layout.has_slots
            win = self.cache.layout.window
            ssm_tokens = win_rows = win_keys = released = 0
            sparse = dict.fromkeys(
                ("sparse_rows_read", "sparse_keys", "blocks_selected",
                 "index_rows_read"), 0)
            for i, row in enumerate(rows):
                r = row.req
                # the row's window of prompt + generated, without
                # building that list (a long prompt a row a step)
                split = len(r.prompt)
                own = r.prompt[row.start:row.start + row.length] \
                    + r.generated[max(row.start - split, 0):
                                  max(row.start + row.length - split, 0)]
                if row.draft:
                    # draft tokens live only in the plan, not in req.tokens
                    window = own[:1] + row.draft
                else:
                    window = own
                if window:
                    tokens[cursor:cursor + row.length] = window
                else:
                    # the token is the pick of the step in flight
                    src[cursor] = before.row_of[r.req_id]
                positions[cursor:cursor + row.length] = np.arange(
                    row.start, row.start + row.length, dtype=np.int32)
                for p in range(row.length):
                    slots[cursor + p] = self.cache.slot_of(r.req_id,
                                                           row.start + p)
                block_tables[i] = self.cache.padded_table(r.req_id, mb)
                context_lens[i] = row.start + row.length
                q_starts[i] = row.start
                kv_read += row.start + row.length
                attn_keys += (row.length * row.start
                              + row.length * (row.length + 1) // 2)
                if self._sparse_counts is not None:
                    row_sparse = self._sparse_counts(row.start, row.length)
                    for name, n in row_sparse.items():
                        sparse[name] += n
                if slotted:
                    ssm_tokens += row.length
                    end = row.start + row.length
                    if win:
                        # the rows read reach back win - 1 from the
                        # first query; a query at p sees min(p + 1, win)
                        # keys: p + 1 below the window's width, win past
                        win_rows += end - max(0, row.start - (win - 1))
                        ramp = min(end, win) - min(row.start, win)
                        win_keys += (
                            ramp * (min(row.start, win) + min(end, win) + 1)
                            // 2 + (row.length - ramp) * win)
                    released += self.cache.release_behind_window(
                        r.req_id, end)
                if row.decode:
                    # verification gathers per-position logits (plain
                    # decode rows have length 1: every column clamps to
                    # the one real index)
                    for j in range(self.spec_len):
                        last_idx[i, j] = cursor + min(j, row.length - 1)
                else:
                    last_idx[i, :] = cursor + row.length - 1
                ntiles = -(-row.length // tq)
                t0 = cursor // tq
                for k in range(ntiles):
                    tile_rows[t0 + k] = i
                    tile_offs[t0 + k] = k * tq
                    # the tile reaches the row's context, cut at its
                    # last query's causal edge: the spans up to there.
                    # A sparse model's decode row reads its kept blocks
                    # through a compacted table: its keys are its reach
                    reach = (row_sparse["sparse_keys"]
                             if self._sparse_counts is not None
                             and row.length == 1 else
                             row.start + min(row.length, (k + 1) * tq))
                    cells += -(-reach // self._cell_keys)
                cursor += ntiles * tq
            # the step's tokens fit the products' compact width
            real = sum(row.length for row in rows)
            if real > self.product_rows:
                raise RuntimeError(
                    f"a step of {real} tokens over its {self.product_rows} "
                    "product rows: the plan broke the chunk budget or the "
                    "batch")
            if slotted:
                # the rows table rides with the pools: this step's rows'
                # slots and rings
                self.cache.pools[-1] = jnp.asarray(self.cache.bind_rows(
                    [row.req.req_id for row in rows]))
            asked = {"kv_tokens_read": kv_read, "attn_keys": attn_keys,
                     "attn_cells": cells,
                     "attn_cells_skipped": self._grid_cells - cells}
            if self._sparse_counts is not None:
                # what ONE sparse layer and kv head reads after
                # selection; real tokens through a lightning layer
                asked.update(sparse, la_tokens=ssm_tokens,
                             state_slots=len(rows))
            elif slotted:
                asked.update(
                    ssm_tokens=ssm_tokens, state_slots=len(rows),
                    kv_rows_full=kv_read, attn_keys_full=attn_keys,
                    kv_rows_window=win_rows, attn_keys_window=win_keys,
                    window_blocks_released=released)
        with annotate("engine.dispatch", step=step):
            (logits, *picks), self.cache.pools, *per_expert = self._donating(
                self._step_fn,
                self.variables, self._merge(tokens, self._last_ids, src),
                positions,
                self.cache.pools, self.cache.qpools, self.cache.qscales,
                block_tables, context_lens, q_starts, tile_rows,
                tile_offs, slots, last_idx)
            self._last_ids = picks[1]
            # three numbers a row come down; the logits stay on the
            # device unless a row samples from its own on the host
            wants = [row.samples and _needs_logits(row.req) for row in rows]
            down = (*picks, per_expert, logits if any(wants) else None)
            # the copies are asked for right away, as `device_get` asks:
            # they follow their own step on the device's queue, not the
            # next one, and cost no round trip of their own once the
            # wait has returned
            for leaf in jax.tree.leaves(down):
                leaf.copy_to_host_async()
            for row in rows:
                r = row.req
                if row.decode:
                    # the step writes its input token's k/v at the
                    # reserved slot; a pick still in flight is supplied
                    # when it lands (`_emit_token`)
                    known = row.start - len(r.prompt) < len(r.generated)
                    self.cache.advance(r.req_id,
                                       r.generated[-1] if known else None)
                else:
                    end = row.start + row.length
                    self.cache.commit_prefill(r.req_id, end)
                    # a chunk that ends on a snapshot boundary of the
                    # prompt: the slot's state is that prefix's
                    self.cache.take_snapshot(r.req_id, end)
                if row.samples:
                    r.in_flight += 1
            # the copies go right behind the step that made the state,
            # before the next step or anything else walks the slots
            self._move_snapshots(self.cache.drain_snapshot_takes(), take=True)
            if self.cache.snapshot_every:
                # restored at this plan's admissions, taken behind this
                # step
                now = (self.cache.snapshots_taken,
                       self.cache.snapshots_restored,
                       self.cache.snapshot_tokens_skipped)
                asked.update(zip(
                    ("snapshots_taken", "snapshots_restored",
                     "snapshot_tokens_skipped"),
                    (a - b for a, b in zip(now, self._snapshots_seen))))
                self._snapshots_seen = now
            self._flight = _Flight(
                step=step, rows=rows, chunks=chunks, decodes=decodes,
                computed=computed, asked=asked, wants=wants, down=down,
                row_of={row.req.req_id: i
                                   for i, row in enumerate(rows)},
                overlapped=before is not None)
            self.scheduler.steps_in_flight += 1
        return self._flight

    def _collect(self, flight: _Flight) -> None:
        """Bring a launched step's picks to the host and emit its
        tokens: `engine.fetch` (with `engine.wait`), `engine.sample`,
        `engine.publish`. `steps` is this step's number from here on."""
        step = self.steps = flight.step
        flight.collected = True
        if self._flight is flight:
            self._flight = None
        self.scheduler.steps_in_flight -= 1
        rows, wants, asked = flight.rows, flight.wants, flight.asked
        with annotate("engine.fetch", step=step) as span:
            # the wait for the device's step apart from what follows it
            with annotate("engine.wait", step=step):
                jax.block_until_ready(flight.down[:3])
            fetched = jax.device_get(flight.down)
            lse, ids, top, per_expert, logits = fetched
            span.set(bytes=sum(a.nbytes for a in jax.tree.leaves(fetched)))
            if logits is not None:
                self._m_logit_downloads.inc()
            if per_expert:
                # the held experts' columns; a share's last column is the
                # pairs it sent away
                held = per_expert[0][:, :self.expert_tokens.shape[1]]
                self.expert_tokens += held
                asked.update(
                    moe_assignments=int(held.sum()),
                    moe_active_experts=int((held > 0).sum()),
                    moe_assignments_away=int(
                        per_expert[0][:, held.shape[1]:].sum()))
        with annotate("engine.sample", step=step) as span:
            # the picks reached the host: every first token and finish
            # of this step is stamped with the span's opening reading
            ts_us = span.ts
            generated = self._m_tokens.labels(kind="generated")
            emitted, finished = generated.value, len(self.finished)
            for i, row in enumerate(rows):
                r = row.req
                if flight.void or r.state != RUNNING:
                    # the request ended after this step was launched (an
                    # end-of-sequence token in the step before, a cancel
                    # from the front door), or the pools were lost:
                    # nothing is emitted, no callback runs, no length is
                    # kept. The row's write landed past the sequence's
                    # last kept token, in a block no index entry covers,
                    # and any later owner of that block or slot writes
                    # after it on the device's queue
                    flight.discarded += 1
                    continue
                r.in_flight -= row.samples
                # the row's own logits where it samples from them
                mine = logits[i] if wants[i] else None
                if row.decode:
                    row_accepted = 0
                    for j in range(len(row.draft) + 1):
                        # logits[i, j] scored window position start+j, i.e.
                        # it predicts the token at start+j+1 (where the
                        # advances below keep the cache in lockstep with j)
                        tok, lp = _pick(
                            None if mine is None else mine[j],
                            ids[i, j], top[i, j], lse[i, j], r,
                            row.start + j + 1)
                        r.logprob_sum += lp
                        self._emit_token(r, tok, ts_us)
                        if r.finish_reason or j >= len(row.draft):
                            break
                        if row.draft[j] != tok:
                            # first rejection: everything past seq_len is
                            # dead weight — rollback is simply NOT
                            # advancing; the stale k/v beyond _lens gets
                            # re-reserved and overwritten by later appends
                            break
                        # draft j verified: its k/v (scattered this launch)
                        # IS the true token's k/v, so advancing onto it
                        # lets the next column's logits be consumed too
                        self.cache.advance(r.req_id, tok)
                        row_accepted += 1
                    if row.draft:
                        flight.drafted += len(row.draft)
                        flight.accepted += row_accepted
                        self._m_spec_drafted.inc(len(row.draft))
                        self._m_spec_accepted.inc(row_accepted)
                        self._m_spec_rejected.inc(
                            len(row.draft) - row_accepted)
                        self._m_spec_ratio.observe(
                            row_accepted / len(row.draft))
                else:
                    self.tracer.on_chunk(r.req_id, row.start, row.length,
                                         ts_us, step)
                    if row.samples:     # the prompt's final chunk
                        picked = (None if mine is None else mine[0],
                                  ids[i, 0], top[i, 0], lse[i, 0])
                        if r.n_candidates > 1 and not r.forks:
                            # fork BEFORE the primary consumes the logits:
                            # each sibling samples its first token from the
                            # same final-chunk row under its own seed
                            self._fork_candidates(r, picked, ts_us)
                        tok, lp = _pick(*picked, r, len(r.prompt))
                        r.logprob_sum += lp
                        if not r.first_token_time:
                            r.first_token_time = ts_us / 1e6
                        self.tracer.on_first_token(r.req_id, ts_us, step)
                        self._emit_token(r, tok, ts_us)
            span.set(emitted=int(generated.value - emitted),
                     finished=len(self.finished) - finished,
                     host_rows=sum(wants))
        with annotate("engine.publish", step=step):
            self._publish(flight)

    def _fork_candidates(self, primary: Request, picked: tuple,
                         ts_us: float) -> None:
        """Split a finished prefill into n parallel-sampling candidates.
        Each sibling's cache sequence shares EVERY prompt block with the
        primary — fork_sequence only bumps refcounts; COW peels a
        private copy the first time a candidate writes into a shared
        block — so the prompt is prefilled once and held once no matter
        how large n is. Siblings enter the running set decode-ready
        (prefill_pos == len(prompt)) and sample their FIRST token from
        the same final-chunk row (`picked`: what `_pick` takes of it;
        a greedy fork's is the step's own pick) under seed + i: because
        _sample is deterministic in (seed, position) and the ragged
        step's rows are batch-invariant, candidate i's whole stream is
        bit-identical to a solo run submitted with that seed."""
        for i in range(1, primary.n_candidates):
            cb = (primary.fork_callback(i)
                  if primary.fork_callback is not None else None)
            sib = Request(
                prompt=list(primary.prompt),
                max_new_tokens=primary.max_new_tokens,
                temperature=primary.temperature,
                top_k=primary.top_k,
                seed=primary.seed + i,
                eos_id=primary.eos_id,
                callback=cb,
                deadline=primary.deadline,
                cand_index=i,
                parent=primary)
            sib.enqueue_time = primary.enqueue_time
            sib.admit_time = primary.admit_time
            sib.prefill_pos = len(sib.prompt)      # decode-ready
            sib.cached_tokens = len(sib.prompt)    # whole prompt shared
            sib.state = RUNNING
            self.cache.fork_sequence(primary.req_id, sib.req_id)
            self.scheduler.running.append(sib)
            primary.forks.append(sib)
            self.tracer.on_enqueue(sib.req_id, ts_us,
                                   prompt=len(sib.prompt))
            self.tracer.on_admit(sib.req_id, ts_us, self.steps,
                                 len(sib.prompt))
            tok, lp = _pick(*picked, sib, len(sib.prompt))
            sib.logprob_sum += lp
            sib.first_token_time = ts_us / 1e6
            self.tracer.on_first_token(sib.req_id, ts_us, self.steps)
            self._emit_token(sib, tok, ts_us)
        self._set_sched_gauges()
        serve_event("serve_fork", req_id=primary.req_id,
                    candidates=primary.n_candidates,
                    shared_blocks=self.cache.shared_blocks,
                    occupancy=round(self.cache.occupancy(), 4))

    def _emit_token(self, req: Request, tok: int, ts_us: float) -> None:
        req.generated.append(tok)
        self._m_tokens.labels(kind="generated").inc()
        if req.callback is not None:
            req.callback(tok)
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if (hit_eos or req.num_generated >= req.max_new_tokens
                or self.scheduler.out_of_room(req)):
            self._finish(req, "eos" if hit_eos else "length", ts_us)
        elif self.cache.trails(req.req_id):
            # the next step is out already with this pick as its input:
            # the cache's record of the sequence gets the value now
            self.cache.supply(req.req_id, tok)

    def _finish(self, req: Request, reason: str, ts_us: float) -> None:
        req.finish_time = ts_us / 1e6
        if self.demote_finished and self.host_tier is not None:
            # demote BEFORE the scheduler frees the blocks: the decode
            # replica pulls exactly the prefix this request committed
            self.cache.demote_sequence(req.req_id, reason="finish")
        self.scheduler.finish(req, reason)
        self.finished[req.req_id] = req
        ttft_ms = (req.first_token_time - req.enqueue_time) * 1e3
        decode_s = max(req.finish_time - req.first_token_time, 1e-9)
        n_gen = req.num_generated
        # per-request latency accounting: the histograms every SLO /
        # serve_bench verdict reads (TPOT only for requests that
        # actually decoded past the first token)
        self._m_ttft.observe(ttft_ms)
        self._m_e2e.observe((req.finish_time - req.enqueue_time) * 1e3)
        if n_gen > 1:
            self._m_tpot.observe(decode_s * 1e3 / (n_gen - 1))
        self._m_reqs.labels(reason=reason).inc()
        self._set_sched_gauges()
        self.tracer.on_finish(req.req_id, reason, ts_us)
        serve_event("serve_done", req_id=req.req_id, reason=reason,
                    tokens=n_gen, ttft_ms=round(ttft_ms, 3),
                    decode_tok_s=round(max(n_gen - 1, 0) / decode_s, 2),
                    cached_tokens=req.cached_tokens,
                    preemptions=req.preemptions)

    def _on_preempt(self, req: Request) -> None:
        self._m_preempts.inc()
        self._set_sched_gauges()
        self.tracer.on_preempt(req.req_id, self._plan_us)
        serve_event("serve_preempt", req_id=req.req_id,
                    kept_tokens=len(req.prompt),
                    occupancy=round(self.cache.occupancy(), 4))

    # -- observability -----------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Cumulative serve counters: prefix-cache hit rate, prefill
        tokens actually computed, COW/shared block counts, peak block
        occupancy. The serve_bench verdicts key off these."""
        out = self.cache.stats()
        out.update({
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "peak_occupancy": round(self.peak_occupancy, 4),
            "max_chunk_tokens": self.max_chunk_tokens,
            "steps": self.steps,
        })
        return out

    def reset_stats(self) -> None:
        """Zero the cumulative counters (after a warmup drain) without
        touching compiled steps or live state. Also zeroes this
        engine's metrics registry IN PLACE (families and child handles
        survive) and the request tracer — the post-warmup baseline
        serve_bench measures from. A step still in flight belongs to
        what came before: it is collected first."""
        if self._flight is not None:
            self._step(ahead=False)
        self.cache.reset_stats()
        self._snapshots_seen = (0, 0, 0)
        self.prefill_tokens_computed = 0
        self.peak_occupancy = 0.0
        self.max_chunk_tokens = 0
        self.steps = self._launched = 0
        self.obs.reset()
        self.tracer.reset()
        # static-config series survive the zeroing: the tp degree and
        # the construction-time collective microprobe describe this
        # engine, not the traffic the reset is drawing a baseline for
        # (the warmup path restores ptpu_engine_compiles the same way)
        self._m_tp_size.set(float(self.tp_size))
        if self.host_tier is not None:
            self.host_tier.republish_boot_state()
        if self._serve_tp is not None:
            self._m_allreduce.labels(mode=self._serve_tp.mode).observe(
                self._allreduce_probe_ms)

    # -- convenience --------------------------------------------------------
    def generate(self, prompts: List[List[int]], max_new_tokens: int = 32,
                 **kwargs) -> List[List[int]]:
        """Batch-submit prompts, drain, return generations in order."""
        reqs = [self.add_request(p, max_new_tokens=max_new_tokens, **kwargs)
                for p in prompts]
        self.run()
        return [self._generated_of(r) for r in reqs]

    @staticmethod
    def _generated_of(req: Request) -> List[int]:
        """All tokens generated for a request, reassembling the ones a
        preemption folded into the prompt."""
        if req.preempt_carry:
            carried = req.prompt[len(req.prompt) - req.preempt_carry:]
            return list(carried) + list(req.generated)
        return list(req.generated)
