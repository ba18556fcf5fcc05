"""PagedKVCache: refcounted block-pool KV storage with prefix sharing.

The HBM side of continuous batching (ENGINE.md): instead of one dense
[B, Tmax, Hkv, hd] cache per batch slot — which reserves worst-case
HBM for every request and welds batch membership to allocation — KV
state lives in ONE pool of fixed-size token blocks per layer. A
sequence owns a BLOCK TABLE (ordered list of pool block ids); growing
a sequence appends a block from the free list, finishing/evicting one
returns its blocks in O(blocks). Fragmentation is bounded at
block_size-1 wasted slots per sequence, and admission capacity is a
pure free-list check.

Prefix sharing (vLLM-style): blocks carry REFCOUNTS, and every FULL
block whose KV content is actually in the pool is registered in a
prefix index keyed by the exact token tuple of the sequence prefix it
ends (collision-free by construction — the key IS the content, not a
hash of it). `alloc_sequence` walks a new prompt block by block
through the index and reuses matching blocks instead of allocating:
a hit means those tokens' KV already exists, so the engine skips their
prefill compute AND their HBM. Because only committed-full blocks are
shareable, a shared block is write-immutable in the common case; the
one legal write into a shared block (a full-prompt hit is capped at
n-1 so the last token always recomputes for logits, landing mid-block)
triggers COPY-ON-WRITE: the writer gets a fresh private block and the
engine replays the old block's contents into it on device
(`drain_copies` -> the engine's compiled gather/scatter).

Freed blocks stay CACHED-FREE: when the last reference drops, the
block returns to the free list but keeps its prefix-index entry, so a
later request with the same prefix (the shared-system-prompt pattern)
revives it from the free list instead of recomputing — the KV is
still sitting in the pool untouched. The entry is evicted lazily, only
when `_pop_free` hands the block out for fresh content; frees append
to the right and pops take from the left, so the longest-freed cached
content is recycled first (FIFO ~ LRU here).

In-device compressed tier (ENGINE.md "In-device KV compression"): with
`compress_blocks > 0` the cache also owns a parallel int8 block pool
plus per-block k/v scales (`qpools`/`qscales`, slot 0 scratch like
block 0). Cold committed prefix blocks QUANTIZE INTO IT at ~half the
bytes — proactively while still fp-resident (compress_cold: the fp
copy and index entry stay, so fp hits remain byte-exact), and as the
first rung of the demotion ladder when the pool recycles a cached-free
block or a sequence preempts: device-fp -> device-int8 -> host tier ->
gone. A prefix hit against a compressed entry claims a fresh fp block
and stages a dequantize PROMOTION the engine flushes before the step
reads it — like a host-tier revival, except the payload never leaves
the device. Everything here is host-side bookkeeping; the actual
quantize/dequantize run as the engine's fixed-lane eager scatters
(primed at construction, jit cache stays at exactly 1), and a spilled
compressed entry ships its int8 payload + scales straight into the
host tier without a second quantization.

Pool layout: one array per layer, [num_blocks, block_size, lanes], a
token's row either each kv head's [k | v | pad] or one latent entry
(`latent=(k_dim, v_dim)`, what a latent-attention model caches). The
row's format and its functions live beside the kernel that reads it
(kernels/paged_attention.py, "The pool's row"); this module asks it for
the lanes (`pool_shape`) and keeps what is policy. Everything above the
row — allocator, prefix index, copy-on-write, eviction, the host tier's
and the transfer plane's whole-block moves (`read_block` / `pack_block`,
which carry a latent row as its two parts [value lanes | the rest]) — is
the same code for both rows. Two things cannot work on a latent row, and
the engine refuses them at its construction (`refuse_latent`): tensor
parallelism (a latent has no head to divide over the chips) and the int8
tier (its per-block k and v scales are laid over a head's [k | v]
halves).

Host/device split: this class is the HOST-side allocator + bookkeeping
(free list, refcounts, per-sequence tables/lengths/tokens, prefix
index). The device-side pools are jnp arrays held in `self.pools`; the
engine's compiled step and COW block copy take them DONATED and return
them updated in place, and the engine assigns the new handles back (a
handle the step consumed is deleted: nothing may hold one across a
step). Nothing here traces into XLA; block tables cross into jit as
plain int32 operands.

Block 0 is reserved as a scratch block: padded batch rows (the engine
pads decode batches to a fixed size for one-compilation serving) write
their garbage k/v there, so a dummy row can never corrupt a live
sequence.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.obs.metrics import MetricsRegistry, default_registry

if TYPE_CHECKING:
    from paddle_tpu.engine.kvtier import HostKVTier


class CacheExhausted(Exception):
    """No free blocks; the scheduler must evict (preempt) a sequence."""


def refuse_latent(tp_size: int, compress_blocks: int) -> None:
    """What a latent pool cannot do, said at the engine's construction."""
    if tp_size > 1:
        raise ValueError(
            f"tp_size={tp_size} over a latent KV pool: a latent row is one "
            "entry a token shared by every head, so there is no kv head "
            "to divide over the chips (serve it with tp_size=1)")
    if compress_blocks > 0:
        raise ValueError(
            f"kv_compress_blocks={compress_blocks} over a latent KV pool: "
            "the int8 tier keeps one k scale and one v scale a block, laid "
            "over each head's [k | v] halves, and a latent row has neither "
            "(serve it with kv_compress_blocks=0)")


def refuse_slots(spec_k: int, host_tier_bytes: int, compress_blocks: int,
                 tp_size: int, demote_finished: bool) -> None:
    """What a cache with per-sequence SLOTS (recurrent state, window
    rings: `CacheLayout`) cannot do yet, said at the engine's
    construction. Prefix
    reuse is no longer among them: a slot's arrays and rings are
    snapshot at block boundaries (`CacheLayout`, "State snapshots")."""
    def no(what: str, why: str, how: str):
        raise ValueError(f"{what} over recurrent state or a window ring: "
                         f"{why} (serve it with {how})")
    if spec_k > 0:
        no(f"spec_k={spec_k}",
           "a rejected draft rolls the paged rows back by not advancing, "
           "and a state that has walked the draft cannot be walked back",
           "spec_k=0")
    if host_tier_bytes > 0:
        no(f"host_tier_bytes={host_tier_bytes}",
           "the host tier demotes and revives whole paged blocks by their "
           "prefix, and a state snapshot lives on the device only: a "
           "revived prefix has no state to resume from",
           "host_tier_bytes=0")
    if compress_blocks > 0:
        no(f"kv_compress_blocks={compress_blocks}",
           "the int8 tier stands in for prefix blocks one at a time, and a "
           "hit over state lands on a snapshot's boundary with every block "
           "before it in the fp pool", "kv_compress_blocks=0")
    if tp_size > 1:
        no(f"tp_size={tp_size}",
           "a slot's state and ring are one chip's arrays: nothing divides "
           "the scan's channels or the ring's heads over a mesh yet",
           "tp_size=1")
    if demote_finished:
        no("demote_finished=True (the prefill phase of kvxfer)",
           "a decode replica that pulls the paged blocks of a prefix would "
           "still lack the state and the ring behind it",
           "the mixed phase")


class CacheLayout:
    """What each layer of a model keeps between steps, as the model
    declares it (`model.cache_layout`, one dict a layer), sized for one
    engine. Five kinds:

    - {"kind": "paged"}: a block pool under the block tables and the
      free list (every layer of a model with paged pools only).
      `"pools": n` gives the layer n such pools (a model whose kv heads
      are read through tables of their own keeps a pool a head);
      `"index": {"stride": s, "lanes": l}` adds an INDEX pool under the
      same tables, [blocks, block_size / s, l]: one row a `s` tokens
      (a sparse layer's compressed keys), shared and evicted with its
      block; `"arrays"` as the state kind's gives the layer state
      arrays besides, in slots (a layer whose attention and recurrence
      run side by side keeps both): the step is handed its pools, then
      those arrays, and its sequences hold blocks and a slot;
    - {"kind": "window", "window": W}: a pool of RINGS, one a slot, of
      `ring_blocks` blocks each: logical block b of a sequence lives in
      ring place b mod ring_blocks, so a sequence holds at most
      `ring_blocks` blocks whatever its context, and a block that falls
      wholly behind the window is given back by being written over;
    - {"kind": "state", "arrays": ((name, shape, dtype), ...)}: arrays
      of one entry a slot (the scan's state, the convolution's tail);
    - {"kind": "reads", "layer": k}: the layer reads layer k's paged
      pool and owns nothing;
    - {"kind": "none"}.

    A SLOT is what one running sequence holds of the window and state
    kinds: slot 0 is the null slot of padding, slots 1..`slots` are
    handed out at admission and taken back when the sequence is freed
    (finished, cancelled or preempted: the scheduler recomputes).
    Nothing zeroes a slot: a sequence's first step starts at position
    0, and the step starts such a row from zeros whatever the slot
    holds (kernels/selective_scan.py `tile_meta`).

    STATE SNAPSHOTS are how a prefix hit works over slots. A slot's
    arrays and rings are copied, every `snapshot_tokens` positions of a
    prompt (a whole number of blocks), into one of `snapshot_slots`
    snapshot places (`snapshot_arrays`: the same arrays with the
    snapshot places where the slots were; place 0 is scratch). The
    manager keeps them by the prefix they end, least recently used
    first out; a hit reaches the deepest boundary that still has one,
    and admission copies it into the request's slot.
    """

    def __init__(self, layers, block_size: int, slots: int,
                 chunk_tokens: int, snapshot_tokens: Optional[int] = None,
                 snapshot_slots: Optional[int] = None):
        self.layers = [dict(layer) for layer in layers]
        self.slots = int(slots)
        # where prefix reuse is on: 16 blocks apart and two places a
        # slot unless the engine or the model says otherwise
        self.snapshot_tokens = int(snapshot_tokens or 16 * block_size)
        self.snapshot_slots = int(snapshot_slots or 2 * self.slots)
        if self.snapshot_tokens % block_size:
            raise ValueError(
                f"snapshot_tokens={self.snapshot_tokens} is not a whole "
                f"number of blocks of {block_size}: a hit ends on a block "
                "boundary")
        for i, layer in enumerate(self.layers):
            if layer["kind"] not in ("paged", "window", "state", "reads",
                                     "none"):
                raise ValueError(f"layer {i}: unknown cache kind "
                                 f"{layer['kind']!r}")
            if layer.get("arrays") and layer["kind"] not in ("paged",
                                                             "state"):
                raise ValueError(f"layer {i}: a {layer['kind']} layer keeps "
                                 "no state arrays")
            if layer["kind"] == "reads" and \
                    self.layers[layer["layer"]]["kind"] != "paged":
                raise ValueError(f"layer {i} reads layer {layer['layer']}, "
                                 "which keeps no paged pool")
            stride = (layer.get("index") or {}).get("stride")
            if stride and block_size % stride:
                raise ValueError(
                    f"layer {i}: an index row a {stride} tokens does not "
                    f"divide a block of {block_size}")
        windows = [layer["window"] for layer in self.layers
                   if layer["kind"] == "window"]
        # a step's chunk of C tokens starting at p reads back to
        # p - (W - 1) and writes up to p + C - 1: that many blocks must
        # be live at once, and one more for the block a boundary splits
        self.window = max(windows, default=0)
        self.ring_blocks = (
            -(-(self.window - 1 + chunk_tokens) // block_size) + 1
            if windows else 0)
        self.has_slots = bool(windows) or any(
            layer["kind"] == "state" or layer.get("arrays")
            for layer in self.layers)

    def arrays(self, pool_shape, dtype):
        """[(kind, shape, dtype)] of the arrays the step is handed, in
        layer order, then, where the layout has slots, the rows
        table."""
        out = []
        nb, bs, lanes = pool_shape
        for layer in self.layers:
            if layer["kind"] == "paged":
                out += [("paged", pool_shape, dtype)] * layer.get("pools", 1)
                if layer.get("index"):
                    out.append(("index",
                                (nb, bs // layer["index"]["stride"],
                                 layer["index"]["lanes"]), dtype))
            if layer["kind"] in ("paged", "state"):
                out += [("state", (self.slots + 1,) + tuple(shape), dt)
                        for _, shape, dt in layer.get("arrays", ())]
            elif layer["kind"] == "window":
                out.append(("window", (1 + self.slots * self.ring_blocks,
                                       bs, lanes), dtype))
        if self.has_slots:
            out.append(("rows", (self.slots + 1, 1 + self.ring_blocks),
                        jnp.int32))
        return out

    def snapshot_arrays(self, arrays) -> list:
        """[(place in `arrays`, shape, dtype)] of the snapshot pool: one
        array a window pool and a state array, with `snapshot_slots`
        places where the slots were."""
        out = []
        for i, (kind, shape, dtype) in enumerate(arrays):
            if kind == "window":
                out.append((i, (1 + self.snapshot_slots * self.ring_blocks,)
                            + tuple(shape[1:]), dtype))
            elif kind == "state":
                out.append((i, (self.snapshot_slots + 1,) + tuple(shape[1:]),
                            dtype))
        return out

    def ring(self, slot: int) -> List[int]:
        """The pool blocks of `slot`'s ring (block 0 is scratch)."""
        first = 1 + (slot - 1) * self.ring_blocks
        return list(range(first, first + self.ring_blocks))


class PagedKVCache:
    """Refcounted block-pool KV cache shared by all layers of one model.

    All paged layers allocate in lockstep (a token occupies the same
    slot in every layer's pool), so ONE free list / block table set
    serves the whole stack. `pools` is the list the `layout`
    (`CacheLayout`) describes: paged pools in the layout `pool_shape`
    gives (module docstring, "Pool layout"), window pools, state arrays,
    and last, where the layout has slots, the ROWS table the step reads
    its rows' slots and rings from (`bind_rows`); `kinds` names each
    entry.
    """

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.float32,
                 enable_prefix_cache: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 host_tier: Optional["HostKVTier"] = None,
                 compress_blocks: int = 0,
                 promote_hits: int = 0,
                 tp_size: int = 1, mesh=None,
                 latent: Optional[Tuple[int, int]] = None,
                 layout: Optional[CacheLayout] = None):
        """`latent=(k_dim, v_dim)` selects the latent row (module
        docstring), with one kv head of `head_dim` = k_dim. `layout`
        gives each layer its
        kind (`CacheLayout`); without one each of the `num_layers`
        keeps a paged pool."""
        self.layout = layout or CacheLayout([{"kind": "paged"}] * num_layers,
                                            block_size, 0, 0)
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is scratch)")
        if compress_blocks < 0:
            raise ValueError(f"compress_blocks {compress_blocks} < 0")
        if promote_hits < 0:
            raise ValueError(f"promote_hits {promote_hits} < 0")
        if tp_size < 1:
            raise ValueError(f"tp_size {tp_size} < 1")
        if num_kv_heads % tp_size != 0:
            # fail at construction, not as a reshape crash mid-serve:
            # the pool shards over kv-heads, so every chip must own a
            # whole number of them (GQA groups stay device-local)
            raise ValueError(
                f"num_kv_heads={num_kv_heads} not divisible by "
                f"tp_size={tp_size}: the KV pool shards over kv-heads "
                "(pool_shape), so tp must divide them evenly")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.latent = latent
        self.dtype = dtype
        self.tp_size = tp_size
        self.enable_prefix_cache = enable_prefix_cache
        # pools are allocated at the GLOBAL shape; under tp the mesh
        # shards the row's heads so each chip HOLDS pool_shape() bytes
        self._sharding = None
        if mesh is not None and tp_size > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._sharding = NamedSharding(mesh, P(None, None, "tp"))
        self.pools: List[jnp.ndarray] = self.fresh_pools()
        # what each entry of `pools` is: "paged", "index", "window",
        # "state" or "rows". Whole-block movers (the COW replay, the
        # tiers) touch the paged ones only
        self.kinds: List[str] = [kind for kind, _, _ in self.layout.arrays(
            self.pool_shape(1), self.dtype)]
        layout = self.layout
        # slots of the window and state kinds: free list, owner map,
        # and how many ring blocks each sequence has given back
        self._free_slots = deque(range(1, layout.slots + 1)
                                 if layout.has_slots else ())
        self._slot: Dict[int, int] = {}
        self._ring_released: Dict[int, int] = {}
        self.window_blocks_released = 0
        # state snapshots (`CacheLayout`): prefix reuse over slots. The
        # snapshot pool `snaps` (one array a window pool and a state
        # array, `snap_places` its place in `pools`); the snapshots held,
        # by the prefix each ends, least recently used first:
        # key -> (place, the blocks under the prefix, the key of the
        # boundary before it); how many held snapshots stand one
        # boundary deeper than a key; which snapshots lean on a block;
        # the copies staged for the engine
        self.snapshot_every = (layout.snapshot_tokens
                               if layout.has_slots and enable_prefix_cache
                               else 0)
        self.snap_places: List[int] = []
        self.snaps: List[jnp.ndarray] = []
        if self.snapshot_every:
            described = layout.snapshot_arrays(
                layout.arrays(self.pool_shape(1), self.dtype))
            self.snap_places = [i for i, _, _ in described]
            self.snaps = [jnp.zeros(shape, dtype)
                          for _, shape, dtype in described]
        self._snap_free = deque(range(1, layout.snapshot_slots + 1)
                                if self.snapshot_every else ())
        self._snap_index: "OrderedDict[tuple, Tuple[int, tuple, tuple]]" \
            = OrderedDict()
        self._snap_deeper: Dict[tuple, int] = {}
        self._block_snaps: Dict[int, Set[tuple]] = {}
        self._pending_restores: List[Tuple[int, int]] = []  # (place, slot)
        self._pending_takes: List[Tuple[int, int]] = []     # (slot, place)
        self.snapshots_taken = 0
        self.snapshots_restored = 0
        self.snapshots_evicted = 0
        self.snapshot_tokens_skipped = 0
        # optional in-device compressed tier: a parallel int8 block pool
        # (+ per-block k/v scales) cold prefix content quantizes into at
        # ~half the bytes. Slot 0 is scratch (the fixed-lane flushes pad
        # with it), mirroring fp block 0. Like `pools`, the arrays are
        # updated FUNCTIONALLY by the engine's eager lane scatters.
        self.compress_blocks = int(compress_blocks)
        self._compress_on = self.compress_blocks > 0 and enable_prefix_cache
        self.qpools: List[jnp.ndarray] = []
        self.qscales: List[Tuple[jnp.ndarray, jnp.ndarray]] = []
        if self._compress_on:
            qshape = (self.compress_blocks + 1,) + self.pool_shape(1)[1:]
            self.qpools = [self._place(jnp.zeros(qshape, jnp.int8))
                           for _ in range(num_layers)]
            self.qscales = [(jnp.ones((self.compress_blocks + 1,),
                                      jnp.float32),
                             jnp.ones((self.compress_blocks + 1,),
                                      jnp.float32))
                            for _ in range(num_layers)]
        # compressed-tier bookkeeping (host-side): slot free list,
        # content-keyed LRU index (OrderedDict end = hottest), reverse
        # map, staged fixed-lane traffic, and the last-hit clock the
        # deterministic coldness policy orders by (the ENGINE publishes
        # step_now each step).
        self._cfree = deque(range(1, self.compress_blocks + 1))
        self._cindex: "OrderedDict[tuple, int]" = OrderedDict()
        self._cslot_key: Dict[int, tuple] = {}
        self._pending_compress: List[Tuple[int, int]] = []  # (fp blk, slot)
        self._pending_promotes: List[Tuple[int, int]] = []  # (fp blk, slot)
        self._promote_slots: Set[int] = set()
        # direct-read plumbing: a compressed hit is served IN PLACE —
        # the block table carries the bias-encoded slot (-(slot+1)) and
        # the ragged step dequantizes it inside the kernel — instead of
        # claiming an fp block and staging a promote. promote_hits is
        # the opt-in warm-up threshold: 0 never promotes, 1 restores the
        # always-promote PR-19 behavior, N>1 promotes a key once it has
        # been hit N times (hot prefixes graduate back to fp reads).
        self.promote_hits = int(promote_hits)
        self._cslot_refs: Dict[int, int] = {}     # slot -> live direct readers
        self._chits: Dict[tuple, int] = {}        # key -> compressed-hit count
        self._last_hit: Dict[int, int] = {}           # block -> step
        self.step_now = 0
        self.compressed_total = 0         # blocks quantized in-device
        self.promoted_total = 0           # compressed blocks re-inflated
        self.compress_spills = 0          # cslot evictions (-> host/gone)
        self.compress_hit_tokens = 0      # prompt tokens served int8
        self.direct_reads = 0             # int8 blocks read in place
        self.direct_read_tokens = 0       # prompt tokens they covered
        # block 0 reserved for padded/dummy rows — never handed out
        self._free = deque(range(1, num_blocks))
        self._tables: Dict[int, List[int]] = {}
        self._lens: Dict[int, int] = {}
        # token ids backing each reserved position (the content identity
        # the prefix index is keyed on)
        self._tokens: Dict[int, List[int]] = {}
        # prefix length per sequence whose KV is actually IN the pool —
        # alloc reserves blocks for the whole prompt up front, but their
        # content arrives chunk by chunk; only committed-full blocks are
        # shareable (a hit must never read a block whose scatter is
        # still queued behind it in the schedule)
        self._committed: Dict[int, int] = {}
        self._refs: Dict[int, int] = {}               # block -> refcount
        # full-prefix token tuple -> block holding that prefix's last block
        self._index: Dict[tuple, int] = {}
        self._key_of: Dict[int, tuple] = {}           # block -> index key
        # block -> (its index key, the key's advertised digest): prefix_rows
        self._digest_of: Dict[int, Tuple[tuple, str]] = {}
        self._pending_copies: List[Tuple[int, int]] = []   # (src, dst)
        # optional host-RAM second tier (engine/kvtier.py): blocks the
        # pool is about to destroy are copied out, and alloc_sequence
        # walks it past the device index. Revivals stage (block, layers)
        # loads here; the engine flushes them into the device pools
        # (drain_host_loads) BEFORE any step reads or COW-copies them.
        self.host_tier = host_tier
        self._pending_host_loads: List[Tuple[int, list]] = []
        self.tier_revivals = 0            # host-tier blocks revived
        self.tier_hit_tokens = 0          # prompt tokens covered by them
        # cumulative stats (serve_event / bench verdicts)
        self.hit_tokens = 0
        self.prompt_tokens = 0
        self.cow_copies = 0
        self.cached_free_evictions = 0    # stale prefix entries recycled
        self.cached_free_revivals = 0     # freed blocks re-hit from the index
        # event-driven counters into the metrics registry
        # (OBSERVABILITY.md); gauges (occupancy/hit_rate) are sampled
        # per step by the engine — nothing here runs per token
        reg = registry if registry is not None else default_registry()
        self._c_cow = reg.counter(
            "ptpu_kv_cow_copies_total", "Copy-on-write block copies")
        self._c_evict = reg.counter(
            "ptpu_kv_cached_free_evictions_total",
            "Cached-free prefix entries evicted on block reuse")
        self._c_revive = reg.counter(
            "ptpu_kv_cached_free_revivals_total",
            "Freed blocks revived from the prefix index")
        self._c_prompt_toks = reg.counter(
            "ptpu_kv_prompt_tokens_total", "Prompt tokens admitted")
        self._c_hit_toks = reg.counter(
            "ptpu_kv_hit_tokens_total",
            "Prompt tokens served from the prefix cache")
        self._c_compress = reg.counter(
            "ptpu_kv_compress_total",
            "Cold prefix blocks quantized into the device int8 pool")
        self._c_promote = reg.counter(
            "ptpu_kv_promote_total",
            "Compressed blocks dequantized back into fp on a prefix hit")
        self._c_direct_reads = reg.counter(
            "ptpu_kv_direct_int8_reads_total",
            "Int8-resident blocks read in place by the ragged step")
        self._c_direct_toks = reg.counter(
            "ptpu_kv_direct_int8_tokens_total",
            "Prompt tokens served by direct int8 reads")
        self._c_snapshots = reg.counter(
            "ptpu_state_snapshots_total",
            "State snapshots taken at a prompt's block boundary, restored "
            "into an admitted request's slot, evicted",
            labelnames=("event",))
        self._g_snapshots = reg.gauge(
            "ptpu_state_snapshots_held", "State snapshots in the pool")

    # -- capacity ---------------------------------------------------------
    def pool_shape(self, tp_size: Optional[int] = None) -> Tuple[int, ...]:
        """PER-CHIP shape of one layer's pool under `tp_size`-way
        tensor parallelism (defaults to this cache's own tp_size): the
        row's heads divide by tp, everything else replicates. tp=1 is
        the global shape. Sizing math (engine HBM planning,
        tools/paged_roofline.py --tp-size) goes through here so the
        layout and the divisibility contract live in ONE place."""
        tp = self.tp_size if tp_size is None else tp_size
        if tp < 1 or self.num_kv_heads % tp != 0:
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} not divisible by "
                f"tp_size={tp}")
        lanes = (paged.latent_lanes(self.latent[0]) if self.latent else
                 self.num_kv_heads // tp * paged.head_lanes(self.head_dim))
        return (self.num_blocks, self.block_size, lanes)

    def _place(self, pool):
        if self._sharding is None:
            return pool
        import jax
        return jax.device_put(pool, self._sharding)

    def fresh_pools(self) -> List[jnp.ndarray]:
        """Zeroed pools of this cache's shape and placement: what the
        constructor holds, and what the engine rebuilds after a step
        that failed with the pools already donated."""
        return [self._place(jnp.zeros(shape, dtype)) for _, shape, dtype
                in self.layout.arrays(self.pool_shape(1), self.dtype)]

    # -- slots (window rings, recurrent state) ----------------------------
    @property
    def slots_in_use(self) -> int:
        return len(self._slot)

    def slot(self, seq_id: int) -> int:
        return self._slot[seq_id]

    def bind_rows(self, seq_ids: Sequence[int]):
        """The ROWS table of a step whose row i is sequence
        `seq_ids[i]`: int32 [slots + 1, 1 + ring_blocks], a row's slot
        then its ring's pool blocks; the rows past the step's, the null
        row among them, keep slot 0 and the scratch block. The engine
        puts it in `pools`' last place before the step."""
        lay = self.layout
        table = np.zeros((lay.slots + 1, 1 + lay.ring_blocks), np.int32)
        for i, seq_id in enumerate(seq_ids):
            slot = self._slot[seq_id]
            table[i, 0] = slot
            table[i, 1:] = lay.ring(slot)
        return table

    def ring_blocks_held(self, seq_id: int, next_pos: int) -> int:
        """Blocks of its ring a sequence holds live once its next query
        stands at `next_pos`: those not wholly behind the window."""
        lay, bs = self.layout, self.block_size
        behind = max(0, next_pos - (lay.window - 1)) // bs
        return -(-next_pos // bs) - behind

    def release_behind_window(self, seq_id: int, next_pos: int) -> int:
        """A step has moved `seq_id`'s next query to `next_pos`: the
        logical blocks now wholly behind every window are given back
        (their ring places are the ones the sequence writes next).
        Returns how many this call released."""
        lay = self.layout
        if not lay.ring_blocks:
            return 0
        behind = max(0, next_pos - (lay.window - 1)) // self.block_size
        newly = behind - self._ring_released.get(seq_id, 0)
        if newly > 0:
            self._ring_released[seq_id] = behind
            self.window_blocks_released += newly
        return max(newly, 0)

    def reset_pools(self) -> None:
        """The fp pools' content is lost (a step failed after it had
        consumed the donated pools): fresh zeroed pools, and nothing on
        them counts as committed any more — no sequence's prefix, no
        cached-free block. The int8 pool and the host tier were not
        donated and keep their content. The engine then preempts every
        running sequence, and each re-prefills."""
        self.pools = self.fresh_pools()
        self._index.clear()
        self._key_of.clear()
        self._last_hit.clear()
        self._committed = dict.fromkeys(self._committed, 0)
        for snap in list(self._snap_index):   # their blocks hold nothing
            self._drop_snapshot(snap)
        self._pending_restores.clear()
        self._pending_takes.clear()

    def _host_kv(self, rows) -> Tuple[np.ndarray, np.ndarray]:
        """Device rows of one block -> its (k, v) on the host, each
        [block_size, Hkv, hd] and contiguous (the tiers keep them). A
        latent row travels as its two parts: ([bs, 1, v_dim] value
        lanes, [bs, 1, k_dim - v_dim] the rest)."""
        rows = np.asarray(rows)
        if self.latent:
            k_dim, v_dim = self.latent
            k, v = rows[:, None, :v_dim], rows[:, None, v_dim:k_dim]
        else:
            k, v = paged.unpack_kv(rows, self.head_dim)
        return np.ascontiguousarray(k), np.ascontiguousarray(v)

    def pack_block(self, k, v):
        """Inverse of `read_block`'s per-layer pair: one block's pool
        rows from the (k, v) the tiers keep."""
        if self.latent:
            xp = np if isinstance(k, np.ndarray) else jnp
            return paged.pack_latent(
                xp.concatenate([k, v], axis=-1)[..., 0, :],
                paged.latent_lanes(self.latent[0]))
        return paged.pack_kv(k, v)

    def read_block(self, block: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        """One block's per-layer (k, v) on the host: the host tier's
        and the transfer plane's encoding."""
        return [self._host_kv(pool[block]) for pool in self.pools]

    def per_chip_pool_bytes(self) -> int:
        """Measured HBM bytes ONE chip holds across every layer's
        pool — read off the arrays' addressable shards, not computed,
        so the serve_bench tp gate checks what XLA actually allocated.
        Falls back to the full array size for unsharded pools."""
        total = 0
        for arr in self.pools:
            shards = getattr(arr, "addressable_shards", None)
            if shards:
                total += max(s.data.nbytes for s in shards)
            else:
                total += arr.nbytes
        return total

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """DISTINCT allocated blocks — sharing shows up as lower usage."""
        return (self.num_blocks - 1) - len(self._free)

    @property
    def shared_blocks(self) -> int:
        return sum(1 for r in self._refs.values() if r > 1)

    @property
    def total_refs(self) -> int:
        return sum(self._refs.values())

    def ref_count(self, block: int) -> int:
        return self._refs.get(block, 0)

    def occupancy(self) -> float:
        """Fraction of allocatable blocks in use (serve_event metric)."""
        return self.used_blocks / max(1, self.num_blocks - 1)

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    def _pop_free(self) -> int:
        """Take a block for FRESH content, lazily evicting any stale
        cached-free index entry it still carries (freed blocks keep
        their prefix KV reusable until the pool actually needs them —
        free_sequence appends to the RIGHT and this pops from the LEFT,
        so the longest-freed cached content is evicted first). With a
        host tier attached the content is demoted before the entry
        dies — eviction becomes a tier transition, not a loss."""
        block = self._free.popleft()
        for snap in list(self._block_snaps.pop(block, ())):
            self._drop_snapshot(snap)    # its prefix loses a block
        key = self._key_of.pop(block, None)
        if key is not None and self._index.get(key) == block:
            self._demote_block(block, key, "evict")
            del self._index[key]
            self.cached_free_evictions += 1
            self._c_evict.inc()
        self._last_hit.pop(block, None)
        return block

    def _demote_block(self, block: int, key: tuple, reason: str) -> bool:
        """Ship one committed block's KV one rung down the demotion
        ladder — device-fp -> device-int8 -> host tier -> gone — under
        its content key. The int8 rung stages a fixed-lane quantize the
        engine flushes before anything overwrites the src block (the
        payload never leaves the device); the host rung is a device_get
        into the tier. reason="finish" skips the int8 rung: finish
        demotion feeds the fleet KV-transfer plane (serve/kvxfer.py
        GET /kvblocks), which serves from the HOST tier. A no-op when a
        lower rung already holds the key — that copy is the truth (the
        key IS the content, so it can never be stale), and re-encoding
        a revived-but-unflushed block would read back garbage."""
        if self._compress_on and reason != "finish":
            if key in self._cindex:
                return False          # already resident one rung down
            slot = self._take_cslot()
            if slot is not None:
                self._stage_compress(block, key, slot)
                return True
        if self.host_tier is None or self.host_tier.contains(key):
            return False
        return self.host_tier.put(key, self.read_block(block), reason=reason)

    # -- in-device compressed tier ----------------------------------------
    def _stage_compress(self, block: int, key: tuple, slot: int) -> None:
        """Queue one fp block's quantize into int8 slot `slot`. The
        payload is READ at flush time, which is safe against every
        same-plan writer: promotions, host loads, and COW copies all
        flush after compressions, and prefill/decode scatters land in
        the step after that."""
        self._pending_compress.append((block, slot))
        self._cindex[key] = slot           # inserted hottest (end)
        self._cslot_key[slot] = key
        self.compressed_total += 1
        self._c_compress.inc()

    def _take_cslot(self) -> Optional[int]:
        """A free int8 slot — or the coldest evictable compressed
        entry's slot, after spilling that entry one rung further down.
        Slots with in-flight lane traffic are not evictable: a
        pending-compress dst holds no payload yet (spilling it would
        read scratch garbage), a pending-promote src is about to be
        read by the flush, and a slot with live direct readers
        (_cslot_refs) is part of a running sequence's block table.
        Returns None when nothing can move; the caller falls through
        to the host rung."""
        if self._cfree:
            return self._cfree.popleft()
        busy = {s for _, s in self._pending_compress}
        busy |= self._promote_slots
        busy |= set(self._cslot_refs)
        for key, slot in self._cindex.items():     # coldest first
            if slot in busy:
                continue
            self._spill_cslot(key, slot)
            del self._cindex[key]
            del self._cslot_key[slot]
            self._chits.pop(key, None)   # warm-up clock dies with the entry
            return slot
        return None

    def _spill_cslot(self, key: tuple, slot: int) -> None:
        """Demote-to-host FAST PATH for an evicted compressed entry:
        the int8 payload + scales ship straight into the host tier —
        one quant step total, never a dequant->requant round trip. An
        int8-mode tier stores the device blobs verbatim (revival
        dequantizes with the original scales); an fp-mode tier stores
        the exact dequantization, which adds no second quant step."""
        self.compress_spills += 1
        if self.host_tier is None or self.host_tier.contains(key):
            return
        self.host_tier.put_device_int8(key, self._slot_qlayers(slot),
                                       self.dtype, reason="evict")

    def _slot_qlayers(self, slot: int) -> list:
        """One int8 slot's per-layer (kq, kscale, vq, vscale) payload —
        the device_int8 wire/tier encoding (kvtier.put_device_int8)."""
        qlayers = []
        for qpool, (ks, vs) in zip(self.qpools, self.qscales):
            kq, vq = self._host_kv(qpool[slot])
            qlayers.append((kq, float(ks[slot]), vq, float(vs[slot])))
        return qlayers

    def compress_cold(self, idle_steps: int = 4,
                      max_blocks: Optional[int] = None) -> int:
        """Proactive cold sweep (engine-driven, once per step):
        quantize the coldest committed prefix blocks — cached-free AND
        refcount-shared — into FREE int8 slots before pool pressure
        would evict them. Coldness is deterministic LRU by last-hit
        step; a block must have sat untouched >= `idle_steps`. The fp
        copy and its index entry STAY, so fp hits remain byte-exact and
        compressing a block that is still referenced is safe (committed
        full blocks are content-immutable: the key IS the content).
        The proactive path only fills free slots — it never spills a
        warmer compressed entry to make room; forced demotions do that.
        Returns blocks staged."""
        if not self._compress_on or not self._cfree:
            return 0
        # blocks whose device contents are not real yet — a staged
        # host-load dst (DMA flushes AFTER compressions) or a staged
        # promote dst — must never feed the quantize lanes this step
        inflight = {b for b, _ in self._pending_host_loads}
        inflight |= {b for b, _ in self._pending_promotes}
        cands = sorted(
            (self._last_hit.get(b, 0), b)
            for b, key in self._key_of.items()
            if key not in self._cindex and b not in inflight
            and self.step_now - self._last_hit.get(b, 0) >= idle_steps)
        staged = 0
        for _, b in cands:
            if not self._cfree or (max_blocks is not None
                                   and staged >= max_blocks):
                break
            self._stage_compress(b, self._key_of[b], self._cfree.popleft())
            staged += 1
        return staged

    def demote_sequence(self, seq_id: int, reason: str = "preempt") -> int:
        """Copy a live sequence's committed full blocks out to the host
        tier — the preemption path: the scheduler calls this right
        before free_sequence so re-admission revives the context by DMA
        instead of re-prefilling it (quadratic recompute becomes a
        linear copy). A prefill-phase engine also calls it at request
        FINISH (reason="finish") so a decode replica can pull the
        finished prefix over the fleet KV-transfer plane
        (serve/kvxfer.py). Returns blocks demoted. With the in-device
        compressed tier enabled this works without a host tier too —
        preempted blocks land one rung down in int8 (the cheapest
        revival) instead of being recompute-only."""
        if (self.host_tier is None and not self._compress_on) \
                or not self.enable_prefix_cache:
            return 0
        table = self._tables.get(seq_id)
        if table is None:
            return 0
        self._register_full_blocks(seq_id)
        toks = self._tokens[seq_id]
        bs = self.block_size
        count = 0
        for bi in range(self._known(seq_id) // bs):
            b = table[bi]
            if b < 0:
                # bias-encoded direct-read entry: the content already
                # lives in the int8 tier, so preempt-demotion is a
                # no-op. Finish-demotion feeds the fleet transfer plane
                # from the HOST tier — ship the int8 payload down the
                # spill fast path (one quant step total, no fp detour).
                slot = -b - 1
                key = (self._cslot_key.get(slot)
                       or tuple(toks[:(bi + 1) * bs]))
                if reason == "finish" and self.host_tier is not None \
                        and not self.host_tier.contains(key):
                    if self.host_tier.put_device_int8(
                            key, self._slot_qlayers(slot), self.dtype,
                            reason=reason):
                        count += 1
                continue
            key = self._key_of.get(b) or tuple(toks[:(bi + 1) * bs])
            if self._demote_block(b, key, reason):
                count += 1
        return count

    def _match_prefix(self, tokens: Sequence[int]) -> List[int]:
        """Longest run of committed full blocks matching `tokens`' head
        (read-only: no refs taken)."""
        if not self.enable_prefix_cache:
            return []
        if self.snapshot_every:     # over slots a hit goes by snapshots
            found = self._match_snapshot(tokens)
            return list(found[1][1]) if found else []
        matched: List[int] = []
        bs = self.block_size
        for end in range(bs, len(tokens) + 1, bs):
            block = self._index.get(tuple(tokens[:end]))
            if block is None:
                break
            matched.append(block)
        return matched

    # -- state snapshots ----------------------------------------------------
    def _match_snapshot(self, tokens: Sequence[int]):
        """The deepest snapshot boundary under `tokens` that leaves a
        token to compute: (its key, (place, blocks)) or None. One key
        a boundary, deepest first (a key is the prefix itself: building
        and hashing one is a pass over it, so the walk is over the
        boundaries, not over the blocks)."""
        every = self.snapshot_every
        if not every or not self._snap_index:
            return None
        for end in range((len(tokens) - 1) // every * every, 0, -every):
            key = tuple(tokens[:end])
            held = self._snap_index.get(key)
            if held is not None:
                return key, held
        return None

    def snapshot_depth(self, tokens: Sequence[int]) -> int:
        """How many of `tokens` a hit over the snapshots held would
        skip."""
        found = self._match_snapshot(tokens)
        return len(found[1][1]) * self.block_size if found else 0

    def take_snapshot(self, seq_id: int, upto: int) -> bool:
        """A chunk of `seq_id`'s prompt has ended at the boundary
        `upto`: keep its slot's state as the snapshot of that prefix,
        unless one is held already. The copy is staged for the engine
        (`drain_snapshot_takes`), which makes it before anything else
        touches the slot. The least recently used snapshot gives way
        when the pool is full: first among those that a deeper
        snapshot of the same prompt stands behind (a prompt that runs on
        past both hits the deeper one), else of all: a long document's
        last boundary outlives its sixteen earlier ones."""
        every = self.snapshot_every
        if not every or upto % every or upto <= 0:
            return False
        key = tuple(self._tokens[seq_id][:upto])
        if key in self._snap_index:
            self._snap_index.move_to_end(key)
            return False
        if not self._snap_free:
            self._drop_snapshot(next(
                (k for k in self._snap_index if self._snap_deeper.get(k)),
                next(iter(self._snap_index))))
        place = self._snap_free.popleft()
        blocks = tuple(self._tables[seq_id][:upto // self.block_size])
        before = key[:upto - every]
        self._snap_index[key] = (place, blocks, before)
        self._snap_deeper[before] = self._snap_deeper.get(before, 0) + 1
        for b in blocks:
            self._block_snaps.setdefault(b, set()).add(key)
        self._pending_takes.append((self._slot[seq_id], place))
        self.snapshots_taken += 1
        self._c_snapshots.labels(event="taken").inc()
        self._g_snapshots.set(len(self._snap_index))
        return True

    def _drop_snapshot(self, key: tuple) -> None:
        place, blocks, before = self._snap_index.pop(key)
        self._snap_free.append(place)
        self._snap_deeper[before] -= 1
        if not self._snap_deeper[before]:
            del self._snap_deeper[before]
        for b in blocks:
            leaning = self._block_snaps.get(b)
            if leaning is not None:
                leaning.discard(key)
                if not leaning:
                    del self._block_snaps[b]
        # a copy into the place that has not been made yet is void
        self._pending_takes = [(s, p) for s, p in self._pending_takes
                               if p != place]
        self.snapshots_evicted += 1
        self._c_snapshots.labels(event="evicted").inc()
        self._g_snapshots.set(len(self._snap_index))

    @property
    def snapshots_held(self) -> int:
        return len(self._snap_index)

    def drain_snapshot_takes(self) -> List[Tuple[int, int]]:
        """Staged (slot, snapshot place) copies: the engine makes them
        right after the step that reached the boundary."""
        out, self._pending_takes = self._pending_takes, []
        return out

    def drain_snapshot_restores(self) -> List[Tuple[int, int]]:
        """Staged (snapshot place, slot) copies of this plan's hits:
        the engine makes them before the step reads the slots."""
        out, self._pending_restores = self._pending_restores, []
        return out

    def can_allocate(self, tokens) -> bool:
        """Admission check. `tokens` may be a token list (prefix-aware:
        matched blocks cost nothing beyond their own revival) or a bare
        count (conservative)."""
        if self.layout.has_slots and not self._free_slots:
            return False        # every slot holds a running sequence
        if isinstance(tokens, int):
            return self.blocks_for(tokens) <= len(self._free)
        matched = self._match_prefix(tokens)
        need = self.blocks_for(len(tokens)) - len(matched)
        # cached-free matches leave the free list too (revival)
        revive = sum(1 for b in matched if b not in self._refs)
        return need + revive <= len(self._free)

    # -- sequence lifecycle ----------------------------------------------
    def alloc_sequence(self, seq_id: int, tokens: Sequence[int],
                       count_stats: bool = True) -> int:
        """Reserve blocks for a sequence's prompt, reusing committed
        prefix blocks from the index. Returns the number of CACHED
        tokens (KV already in the pool — the engine prefills only the
        suffix). A full-prompt hit is capped at n-1 so the last token
        always recomputes (its logits seed sampling); that write lands
        inside a shared block and COWs it. Raises CacheExhausted
        (allocating nothing) when the free list is short — the
        scheduler turns that into deferred admission or preemption.
        `count_stats=False` leaves hit_tokens/prompt_tokens untouched:
        a preemption re-admission re-hits its own just-committed blocks
        and would otherwise inflate hit_rate."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        if self.layout.has_slots and not self._free_slots:
            raise CacheExhausted("no free state slot")
        n = len(tokens)
        bs = self.block_size
        snapshot = self._match_snapshot(tokens)
        matched = (list(snapshot[1][1]) if snapshot else
                   [] if self.snapshot_every else self._match_prefix(tokens))
        # walk PAST the device-fp match into the compressed tier. Each
        # hit is served IN PLACE by default: the table entry carries the
        # bias-encoded slot (-(slot+1)) and the ragged step dequantizes
        # the block inside the kernel — no fp claim, no promote lanes.
        # A hit claims a fresh fp block + staged dequantize promotion
        # only when the warm-up threshold says so (promote_hits; see
        # __init__) or when the hit is the prompt's FINAL block: the
        # full-prompt cap recomputes token n-1, and its write must land
        # in a writable fp block, never an int8 slot.
        chits: List[Tuple[tuple, int, bool]] = []   # (key, slot, promote?)
        if self._compress_on:
            for end in range((len(matched) + 1) * bs, n + 1, bs):
                key = tuple(tokens[:end])
                slot = self._cindex.get(key)
                if slot is None:
                    break
                hits = self._chits.get(key, 0) + 1
                chits.append((key, slot,
                              end >= n or 0 < self.promote_hits <= hits))
        # ... and past THAT into the host tier: every hit is fetched
        # now (the payload is pinned here — a later demotion's LRU
        # eviction between admission and flush can't revoke it)
        host_loads: List[Tuple[tuple, list]] = []
        if self.host_tier is not None and self.enable_prefix_cache:
            for end in range((len(matched) + len(chits) + 1) * bs,
                             n + 1, bs):
                layers = self.host_tier.get(tuple(tokens[:end]))
                if layers is None:
                    break
                host_loads.append((tuple(tokens[:end]), layers))
        n_direct = sum(1 for _, _, p in chits if not p)
        need = self.blocks_for(n) - len(matched) - n_direct
        revive = [b for b in matched if b not in self._refs]
        if need + len(revive) > len(self._free):
            raise CacheExhausted(
                f"need {need + len(revive)} blocks, {len(self._free)} free")
        for b in matched:
            if b in self._refs:
                self._refs[b] += 1
            else:                       # cached-free hit: revive the block
                self._free.remove(b)
                self._refs[b] = 1
                self.cached_free_revivals += 1
                self._c_revive.inc()
            self._last_hit[b] = self.step_now
        # Pin every compressed hit's slot FIRST: the _pop_free calls
        # below can themselves demote dying cached-free entries into
        # the int8 pool, and a full pool would otherwise evict (spill)
        # the very slots this table is about to read or promote from.
        mid_blocks: List[int] = []      # compressed hits, in table order
        n_promoted = 0
        if chits:
            self._promote_slots.update(s for _, s, p in chits if p)
            for _, s, p in chits:
                if not p:
                    self._cslot_refs[s] = self._cslot_refs.get(s, 0) + 1
            for key, slot, p in chits:
                self._chits[key] = self._chits.get(key, 0) + 1
                self._cindex.move_to_end(key)        # LRU touch: hottest
                if not p:
                    mid_blocks.append(-(slot + 1))
                    self.direct_reads += 1
                    self._c_direct_reads.inc()
                    continue
                b = self._pop_free()
                self._refs[b] = 1
                mid_blocks.append(b)
                n_promoted += 1
                self._pending_promotes.append((b, slot))
                self._last_hit[b] = self.step_now
                if key not in self._index and b not in self._key_of:
                    self._index[key] = b
                    self._key_of[b] = key
                self.promoted_total += 1
                self._c_promote.inc()
        # host-tier hits claim fresh device blocks and stage their DMA;
        # the key registers first-wins so later prompts can share the
        # block as soon as the engine flushes the load
        host_blocks: List[int] = []
        for key, layers in host_loads:
            b = self._pop_free()
            self._refs[b] = 1
            host_blocks.append(b)
            self._pending_host_loads.append((b, layers))
            self._last_hit[b] = self.step_now
            if key not in self._index and b not in self._key_of:
                self._index[key] = b
                self._key_of[b] = key
        fresh = [self._pop_free()
                 for _ in range(need - n_promoted - len(host_blocks))]
        for b in fresh:
            self._refs[b] = 1
            self._last_hit[b] = self.step_now
        self._tables[seq_id] = matched + mid_blocks + host_blocks + fresh
        if self._free_slots:
            self._slot[seq_id] = self._free_slots.popleft()
        if snapshot:    # the slot starts from the boundary's state
            self._snap_index.move_to_end(snapshot[0])
            self._pending_restores.append(
                (snapshot[1][0], self._slot[seq_id]))
            self.snapshots_restored += 1
            self.snapshot_tokens_skipped += len(matched) * bs
            self._c_snapshots.labels(event="restored").inc()
        self._lens[seq_id] = n
        self._tokens[seq_id] = list(tokens)
        cached = min((len(matched) + len(chits) + len(host_blocks))
                     * bs, n - 1)
        self._committed[seq_id] = cached
        if chits:
            self.compress_hit_tokens += max(
                0, min((len(matched) + len(chits)) * bs, cached)
                - len(matched) * bs)
        if n_direct:
            self.direct_read_tokens += n_direct * bs
            self._c_direct_toks.inc(n_direct * bs)
        if host_blocks:
            tier_toks = max(0, cached - (len(matched) + len(chits))
                            * bs)
            self.tier_revivals += len(host_blocks)
            self.tier_hit_tokens += tier_toks
            self.host_tier.note_revived(len(host_blocks), tier_toks)
        if count_stats:
            self.hit_tokens += cached
            self.prompt_tokens += n
            self._c_hit_toks.inc(cached)
            self._c_prompt_toks.inc(n)
        return cached

    def ensure_writable(self, seq_id: int, start: int, end: int) -> None:
        """Copy-on-write pass before the engine scatters positions
        [start, end): every touched block with refcount > 1 is swapped
        for a fresh private block and an on-device (src, dst) block
        copy is queued (drain_copies) so already-valid positions in the
        block survive. Raises CacheExhausted when a COW needs a block
        and the free list is empty."""
        table = self._tables[seq_id]
        bs = self.block_size
        for bi in range(start // bs, (max(end, start + 1) - 1) // bs + 1):
            old = table[bi]
            if old < 0:
                # unreachable by construction: writes land at positions
                # >= cached, and alloc_sequence force-promotes the one
                # compressed hit a capped full-prompt write can touch
                # (the final block). Fail loudly rather than corrupt
                # the shared int8 slot.
                raise RuntimeError(
                    f"copy-on-write reached int8-resident entry {old} "
                    f"(seq {seq_id}, block index {bi})")
            if self._refs[old] <= 1:
                continue
            if not self._free:
                raise CacheExhausted("no free block for copy-on-write")
            new = self._pop_free()
            self._refs[old] -= 1
            self._refs[new] = 1
            table[bi] = new
            self._pending_copies.append((old, new))
            self.cow_copies += 1
            self._c_cow.inc()

    def drain_copies(self) -> List[Tuple[int, int]]:
        """Queued COW block copies; the engine MUST replay them on the
        device pools (src block -> dst block, every layer) before the
        next prefill/decode call reads or writes the dst blocks."""
        out, self._pending_copies = self._pending_copies, []
        return out

    def drain_host_loads(self) -> List[Tuple[int, list]]:
        """Staged host-tier revivals: (block, per-layer [(k, v)] host
        arrays). The engine MUST write them into the device pools
        BEFORE draining COW copies — a just-revived block can be the
        src of a same-plan copy-on-write."""
        out, self._pending_host_loads = self._pending_host_loads, []
        return out

    def drain_compress(self) -> List[Tuple[int, int]]:
        """Staged (fp block, int8 slot) quantizations. The engine MUST
        flush these FIRST — before promotions, host loads, and COW
        copies — so the quantize lanes read every src block's content
        ahead of any same-plan writer reusing it."""
        out, self._pending_compress = self._pending_compress, []
        return out

    @property
    def tier_flush_pending(self) -> bool:
        """Whether a compress, promote or host-tier load is staged for
        the next step's flush."""
        return bool(self._pending_compress or self._pending_promotes
                    or self._pending_host_loads)

    def drain_promotes(self) -> List[Tuple[int, int]]:
        """Staged (fp block, int8 slot) dequantize promotions, flushed
        AFTER compressions (a promo may read a slot the same plan just
        filled) and BEFORE host loads / COW copies / the step read."""
        out, self._pending_promotes = self._pending_promotes, []
        self._promote_slots = set()
        return out

    def commit_prefill(self, seq_id: int, upto: int) -> None:
        """Mark positions [0, upto) as present in the pool (a prefill
        chunk just scattered them) and register every newly-full block
        in the prefix index so later prompts can share it."""
        self._committed[seq_id] = max(self._committed.get(seq_id, 0), upto)
        self._register_full_blocks(seq_id)

    def committed_len(self, seq_id: int) -> int:
        return self._committed.get(seq_id, 0)

    def _known(self, seq_id: int) -> int:
        """Positions that are in the pool AND whose tokens the host
        knows: what a prefix key may cover. A sequence's length runs one
        ahead of its tokens while the step that picks the token at its
        tail is still in flight (`advance` without a value)."""
        return min(self._committed.get(seq_id, 0), len(self._tokens[seq_id]))

    def _register_full_blocks(self, seq_id: int) -> None:
        # over slots a hit goes by the snapshots' own record of their
        # blocks: no block is indexed (a key is the prefix itself, and
        # hashing one a block is a pass over a long document a block)
        if not self.enable_prefix_cache or self.snapshot_every:
            return
        bs = self.block_size
        table = self._tables[seq_id]
        toks = self._tokens[seq_id]
        for bi in range(self._known(seq_id) // bs):
            block = table[bi]
            if block < 0:
                continue    # int8-resident: indexed by _cindex, not here
            if block in self._key_of:
                continue                    # already indexed (maybe shared)
            key = tuple(toks[:(bi + 1) * bs])
            if key in self._index:
                continue                    # duplicate content: first wins
            self._index[key] = block
            self._key_of[block] = key

    def append_token(self, seq_id: int) -> int:
        """Reserve the slot for this sequence's next token (allocating a
        fresh block at a block boundary, COWing a shared tail block);
        returns the FLAT pool slot (block_id * block_size + offset) the
        engine passes to the decode step. Does NOT advance the length —
        call advance() after the step actually writes."""
        return self.reserve_slots(seq_id, 1)[0]

    def reserve_slots(self, seq_id: int, count: int) -> List[int]:
        """Reserve the next `count` token slots in one ALL-OR-NOTHING
        transaction (the speculative-decode path: the base token plus k
        draft tokens land in one multi-token StepRow, so either the
        whole window gets slots or the scheduler falls back to a plain
        1-token decode). The bill is pre-checked — COW copies for
        shared blocks the window touches plus fresh blocks past the
        table's end — and CacheExhausted raises BEFORE any refcount or
        table mutation, so a failed reservation leaves nothing to roll
        back. Returns the flat pool slots in window order. Like
        append_token, the length does not advance: the engine calls
        advance() only for positions verification actually accepted,
        and un-advanced slots are simply re-reserved (and overwritten)
        by the next step — that IS the speculative rollback."""
        pos = self._lens[seq_id]
        table = self._tables[seq_id]
        bs = self.block_size
        end = pos + count
        in_table_end = min(end, len(table) * bs)
        cow_need = 0
        if in_table_end > pos:
            cow_need = sum(
                1 for bi in range(pos // bs, (in_table_end - 1) // bs + 1)
                if self._refs[table[bi]] > 1)
        new_need = max(0, self.blocks_for(end) - len(table))
        if cow_need + new_need > len(self._free):
            raise CacheExhausted(
                f"need {cow_need + new_need} blocks ({cow_need} COW + "
                f"{new_need} fresh), {len(self._free)} free")
        if in_table_end > pos:
            self.ensure_writable(seq_id, pos, in_table_end)
        for _ in range(new_need):
            block = self._pop_free()
            self._refs[block] = 1
            self._last_hit[block] = self.step_now
            table.append(block)
        return [table[(pos + j) // bs] * bs + (pos + j) % bs
                for j in range(count)]

    def fork_sequence(self, src_id: int, dst_id: int) -> None:
        """Clone `src_id`'s sequence state into `dst_id` sharing EVERY
        block (refcount bump — zero new blocks, zero device copies):
        the parallel-sampling / best-of-n primitive. A finished prefill
        forks into n candidates that all read the same prompt KV; the
        first time a fork WRITES (its own generated tokens, starting
        with the shared partially-filled tail block) the ordinary
        ensure_writable copy-on-write path peels it a private copy.
        free_sequence needs no special casing: a fork's exclusive
        blocks (refcount 1) return to the free list, shared prompt
        blocks just drop one reference."""
        if dst_id in self._tables:
            raise ValueError(f"sequence {dst_id} already allocated")
        if self.layout.has_slots:
            raise ValueError(
                "a fork over recurrent state or a window ring: the paged "
                "blocks share by refcount and copy on write, and a slot's "
                "state and ring have no copy to hand the sibling (serve it "
                "with n=1)")
        table = self._tables[src_id]
        for b in table:
            if b < 0:       # shared direct-read slot: bump its pin too
                self._cslot_refs[-b - 1] += 1
            else:
                self._refs[b] += 1
        self._tables[dst_id] = list(table)
        self._lens[dst_id] = self._lens[src_id]
        self._tokens[dst_id] = list(self._tokens[src_id])
        self._committed[dst_id] = self._committed[src_id]

    def advance(self, seq_id: int, token: Optional[int] = None) -> None:
        """A decode step was launched that writes `token`'s k/v at the
        reserved slot: extend the sequence, so that the next plan
        reserves the slot behind it, and index the tail block if it just
        filled (generated continuations are shareable too). `token` is
        None where the value is still on the device, the pick of a step
        in flight: the length then runs one ahead of the tokens until
        `supply` brings the value, and no block is indexed before all
        its tokens are known."""
        self._lens[seq_id] += 1
        self._committed[seq_id] = self._lens[seq_id]
        if token is not None:
            self.supply(seq_id, token)

    def trails(self, seq_id: int) -> bool:
        """Whether the sequence's tokens are one short of its length."""
        return len(self._tokens[seq_id]) < self._lens[seq_id]

    def supply(self, seq_id: int, token: int) -> None:
        """The value of the position the length had run ahead by."""
        toks = self._tokens[seq_id]
        toks.append(token)
        if len(toks) % self.block_size == 0:
            self._register_full_blocks(seq_id)

    def free_sequence(self, seq_id: int) -> int:
        """Drop this sequence's references; blocks whose refcount hits
        zero return to the free list but KEEP their prefix-index entry
        (cached-free): a later prompt with the same prefix revives them
        instead of recomputing, and `_pop_free` lazily evicts the entry
        only when the pool reuses the block for fresh content. Queued
        COW copies targeting a freed block are cancelled — the pool may
        hand the block straight back out, and a stale copy flushing
        later would clobber the new owner's KV. Returns how many blocks
        went back to the free list (shared ones live on)."""
        blocks = self._tables.pop(seq_id, [])
        slot = self._slot.pop(seq_id, None)
        if slot is not None:    # state and ring go with the sequence
            self._free_slots.append(slot)
            self._ring_released.pop(seq_id, None)
            self._pending_restores = [(p, s) for p, s in
                                      self._pending_restores if s != slot]
        self._lens.pop(seq_id, None)
        self._tokens.pop(seq_id, None)
        self._committed.pop(seq_id, None)
        freed = 0
        freed_set = set()
        for b in blocks:
            if b < 0:
                # direct-read entry: unpin the int8 slot. The payload
                # stays resident in _cindex (it never left), so there
                # is no cached-free bookkeeping — the slot just becomes
                # spillable again once its last reader drops.
                slot = -b - 1
                left = self._cslot_refs.get(slot, 0) - 1
                if left > 0:
                    self._cslot_refs[slot] = left
                else:
                    self._cslot_refs.pop(slot, None)
                continue
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)
                # the block was in live use until this very step — its
                # cached-free coldness clock starts NOW
                self._last_hit[b] = self.step_now
                freed += 1
                freed_set.add(b)
        if freed_set and self._pending_copies:
            self._pending_copies = [
                (s, d) for s, d in self._pending_copies
                if d not in freed_set]
        if freed_set and self._pending_host_loads:
            # cancel-mid-revival: the request died before its staged
            # host loads flushed. The freed blocks were index-registered
            # for content that never arrived — deregister them (the
            # host tier still holds the data; a re-request revives it
            # onto new blocks).
            stale = [b for b, _ in self._pending_host_loads
                     if b in freed_set]
            if stale:
                self._pending_host_loads = [
                    (b, la) for b, la in self._pending_host_loads
                    if b not in freed_set]
                for b in stale:
                    key = self._key_of.pop(b, None)
                    if key is not None and self._index.get(key) == b:
                        del self._index[key]
        if freed_set and self._pending_promotes:
            # cancel-mid-promotion (mirror of the host-load cancel):
            # a freed dst block may be re-issued immediately, and a
            # stale dequantize flushing later would clobber the new
            # owner's KV. The compressed entry still holds the payload;
            # a re-request promotes it onto new blocks.
            stale_p = [b for b, _ in self._pending_promotes
                       if b in freed_set]
            if stale_p:
                self._pending_promotes = [
                    (b, s) for b, s in self._pending_promotes
                    if b not in freed_set]
                self._promote_slots = {s for _, s in self._pending_promotes}
                for b in stale_p:
                    key = self._key_of.pop(b, None)
                    if key is not None and self._index.get(key) == b:
                        del self._index[key]
        return freed

    # -- views for the jitted step ---------------------------------------
    def seq_len(self, seq_id: int) -> int:
        return self._lens[seq_id]

    def block_table(self, seq_id: int) -> List[int]:
        return list(self._tables[seq_id])

    def slot_of(self, seq_id: int, pos: int) -> int:
        """Flat pool slot of an ALREADY-RESERVED position."""
        table = self._tables[seq_id]
        return table[pos // self.block_size] * self.block_size \
            + pos % self.block_size

    def padded_table(self, seq_id: int, max_blocks: int) -> List[int]:
        """Block table right-padded with scratch block 0 to the fixed
        width the compiled decode step expects."""
        table = self._tables[seq_id]
        if len(table) > max_blocks:
            raise ValueError(f"sequence {seq_id} spans {len(table)} blocks "
                             f"> max {max_blocks}")
        return table + [0] * (max_blocks - len(table))

    def prefix_rows(self, limit: int = 512) -> List[Tuple[int, str]]:
        """(length, digest) of the most recently indexed prefix keys
        (device tier) — the engine's half of the fleet prefix directory
        advertisement. Engine-loop thread only (reads the unlocked
        index). A digest is a pass over the key's tokens, thousands for
        a long shared document, and the advertisement is refreshed on
        the engine loop every quarter second: so a key's digest is
        computed once and kept beside its block for as long as the
        block carries that very key."""
        from paddle_tpu.engine.kvtier import prefix_digest
        # over slots what can be hit is a snapshot's prefix; its first
        # last block stands where an indexed prefix's block does
        items = ([(key, held[1][-1]) for key, held in self._snap_index.items()]
                 if self.snapshot_every else list(self._index.items()))
        if limit and len(items) > limit:
            items = items[-limit:]
        rows = []
        for key, block in items:
            kept = self._digest_of.get(block)
            if kept is None or kept[0] is not key:
                kept = self._digest_of[block] = (key, prefix_digest(key))
            rows.append((len(key), kept[1]))
        if len(self._digest_of) > 2 * len(items) + 64:
            live = {block for _, block in items}  # entries of blocks gone
            self._digest_of = {b: v for b, v in self._digest_of.items()
                               if b in live}
        return rows

    def compressed_keys(self, limit: int = 512) -> List[tuple]:
        """Most recently touched compressed-tier keys (hottest last) —
        advertised as the `device_int8` rung of the fleet prefix
        directory, between device-fp and host. Engine-loop thread
        only."""
        keys = list(self._cindex.keys())
        return keys[-limit:] if limit and len(keys) > limit else keys

    @property
    def compress_enabled(self) -> bool:
        """Whether the in-device int8 tier is active (budget > 0 and
        prefix caching on) — the scheduler's victim costing and the
        engine's directory advertisement branch on this."""
        return self._compress_on

    @property
    def direct_read_enabled(self) -> bool:
        """Whether compressed hits are served in place by the mixed
        ragged step (promote_hits != 1; 1 restores always-promote).
        The scheduler's victim costing and the frontend's /kvprefixes
        capability field branch on this."""
        return self._compress_on and self.promote_hits != 1

    @property
    def compressed_resident(self) -> int:
        return len(self._cindex)

    @property
    def compress_free_slots(self) -> int:
        """Unused int8 slots — the scheduler's victim costing caps the
        cheap-rung credit by this (a forced demotion beyond it spills
        warmer entries or, with no host tier, drops content)."""
        return len(self._cfree)

    def effective_pool_bytes(self) -> int:
        """fp-equivalent bytes of UNIQUE KV the device currently holds:
        the fp pool plus compressed entries whose content lives ONLY in
        the int8 tier. Proactively compressed blocks keep their fp copy
        resident (compress_cold), so counting every _cindex entry would
        double-count content present in both tiers; an entry counts
        only once its fp index entry is gone (the block was evicted or
        was never fp-resident). Reaches (num_blocks-1 + compress_blocks)
        x block-bytes when the int8 pool is full of fp-evicted content
        — the ~2x-effective-pool headline, sampled into
        ptpu_kv_pool_effective_bytes."""
        values = (self.latent[0] if self.latent else
                  2 * self.num_kv_heads * self.head_dim)
        blk = (self.block_size * values
               * np.dtype(self.dtype).itemsize * len(self.pools))
        uniq = sum(1 for k in self._cindex if k not in self._index)
        return (self.num_blocks - 1 + uniq) * blk

    # -- observability ----------------------------------------------------
    def hit_rate(self) -> float:
        """Fraction of all prompt tokens served from the prefix cache."""
        return self.hit_tokens / max(1, self.prompt_tokens)

    def stats(self) -> Dict[str, float]:
        out = {
            "hit_tokens": self.hit_tokens,
            "prompt_tokens": self.prompt_tokens,
            "hit_rate": round(self.hit_rate(), 4),
            "cow_copies": self.cow_copies,
            "cached_free_evictions": self.cached_free_evictions,
            "cached_free_revivals": self.cached_free_revivals,
            "shared_blocks": self.shared_blocks,
            "used_blocks": self.used_blocks,
            "occupancy": round(self.occupancy(), 4),
        }
        if self.snapshot_every:
            out["snapshots_held"] = len(self._snap_index)
            out["snapshots_taken"] = self.snapshots_taken
            out["snapshots_restored"] = self.snapshots_restored
            out["snapshots_evicted"] = self.snapshots_evicted
            out["snapshot_tokens_skipped"] = self.snapshot_tokens_skipped
        if self._compress_on:
            out["compressed_blocks"] = len(self._cindex)
            out["compress_total"] = self.compressed_total
            out["promote_total"] = self.promoted_total
            out["compress_spills"] = self.compress_spills
            out["compress_hit_tokens"] = self.compress_hit_tokens
            out["direct_int8_reads"] = self.direct_reads
            out["direct_int8_tokens"] = self.direct_read_tokens
        if self.host_tier is not None:
            out["tier_revivals"] = self.tier_revivals
            out["tier_hit_tokens"] = self.tier_hit_tokens
            out.update(self.host_tier.stats())
        return out

    def reset_stats(self) -> None:
        self.hit_tokens = self.prompt_tokens = self.cow_copies = 0
        self.cached_free_evictions = self.cached_free_revivals = 0
        self.tier_revivals = self.tier_hit_tokens = 0
        self.compressed_total = self.promoted_total = 0
        self.compress_spills = self.compress_hit_tokens = 0
        self.direct_reads = self.direct_read_tokens = 0
        self.snapshots_taken = self.snapshots_restored = 0
        self.snapshots_evicted = self.snapshot_tokens_skipped = 0

    def assert_quiesced(self) -> None:
        """Leak check: with no live sequences every refcount must be
        gone and the free list full. Index entries may remain, but only
        for cached-free blocks (their content stays reusable by
        design); an indexed block NOT on the free list is a leak."""
        if self._tables:
            raise RuntimeError(f"live sequences: {list(self._tables)}")
        if self._slot:
            raise RuntimeError(f"leaked state slots: {self._slot}")
        if self._pending_restores or self._pending_takes:
            raise RuntimeError(
                f"{len(self._pending_restores)} snapshot restores and "
                f"{len(self._pending_takes)} takes never made")
        if self.snapshot_every and len(self._snap_free) \
                + len(self._snap_index) != self.layout.snapshot_slots:
            raise RuntimeError(
                f"snapshot place leak: {len(self._snap_free)} free + "
                f"{len(self._snap_index)} held != "
                f"{self.layout.snapshot_slots}")
        if self._refs:
            raise RuntimeError(f"leaked refcounts: {self._refs}")
        if self._pending_host_loads:
            raise RuntimeError(
                f"{len(self._pending_host_loads)} host-tier loads never "
                "flushed")
        if self._pending_compress:
            raise RuntimeError(
                f"{len(self._pending_compress)} compress lanes never "
                "flushed")
        if self._pending_promotes:
            raise RuntimeError(
                f"{len(self._pending_promotes)} promote lanes never "
                "flushed")
        if self._cslot_refs:
            raise RuntimeError(
                f"leaked direct-read slot pins: {self._cslot_refs}")
        if self._compress_on and \
                len(self._cfree) + len(self._cindex) != self.compress_blocks:
            raise RuntimeError(
                f"compressed-slot leak: {len(self._cfree)} free + "
                f"{len(self._cindex)} resident != {self.compress_blocks}")
        if len(self._free) != self.num_blocks - 1:
            raise RuntimeError(
                f"free list {len(self._free)} != {self.num_blocks - 1}")
        free = set(self._free)
        leaked = [b for b in self._key_of if b not in free]
        if leaked:
            raise RuntimeError(
                f"indexed blocks not on the free list: {leaked}")
