"""Continuous batching scheduler (iteration-level scheduling, Orca-style)
with chunked prefill.

Classic static batching admits a batch, decodes until EVERY member
finishes, then admits the next — short requests wait on the longest
one, and freed KV memory idles. Continuous batching reschedules every
STEP: finished sequences leave the running set immediately, waiting
requests are admitted the moment blocks free up, and each step the
scheduler hands the engine ONE MIXED plan — every decode-ready row
plus budget-bounded prefill chunks, packed into the same launch.

Policy (simple and deterministic, ENGINE.md §scheduler):

- Admission is FIFO and block-bound only: a request admits when a
  batch slot is open and its prompt's blocks fit (prefix-cache hits
  shrink the bill). Admission allocates the WHOLE prompt's blocks and
  records how many leading tokens the prefix cache already holds —
  those are never prefilled.
- MIXED STEPS: every step carries one row per running request — a
  decode row (its next token) for decode-ready sequences, a prefill
  chunk of at most `max_prefill_tokens` total tokens for sequences
  still prefilling (Sarathi-style piggybacking). A long prompt can
  never starve running decodes (they advance EVERY step) and is never
  starved by them (every step also moves its prefill forward), so
  both inter-token latency and TTFT stay bounded without the old
  chunk/decode alternation. A request whose final chunk ran becomes
  decode-ready (the engine samples its first token from that chunk's
  logits). A decode row is just the 1-token window
  [seq_len, seq_len+1) of req.tokens — the engine packs both row
  kinds into one flat launch (kernels/paged_attention.py ragged).
- Preemption by recompute: when a decode append or a COW copy needs a
  block and the pool is empty, the LAST-admitted running request is
  evicted — its blocks are dropped (refcounts) and it rejoins the
  FRONT of the waiting queue with prompt := prompt + generated, so its
  re-prefill reproduces the exact KV state (cheaper than copy-out for
  short sequences, and the deterministic choice keeps tests
  reproducible). FIFO order of the others is preserved.

The scheduler owns no device state; it manipulates the PagedKVCache's
host-side bookkeeping and Request objects. The engine turns its plans
into jitted prefill/decode calls.
"""

from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from paddle_tpu.engine.paged_cache import CacheExhausted, PagedKVCache

# request lifecycle: WAITING -> RUNNING -> FINISHED (PREEMPTED -> WAITING)
WAITING, RUNNING, FINISHED = "waiting", "running", "finished"

_req_ids = itertools.count()


@dataclass(eq=False)
class Request:
    """One inference request; `prompt` grows on preemption (recompute).
    A request is itself and equals no other (`eq=False`): the plan asks
    `req in self.running` some 1,500 times a step of 32 rows, and a
    comparison field by field cost 2 ms of every plan."""
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 => greedy
    top_k: int = 0                    # 0 => full vocab
    seed: int = 0
    eos_id: Optional[int] = None
    callback: Optional[Callable[[int], None]] = None  # per-token stream
    # absolute monotonic completion deadline (inf = none). The
    # scheduler's preemption choice reads it: the victim is the running
    # request with the MOST slack, so tight-deadline requests keep
    # their KV state under pool pressure.
    deadline: float = float("inf")
    # parallel sampling (best-of-n): the engine forks n_candidates - 1
    # siblings off this request's finished prefill, all sharing its
    # prompt blocks (PagedKVCache.fork_sequence). Siblings are ordinary
    # requests with cand_index > 0 and `parent` set; the primary lists
    # them in `forks`. fork_callback(i) builds sibling i's per-token
    # stream callback (None = decode silently, the best_of > n case).
    n_candidates: int = 1
    cand_index: int = 0
    parent: Optional["Request"] = None
    forks: List["Request"] = field(default_factory=list)
    fork_callback: Optional[Callable[[int],
                                     Optional[Callable[[int], None]]]] = None
    # cumulative log-probability of the sampled tokens under each
    # step's sampling distribution — the best-of-n ranking signal
    logprob_sum: float = 0.0
    req_id: int = field(default_factory=lambda: next(_req_ids))
    generated: List[int] = field(default_factory=list)
    state: str = WAITING
    preemptions: int = 0
    preempt_carry: int = 0            # tokens folded into prompt on preempt
    prefill_pos: int = 0              # prompt tokens prefilled (or cached)
    cached_tokens: int = 0            # prefix-cache hit at last admission
    enqueue_time: float = 0.0
    admit_time: float = 0.0           # first admission (queue-wait metric)
    first_token_time: float = 0.0
    finish_time: float = 0.0
    finish_reason: str = ""
    # tokens that steps launched and not yet collected will yield (0 or
    # 1 whenever a plan is made): `generated` gets a token only when
    # its step is collected, and the plan made meanwhile counts it in
    in_flight: int = 0

    @property
    def tokens(self) -> List[int]:
        """Prompt as the cache must hold it (original + regenerated)."""
        return self.prompt + self.generated

    @property
    def num_generated(self) -> int:
        """Tokens generated across preemptions (prompt absorbs them)."""
        return len(self.generated) + self.preempt_carry

    @property
    def prefilling(self) -> bool:
        # against the PROMPT, not tokens: generated tokens enter the
        # cache via decode's append/advance, never via a chunk
        return self.prefill_pos < len(self.prompt)


@dataclass
class StepRow:
    """One row of a mixed step: run `req`'s token window
    [start, start + length). decode=True is the next-token window of a
    decode-ready sequence (its slots already reserved); decode=False
    is a prefill chunk of the prompt. A decode row with a non-empty
    `draft` is a SPECULATIVE row: its window is [start, start+1+k) —
    the base token plus k drafted tokens — and the engine verifies all
    k positions from the one launch, emitting the accepted prefix."""
    req: Request
    start: int
    length: int
    decode: bool = False
    draft: List[int] = field(default_factory=list)

    @property
    def samples(self) -> bool:
        """Whether the step yields a token for this row: a decode row
        does, and the chunk that ends its prompt."""
        return (self.decode
                or self.start + self.length == len(self.req.prompt))


# back-compat alias: a prefill chunk is a StepRow with decode=False
PrefillChunk = StepRow

Plan = List[StepRow]


class Scheduler:
    """Decides, per engine step, what work runs: one mixed plan of
    decode rows and prefill chunks. Bounds: `max_batch_size` concurrent
    running sequences (the engine packs exactly this many rows into its
    compiled step), `max_prefill_tokens` prompt tokens per step's
    chunks (decode rows ride free), `max_seq_len` ceiling on
    prompt+generation."""

    def __init__(self, cache: PagedKVCache, max_batch_size: int = 8,
                 max_prefill_tokens: int = 512, max_seq_len: int = 2048,
                 drafter=None):
        self.cache = cache
        self.max_batch_size = max_batch_size
        self.max_prefill_tokens = max_prefill_tokens
        self.max_seq_len = max_seq_len
        # speculative decoding (engine/draft.py): when set, decode-ready
        # rows carry up to drafter.k drafted tokens for batched
        # verification; None = plain 1-token decode rows
        self.drafter = drafter
        self.waiting: deque[Request] = deque()
        self.running: List[Request] = []
        # engine hooks: fired after a preemption moves a req back to
        # waiting / after admission moves one to running (telemetry:
        # queue-wait histograms and request-lifecycle spans)
        self.on_preempt: Optional[Callable[[Request], None]] = None
        self.on_admit: Optional[Callable[[Request], None]] = None
        # steps the engine has launched and not yet collected: their
        # rows' tokens, finishes and freed blocks are still to come
        self.steps_in_flight = 0
        # engine hook, called before a victim is chosen: True if it gave
        # blocks back or made tokens known (it collected the step in
        # flight), and the reservation is tried again first
        self.on_starved: Optional[Callable[[], bool]] = None

    # -- intake -----------------------------------------------------------
    def add(self, req: Request) -> None:
        if len(req.prompt) > self.max_seq_len:
            raise ValueError(
                f"prompt len {len(req.prompt)} > max_seq_len {self.max_seq_len}")
        req.state = WAITING
        self.waiting.append(req)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def has_work(self) -> bool:
        """A step in flight is work too: its rows' requests may all have
        been cancelled since, and it still has to be collected."""
        return bool(self.waiting or self.running or self.steps_in_flight)

    # -- planning ---------------------------------------------------------
    def next_batch(self) -> Optional[Plan]:
        """Plan one MIXED step: a list of StepRows (decode rows plus
        prefill chunks, one row per running request, in admission
        order) or None when idle. Admission allocates cache blocks
        (prefix hits included) and moves requests to RUNNING; chunk
        planning advances `prefill_pos` optimistically (the engine
        always executes the plan it is handed); every decode row has
        its next-token block reserved before it enters the plan,
        preempting from the tail if the pool runs dry. The chunk token
        budget goes to the head request first, so earlier prompts
        reach their first token sooner.

        The engine may plan while the step before is still in flight
        (ENGINE.md "A second step in flight"): a sequence's length then
        stands one past its known tokens, the row's input token is that
        step's pick, and `Request.in_flight` counts the token to come.
        A request whose token in flight is its last, by
        `max_new_tokens` or by `max_seq_len`, gets no row. A plan that
        would have to preempt asks `on_starved` first: a victim's
        `prompt + generated` has to be whole."""
        self._try_admit()
        if not self.running:
            self._check_liveness()
            return None
        rows: List[StepRow] = []
        ending = False      # a request waits for its last token, in flight
        budget = self.max_prefill_tokens
        for req in list(self.running):
            if req not in self.running:     # preempted by an earlier row
                continue
            if req.prefilling:
                if budget <= 0:
                    continue
                take = min(len(req.prompt) - req.prefill_pos, budget)
                start = req.prefill_pos
                every = self.cache.snapshot_every
                if every:
                    # a chunk ends on the next snapshot boundary, so the
                    # slot's state there can be kept for a later hit
                    take = min(take, every - start % every)
                # COW (a chunk writing into a shared block) may need a
                # free block; a dry pool preempts from the tail
                self._ensure_writable_or_preempt(req, start, start + take)
                req.prefill_pos += take
                budget -= take
                rows.append(StepRow(req, start, take, decode=False))
            elif self._ends_in_flight(req):
                ending = True   # no row that would be thrown away
            else:
                draft = self._propose_draft(req)
                if draft:
                    try:
                        # all-or-nothing: base token + k draft slots in
                        # one transaction; a short pool drops the draft
                        # (below) rather than preempting for it —
                        # speculation is an optimization, never worth
                        # evicting a neighbor's KV state
                        self.cache.reserve_slots(req.req_id,
                                                 1 + len(draft))
                        rows.append(StepRow(
                            req, self.cache.seq_len(req.req_id),
                            1 + len(draft), decode=True, draft=draft))
                        continue
                    except CacheExhausted:
                        pass
                if self._reserve_decode_block(req):
                    rows.append(StepRow(
                        req, self.cache.seq_len(req.req_id), 1,
                        decode=True))
        # a later row's block starvation may have evicted an
        # ALREADY-planned request (_pick_victim considers every running
        # row): its table is freed and prefill_pos reset, so its row
        # must not reach the engine
        rows = [w for w in rows if w.req in self.running]
        if rows:
            return rows
        if ending:
            return None     # nothing to plan until that step is collected
        if self.running:
            return self.next_batch()    # everything preempted; replan
        self._check_liveness()
        return None

    def _ends_in_flight(self, req: Request) -> bool:
        """Whether the token a step in flight will yield is `req`'s
        last, by its count or by the sequence-length ceiling: the host
        knows both without the token's value."""
        # (the lengths, not `req.tokens`: that builds the list, 33k
        # tokens a running request a call in the longest cell)
        return req.in_flight > 0 and (
            req.num_generated + req.in_flight >= req.max_new_tokens
            or self.out_of_room(req, req.in_flight))

    def out_of_room(self, req: Request, coming: int = 0) -> bool:
        """Whether `req` stands at the sequence-length ceiling once
        `coming` more tokens have landed: the engine ends a request by
        it when a token is emitted, and the plan made while that token
        is in flight foresees it by the same bound."""
        return (len(req.prompt) + len(req.generated) + coming
                >= self.max_seq_len)

    def _propose_draft(self, req: Request) -> List[int]:
        """Draft tokens for one decode-ready row, capped so the whole
        speculative window — base token + k drafts, each potentially
        EMITTING a token — can never overrun the request's token budget
        or the sequence-length ceiling."""
        if self.drafter is None:
            return []
        room = min(self.drafter.k,
                   req.max_new_tokens - req.num_generated - 1,
                   self.max_seq_len - len(req.tokens) - 1)
        if room <= 0:
            return []
        return self.drafter.propose(req.tokens, room)

    def _slots_of(self, req: Request) -> int:
        """Batch slots a request claims: itself, plus — while it still
        prefills — one per sibling the engine will fork at its final
        chunk. Admission counts the whole group up front so the forks'
        decode rows are guaranteed batch room the moment they exist."""
        if not req.prefilling:
            # one that ends with the token in flight has no row any more
            return 0 if self._ends_in_flight(req) else 1
        return 1 + max(0, req.n_candidates - 1 - len(req.forks))

    def _try_admit(self) -> List[Request]:
        admitted: List[Request] = []
        at = 0      # the first request that does not wait of its own accord
        while at < len(self.waiting):
            req = self.waiting[at]
            if self._awaits_a_prefill(req, admitted):
                at += 1     # those behind it may pass: it loses nothing
                continue
            slots = (sum(self._slots_of(r) for r in self.running)
                     + sum(self._slots_of(r) for r in admitted))
            if (slots + self._slots_of(req) > self.max_batch_size
                    or not self.cache.can_allocate(req.tokens)):
                break       # FIFO: don't skip ahead of a request held up
            del self.waiting[at]
            # re-admissions re-hit their own committed blocks; don't let
            # that inflate the prefix-cache hit rate
            cached = self.cache.alloc_sequence(
                req.req_id, req.tokens, count_stats=req.preemptions == 0)
            req.prefill_pos = cached
            req.cached_tokens = cached
            req.state = RUNNING
            admitted.append(req)
        self.running.extend(admitted)
        if self.on_admit is not None:
            for req in admitted:
                self.on_admit(req)
        return admitted

    def _awaits_a_prefill(self, req: Request, admitted) -> bool:
        """Over state snapshots (`CacheLayout`): whether a running
        request is still prefilling towards a snapshot boundary that
        `req` could hit and cannot yet. Blocks are shared as they
        commit, but a slot's state only where a snapshot was taken, so
        a request admitted beside its prefix's first owner would compute
        the whole prefix again; it waits in the queue instead, is
        admitted onto the owner's snapshots, and meanwhile lets the
        requests behind it pass (`_try_admit`). It waits for
        a request that is running and moving only: an owner that was
        preempted stands before it in the queue, and one past the
        boundary whose snapshot is gone is not waited for."""
        every = self.cache.snapshot_every
        if not every:
            return False
        toks = req.tokens
        want = self.cache.snapshot_depth(toks) + every
        if want >= len(toks):
            return False
        head = toks[:want]
        return any(r.prefilling and r.prefill_pos < want <= len(r.prompt)
                   and r.prompt[:want] == head
                   for r in self.running + admitted)

    def _ensure_writable_or_preempt(self, req: Request, start: int,
                                    end: int) -> None:
        """COW the chunk's target blocks, evicting tail requests (never
        `req` itself) while the pool is dry."""
        while True:
            try:
                self.cache.ensure_writable(req.req_id, start, end)
                return
            except CacheExhausted:
                if self.on_starved is not None and self.on_starved():
                    continue
                victim = self._pick_victim(req)
                if victim is None:
                    raise
                self.preempt(victim)

    def _reserve_decode_block(self, req: Request) -> bool:
        """Ensure a decode-ready sequence can hold one more token,
        evicting from the tail (last admitted) until allocation holds.
        Returns False when `req` itself was preempted along the way."""
        while req in self.running:
            try:
                self.cache.append_token(req.req_id)
                return True
            except CacheExhausted:
                if self.on_starved is not None and self.on_starved():
                    continue
                victim = self._pick_victim(req)
                if victim is None:
                    raise CacheExhausted(
                        "single sequence exceeds total KV pool; "
                        "increase num_blocks or lower max_seq_len")
                self.preempt(victim)
        return False

    def _preempt_cost(self, req: Request) -> float:
        """Modeled cost of evicting `req` and bringing it back. Without
        any lower tier every committed token re-prefills, and attention
        over the growing context makes that superlinear: ~n^2. With a
        host tier, committed FULL blocks swap out and revive by DMA
        (linear in bytes ~ n) and only the uncommitted tail re-prefills
        (~tail^2) — which is why long-context victims flip from worst
        choice to best under a tier. The in-device int8 rung is
        CHEAPER still: demotion and promotion are on-device lane
        scatters (no host DMA on either side) — but only as many blocks
        as the int8 pool has FREE slots get that rate; a demotion
        beyond that spills to the host rung (with a tier) or drops
        content entirely (without one, making it recompute-only), so
        the cheap credit is capped by free-slot capacity rather than
        handed to every committed block of an arbitrarily long
        victim. With direct reads (promote_hits != 1) the int8 rate
        drops further: revival no longer pays the promote round-trip
        (fp claim + dequantize scatter) — re-admission just bias-encodes
        the resident slots into the new block table."""
        n = len(req.tokens)
        if self.cache.host_tier is None \
                and not self.cache.compress_enabled:
            return float(n * n)
        full = (n // self.cache.block_size) * self.cache.block_size
        tail = n - full
        if self.cache.compress_enabled:
            cheap = min(full,
                        self.cache.compress_free_slots
                        * self.cache.block_size)
            rest = full - cheap
            rate = 0.1 if self.cache.direct_read_enabled else 0.25
            if self.cache.host_tier is not None:
                return float(cheap * rate + rest + tail * tail)
            return float(cheap * rate + rest * rest + tail * tail)
        return float(full + tail * tail)

    def _pick_victim(self, keep: Request) -> Optional[Request]:
        """The running request (other than `keep`) with the MOST
        deadline slack — a recompute preemption costs its victim a full
        re-prefill, so it should land on the request that can best
        absorb it. Without deadlines every slack is +inf and the choice
        degrades to the original deterministic rule: last admitted.
        With a host tier or the in-device compressed tier attached,
        equal-slack candidates are split by the swap-vs-recompute cost
        model instead (cheapest round-trip loses its blocks); with
        neither the legacy rule is bit-exact. None when nothing else is
        left to evict."""
        if self.cache.host_tier is None \
                and not self.cache.compress_enabled:
            best: Optional[Request] = None
            for r in self.running:      # later index wins ties (stable max)
                if r is not keep and (best is None
                                      or r.deadline >= best.deadline):
                    best = r
            return best
        best = None
        best_cost = 0.0
        for r in self.running:          # later index wins ties (stable max)
            if r is keep:
                continue
            cost = self._preempt_cost(r)
            if (best is None or r.deadline > best.deadline
                    or (r.deadline == best.deadline and cost <= best_cost)):
                best, best_cost = r, cost
        return best

    def preempt(self, req: Request) -> None:
        """Evict by recompute: drop block refs, fold generated tokens
        into the prompt, and requeue at the FRONT so it re-prefills
        first. With a host tier the committed blocks demote first —
        re-admission then revives them by DMA and only the tail
        recomputes."""
        self.cache.demote_sequence(req.req_id)
        self.cache.free_sequence(req.req_id)
        self.running.remove(req)
        req.preempt_carry += len(req.generated)
        req.prompt = req.prompt + req.generated
        req.generated = []
        req.preemptions += 1
        req.prefill_pos = 0
        req.state = WAITING
        self.waiting.appendleft(req)
        if self.on_preempt is not None:
            self.on_preempt(req)

    def _check_liveness(self) -> None:
        """With an idle engine and an empty pool, a head request that
        still can't admit NEVER will — fail loud instead of silently
        stranding it in the queue. (Chunked prefill removed the
        prefill-budget ceiling: any prompt that fits the pool admits.)"""
        if not self.waiting or self.running:
            return
        req = self.waiting[0]
        n = len(req.tokens)
        if self.cache.blocks_for(n) > self.cache.num_blocks - 1:
            raise CacheExhausted(
                f"request {req.req_id} ({n} tokens incl. "
                f"{req.preempt_carry} preempt-folded) can never be "
                f"scheduled; raise num_blocks ({self.cache.num_blocks})")

    # -- completion -------------------------------------------------------
    def finish(self, req: Request, reason: str) -> None:
        self.cache.free_sequence(req.req_id)
        self.running.remove(req)
        req.state = FINISHED
        req.finish_reason = reason

    def cancel(self, req: Request) -> bool:
        """Remove a request wherever it sits — the wait queue (no KV
        held) or the running set (frees its blocks; shared prefix
        blocks just drop one refcount and queued COW copies to freed
        blocks are cancelled by free_sequence). Returns False when the
        request already finished. Engine-thread only, BETWEEN calls of
        `step()` (the serve front-end marshals client disconnects
        through the engine loop, serve/frontend.py): a row the request
        has in a step in flight is thrown away when that step is
        collected."""
        if req in self.running:
            self.cache.free_sequence(req.req_id)
            self.running.remove(req)
        elif req in self.waiting:
            self.waiting.remove(req)
        else:
            return False
        req.state = FINISHED
        req.finish_reason = "cancelled"
        return True
