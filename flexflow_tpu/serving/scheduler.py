"""Continuous (iteration-level) batching scheduler for the serving engine.

Orca-style (OSDI'22) iteration-level scheduling over a fixed pool of decode
slots: new requests are admitted into the in-flight decode batch the moment
a slot frees up (no wait for the whole batch to drain), prompts are
length-bucketed so prefill compiles once per bucket instead of once per
prompt length (padding-free in the compile-cache sense: a handful of
static shapes cover every length), finished slots are recycled on
EOS/max-tokens, and admission backpressure is a bounded queue — ``submit``
refuses instead of letting an unbounded backlog eat host memory.

The scheduler is PURE host-side bookkeeping — deterministic by
construction (same submission order + same engine -> same token streams),
which is what the cross-request isolation tests key on. Device work
(prefill/decode/slot writes) lives in serving/engine.py.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Deque, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.reqtrace import get_reqtrace

_req_counter = itertools.count(1)


def reserve_rids(past: int) -> None:
    """Advance the process-wide rid counter past ``past`` (ISSUE 20):
    journal recovery replays requests under their ORIGINAL rids, so the
    counter must skip every rid the dead process ever issued or a fresh
    submit would collide with a replayed one. Monotone — never moves
    the counter backwards."""
    global _req_counter
    cur = next(_req_counter)  # consumed value is re-issued by count()
    _req_counter = itertools.count(max(cur, int(past) + 1))


def now_ms() -> float:
    """Default monotonic time base (ms) for deadline/drain decisions —
    ONE definition shared by the scheduler and the resilience policy so
    the two clocks cannot drift apart in units."""
    return time.monotonic() * 1e3


def remove_by_identity(queue, req: "Request") -> bool:
    """Remove ``req`` from a queue by IDENTITY (``is``), returning
    whether it was found. The one implementation behind every queue
    removal here and in the fleet router: Request is a dataclass holding
    ndarrays, so ``list.remove`` / ``in`` (``==`` comparison) raise
    ambiguous-truth mid-sweep."""
    for i, q in enumerate(queue):
        if q is req:
            del queue[i]
            return True
    return False


class ServingRejection(RuntimeError):
    """Common base of every admission refusal (ISSUE 9): the bounded-queue
    ``QueueFullError`` and the load shedder's ``OverloadError``
    (serving/resilience.py) both carry the same retry context, so a caller
    writes ONE except clause:

        try:
            engine.admit(sched, req)
        except ServingRejection as e:
            backoff(e.retry_after_ms); resubmit later

    ``queued``/``active`` snapshot the scheduler at refusal time;
    ``retry_after_ms`` is the admission controller's drain-time hint (0.0
    when no cost estimate exists yet)."""

    def __init__(self, message: str, queued: int = 0, active: int = 0,
                 retry_after_ms: float = 0.0):
        super().__init__(message)
        self.queued = int(queued)
        self.active = int(active)
        self.retry_after_ms = float(retry_after_ms)


class QueueFullError(ServingRejection):
    """Admission refused: the bounded submit queue is at capacity
    (``max_queue``). Callers should retry later or shed load — this is the
    backpressure signal, not an internal failure."""


class ContextOverflowError(ServingRejection):
    """Admission refused: the request's worst case (prompt + max new
    tokens) exceeds the engine's max supported context — the position
    embedding table bounds decodable length below the decode ring/pool
    capacity (ISSUE 12 satellite: previously the engine warned and
    clamped the ring at construction; rejecting AT ADMISSION, naming the
    limit, is what guarantees a too-long request can never silently alias
    position rows)."""


class BlockAccountingError(RuntimeError):
    """A paged-KV block operation violated the allocator's refcount laws
    (ISSUE 14 satellite): double-free (freeing a block whose refcount is
    already 0), sharing a free block, or touching the reserved garbage
    block. Before refcounts these corrupted the FIFO free list SILENTLY
    — the same block handed to two live requests, KV cross-talk with no
    error at the scene of the crime — so the laws are now typed and
    loud."""


class BlockAllocator:
    """Host-side refcounted free-list allocator over the paged KV pool
    (ISSUE 12; refcounts + copy-on-write support ISSUE 14).

    The pool is ``n_blocks`` fixed-size blocks of ``block_size`` tokens;
    block ``GARBAGE_BLOCK`` (0) is reserved — unused table entries point
    at it — so ``n_blocks - 1`` blocks are allocatable. Allocation is
    whole-request up front (``blocks_needed(prompt + max_new)``) at the
    moment a request is admitted into a slot, so the decode hot loop
    never allocates; recycling (EOS/length/eviction/quarantine/
    cancellation) returns the blocks through the scheduler's one
    ``_release_blocks`` choke point. Pure host bookkeeping — deterministic
    FIFO free list, so the schedule stays a function of the submission
    sequence.

    Prefix sharing (ISSUE 14, serving/prefix.py): a block may be mapped
    by several requests' block tables at once — the radix-tree prefix
    cache plus every request currently reusing that prefix. ``share``
    grows the refcount, ``free`` decrements it, and the block returns to
    the FIFO free list only at refcount 0; sharers never write into a
    shared block (a divergent write clones it first — the COW path), so
    refcounts are pure bookkeeping, not synchronization. The refcount
    laws (alloc/share/free round-trips, zero leaks under churn) are
    pinned property-style in tests/test_prefix_cache.py."""

    def __init__(self, n_blocks: int, block_size: int):
        assert n_blocks >= 2, "paged pool needs >= 1 usable block " \
                              "+ the garbage block"
        assert block_size >= 1
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.free_blocks: Deque[int] = deque(range(1, self.n_blocks))
        # refcounts[b] > 0 <=> b is live (mapped by >= 1 request table
        # and/or retained by the prefix trie); the garbage block is
        # never allocated and keeps refcount 0
        self.refcounts: List[int] = [0] * self.n_blocks
        self.blocks_hwm = 0

    @property
    def n_usable(self) -> int:
        return self.n_blocks - 1

    @property
    def in_use(self) -> int:
        return self.n_usable - len(self.free_blocks)

    def blocks_needed(self, tokens: int) -> int:
        return -(-max(int(tokens), 1) // self.block_size)

    def refcount(self, block: int) -> int:
        return self.refcounts[int(block)]

    def _check(self, block: int) -> int:
        b = int(block)
        if b <= 0 or b >= self.n_blocks:
            raise BlockAccountingError(
                f"block {b} is outside the pool (usable ids 1.."
                f"{self.n_blocks - 1}; 0 is the reserved garbage block)")
        return b

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` block ids at refcount 1 each, or None when the pool
        cannot satisfy the request right now (the scheduler keeps it
        queued and decodes; prefix-cache eviction may free some)."""
        if n > len(self.free_blocks):
            return None
        out = []
        for _ in range(int(n)):
            b = self.free_blocks.popleft()
            if self.refcounts[b] != 0:
                raise BlockAccountingError(
                    f"free list corrupt: block {b} popped with refcount "
                    f"{self.refcounts[b]} (double-listed)")
            self.refcounts[b] = 1
            out.append(b)
        self.blocks_hwm = max(self.blocks_hwm, self.in_use)
        return out

    def share(self, blocks: List[int]) -> None:
        """Add one reference to each block — a new request mapping a
        cached prefix, or the trie adopting a request's block."""
        for b in blocks:
            b = self._check(b)
            if self.refcounts[b] == 0:
                raise BlockAccountingError(
                    f"cannot share block {b}: it is free (refcount 0) — "
                    "a stale block id outlived its release")
            self.refcounts[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block; a block returns to the FIFO
        free list only when its last reference is gone. Freeing an
        already-free block raises (the double-free that used to corrupt
        the list silently)."""
        for b in blocks:
            b = self._check(b)
            if self.refcounts[b] == 0:
                raise BlockAccountingError(
                    f"double free of block {b}: refcount is already 0")
            self.refcounts[b] -= 1
            if self.refcounts[b] == 0:
                self.free_blocks.append(b)

    def leaked(self) -> List[int]:
        """Blocks still referenced — the zero-leak churn tests assert
        this is empty (or exactly the trie's retained set)."""
        return [b for b in range(1, self.n_blocks) if self.refcounts[b]]

    def reset(self) -> None:
        """Forget every allocation (replica kill/rejoin: the pool arrays
        are rebuilt from zeros, so no block is live anymore)."""
        self.free_blocks = deque(range(1, self.n_blocks))
        self.refcounts = [0] * self.n_blocks


@dataclasses.dataclass
class Request:
    """One generation request. ``prompt`` is a 1-D int token array;
    ``generated`` fills as decode steps commit tokens."""

    prompt: np.ndarray
    max_new_tokens: int
    rid: int = dataclasses.field(default_factory=lambda: next(_req_counter))
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None  # "eos" | "length"
    # serving telemetry (per-request): set by the engine
    submit_step: int = 0
    first_token_step: Optional[int] = None
    # first-token wall stamp (scheduler clock, ms): TTFT = first_token_ms
    # - submit_ms — THE head-of-line-blocking metric the chunked-prefill
    # bench sub-leg reports (a short request behind a monolithic long
    # prefill pays the whole prefill wall here)
    first_token_ms: float = 0.0
    # sampling-stream tag: the engine keys each request's rng fold on this
    # (submission order) rather than the process-global ``rid`` counter, so
    # the same (prompts, seed) reproduces the same draws run after run
    rng_tag: Optional[int] = None
    # resilience (ISSUE 9, docs/serving.md "Serving under failure"):
    # deadline_ms is the relative completion budget from submission (None =
    # no deadline; the engine defaults it from --request-timeout-ms);
    # submit_ms is stamped by the scheduler's clock at submit; outcome is
    # the terminal disposition, exactly one of
    # ok | deadline_exceeded | shed | decode_fault | preempted;
    # retries_used counts decode-fault re-prefills against the
    # --decode-retry-budget
    deadline_ms: Optional[float] = None
    submit_ms: float = 0.0
    outcome: Optional[str] = None
    retries_used: int = 0
    # paged KV (ISSUE 12): pool block ids this request holds while it
    # occupies a slot (allocated at admission, freed on recycle) — empty
    # while queued, and under a scheduler driven without an allocator
    kv_blocks: List[int] = dataclasses.field(default_factory=list)
    # prefix cache + chunked prefill (ISSUE 14, serving/prefix.py /
    # docs/serving.md "Prefix cache & chunked prefill"):
    # prefix_hit_tokens — tokens mapped from the radix trie at admission
    # (their prefill compute is skipped); prefill_pos — tokens of the
    # effective prompt whose KV is in the pool so far (starts at the
    # hit, advances per chunk); prefill_target — the effective prompt
    # length this admission must prefill; chunk_shape — the compiled
    # chunk program's token width; pending_cow — (src, dst) block pair
    # when the shared partial tail block must be cloned before the
    # first suffix write (the copy-on-write path); finish_ms — terminal
    # clock stamp (request-completion latency = finish_ms - submit_ms)
    prefix_hit_tokens: int = 0
    prefill_pos: int = 0
    prefill_target: int = 0
    chunk_shape: int = 0
    pending_cow: Optional[Tuple[int, int]] = None
    finish_ms: float = 0.0
    # sequence-parallel decode (ISSUE 18): the searched context-length
    # bucket this request was routed to at admission (None = engine has
    # no --context-buckets) — the bucket whose seq_shards the plan's
    # ``seq_shards_for`` picked; the fleet router and trace digest read
    # it back
    context_bucket: Optional[int] = None
    # multi-tenant SLO tiers (ISSUE 19, docs/multitenant.md): the tier
    # label the fleet door's weighted fair queue and per-tenant ledgers
    # key on. None = untenanted — scheduled under the standard tier's
    # parameters, aggregate-only accounting (pre-tenant behavior)
    tenant: Optional[str] = None

    @property
    def prefilling(self) -> bool:
        """True while this request occupies a slot whose prompt KV is
        not fully in the pool yet — the decode batch excludes it (its
        length cursor is unset; decode would read garbage)."""
        return self.prefill_target > 0 and \
            self.prefill_pos < self.prefill_target

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def effective_len(self) -> int:
        """Prompt length the NEXT prefill of this request needs: the
        original prompt plus everything already generated — a decode-fault
        retry re-prefills the full committed stream onto a fresh slot so
        generation continues exactly where the quarantine cut it."""
        return self.prompt_len + len(self.generated)

    def current_prompt(self) -> np.ndarray:
        """Token ids the next prefill feeds: ``prompt`` for a fresh
        request, ``prompt + generated`` for a quarantine retry."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])

    def expired(self, now_ms: float) -> bool:
        return (self.deadline_ms is not None and self.deadline_ms > 0
                and now_ms - self.submit_ms > self.deadline_ms)


def default_buckets(max_prompt_len: int, min_bucket: int = 16
                    ) -> Tuple[int, ...]:
    """Geometric prefill buckets: powers of two from ``min_bucket``,
    capped by ``max_prompt_len`` itself as the last bucket (a bucket wider
    than the decode ring would overflow the KV buffers) — each prompt pads
    to the smallest covering bucket, so the prefill jit cache holds at
    most log2(max/min)+1 entries."""
    buckets = []
    b = min(max(int(min_bucket), 1), max_prompt_len)
    while b < max_prompt_len:
        buckets.append(b)
        b *= 2
    buckets.append(min(b, max_prompt_len))
    return tuple(buckets)


def bucket_for(length: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(
        f"prompt length {length} exceeds the largest prefill bucket "
        f"{buckets[-1]} (raise --max-decode-len / the engine's buckets)")


class ContinuousBatchScheduler:
    """Slot allocator + admission queue for iteration-level batching.

    The engine drives it in a loop:

        while scheduler.active or scheduler.queued:
            action = scheduler.next_action()
            if action[0] == "prefill": ...engine prefills into a slot...
            else:                      ...engine runs one decode step...

    Invariants (tested): a slot serves exactly one request at a time; a
    freed slot's cache rows are fully overwritten by the next prefill
    before any decode reads them (no cross-request leakage); admission
    order is FIFO; the whole schedule is a deterministic function of the
    submission sequence.
    """

    def __init__(self, n_slots: int, max_queue: int = 64,
                 buckets: Optional[Sequence[int]] = None,
                 max_len: int = 128, clock=None):
        assert n_slots >= 1, "need at least one decode slot"
        self.n_slots = n_slots
        self.max_queue = max_queue
        self.max_len = max_len
        self.buckets = tuple(buckets) if buckets else \
            default_buckets(max_len)
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self._free: Deque[int] = deque(range(n_slots))
        self.finished: List[Request] = []
        # counters for the obs serving block / bench occupancy
        self.queue_depth_hwm = 0
        self.admitted = 0
        self.recycled = 0
        # resilience (ISSUE 9): submit stamps each request with this clock
        # (ms) so deadline math shares one time base with the engine's
        # sweeps; injectable for deterministic tests. The shed policy in
        # effect is recorded here so the backpressure refusal can NAME it;
        # draining=True stops admission (next_action only decodes) during a
        # graceful SIGTERM drain.
        self.clock = clock if clock is not None else now_ms
        self.shed_policy = "off"
        self.draining = False
        # request-level tracing (ISSUE 16, obs/reqtrace.py): captured at
        # construction like the engine's tracer; every lifecycle edge
        # below notes the singleton behind an ``enabled`` guard (one
        # attribute load + truth test when tracing is off). The fleet
        # stamps its replica index here so cross-replica hops carry it.
        self.rt = get_reqtrace()
        self.replica_idx: Optional[int] = None
        self.quarantined = 0
        self.evicted = 0
        # paged KV (ISSUE 12): the engine attaches its BlockAllocator and
        # max supported context (position-table bound) before driving the
        # loop; None = a scheduler driven alone (no pool to account) / no
        # context bound below max_len.
        # on_slot_freed fires on EVERY slot-freeing path (finish, evict,
        # quarantine, hedge cancel) — the paged engine resets the freed
        # slot's device-side block-table row and length cursor there: a
        # stale row would keep scattering the freed slot's discarded
        # tokens into blocks the allocator may have already handed to a
        # NEW request in another slot
        self.allocator: Optional[BlockAllocator] = None
        self.max_context: Optional[int] = None
        self.on_slot_freed = None
        # on_commit fires once per committed token, at THE commit point
        # (ISSUE 20): the fleet points it at the request journal's
        # progress writer when --journal-commit-every is on, so a
        # journaled token prefix is always a prefix of the real stream.
        # None (the default) keeps the journal-off hot path branch-only.
        self.on_commit = None
        # prefix cache + chunked prefill (ISSUE 14): the paged engine
        # attaches its radix-tree PrefixCache and --prefill-chunk-tokens
        # here; admission walks the trie, maps the hit into the slot's
        # block table and only the suffix is prefilled (in chunks when
        # the suffix exceeds chunk_tokens). _chunk_turn alternates chunk
        # and decode actions so a long prompt's chunks interleave with
        # other slots' decode steps instead of stalling them.
        self.prefix = None
        self.chunk_tokens = 0
        self._chunk_turn = False
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        # hedge-loss cancellations (ISSUE 11): slots/queue entries freed
        # WITHOUT a terminal outcome — the winning twin owns the ledger
        self.cancelled = 0
        # slot incarnation counters (ISSUE 17, the async serve loop):
        # bumped on EVERY slot-freeing path. A commit that was dispatched
        # against incarnation e of a slot must be discarded if the slot
        # was recycled (finish/evict/quarantine/hedge-cancel) while its
        # result was in flight — identity of the Request object alone is
        # not enough, a quarantined request can re-enter the SAME slot
        self.slot_epoch: List[int] = [0] * n_slots

    # ------------------------------------------------------------ admission
    @property
    def queued(self) -> int:
        return len(self.queue)

    @property
    def active(self) -> int:
        return self.n_slots - len(self._free)

    def submit(self, req: Request) -> None:
        """FIFO admission with bounded-queue backpressure."""
        if len(self.queue) >= self.max_queue:
            raise QueueFullError(
                f"serving queue full ({self.max_queue} waiting, shed "
                f"policy '{self.shed_policy}'); retry later or raise "
                "--max-inflight/max_queue",
                queued=len(self.queue), active=self.active)
        if req.prompt_len + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + "
                f"max_new_tokens {req.max_new_tokens} exceeds the decode "
                f"ring capacity {self.max_len} (--max-decode-len)")
        # max supported context (ISSUE 12 satellite): the position table
        # bounds decodable length below the ring/pool capacity — reject
        # at admission, naming the limit, instead of the old
        # warn-and-clamp at engine construction
        if self.max_context is not None and \
                req.prompt_len + req.max_new_tokens > self.max_context:
            raise ContextOverflowError(
                f"request {req.rid}: prompt {req.prompt_len} + "
                f"max_new_tokens {req.max_new_tokens} exceeds the max "
                f"supported context {self.max_context} (position "
                "embedding table limit; build the model with a longer "
                "seq_len or lower max_new_tokens)",
                queued=len(self.queue), active=self.active)
        # a request the whole pool cannot hold would deadlock admission —
        # refuse it at submit, like the ring-capacity wall above
        if self.allocator is not None:
            need = self.allocator.blocks_needed(
                req.prompt_len + req.max_new_tokens)
            if need > self.allocator.n_usable:
                raise ValueError(
                    f"request {req.rid}: needs {need} KV blocks but the "
                    f"pool has {self.allocator.n_usable} (raise "
                    "--kv-pool-blocks or --kv-block-size)")
        # fail HERE, not after next_action() already claimed a slot: a
        # prompt no bucket covers must never corrupt the slot pool.
        # effective_len (prompt + committed tokens) is what the prefill
        # actually feeds — a drained quarantine-retry resubmitted to a
        # narrower scheduler must be refused at submit too
        bucket_for(req.effective_len, self.buckets)
        req.submit_ms = float(self.clock())
        self.queue.append(req)
        self.queue_depth_hwm = max(self.queue_depth_hwm, len(self.queue))
        if self.rt.enabled:
            self.rt.note(req.rid, "submit", req.submit_ms,
                         prompt_len=req.prompt_len,
                         max_new=req.max_new_tokens,
                         deadline_ms=req.deadline_ms,
                         replica=self.replica_idx)

    # ------------------------------------------------------------ scheduling
    def _admit_head(self):
        """Admit the head-of-queue request into a free slot with
        prefix-aware block accounting (ISSUE 14). Returns the classic
        ``("prefill", ...)`` action, the string ``"chunked"`` when the
        request entered the chunk-prefill path (admission bookkeeping
        only — action selection continues), or None when the pool cannot
        hold it yet (admission waits; decode continues).

        The trie walk maps the longest cached prefix (>= one full block)
        into the new slot's block table with zero prefill compute; only
        the suffix is prefilled. A hit whose boundary falls inside a
        shared block schedules a copy-on-write clone (``pending_cow``):
        the tail block is cloned into a freshly-allocated block before
        the first divergent write, so the sharer's rows are never
        perturbed."""
        req = self.queue[0]
        eff = req.effective_len
        match_blocks: List[int] = []
        match_t = 0
        if self.allocator is not None:
            alc = self.allocator
            if self.prefix is not None:
                # never match the full prompt: the final token's forward
                # pass is what produces the next-token logits admission
                # needs, so >= 1 token always prefills
                match_blocks, match_t = self.prefix.match(
                    req.current_prompt(), cap=eff - 1)
            # worst-case extent: the ORIGINAL prompt + the total token
            # cap (generated tokens count toward max_new_tokens, so a
            # quarantine retry's committed tokens are already inside it)
            need_total = alc.blocks_needed(
                req.prompt_len + req.max_new_tokens)
            partial = match_t % alc.block_size != 0
            fresh_needed = need_total - len(match_blocks) + (1 if partial
                                                            else 0)
            if match_blocks:
                # pin the matched nodes before any eviction can run
                alc.share(match_blocks)
            fresh = alc.alloc(fresh_needed)
            if fresh is None and self.prefix is not None:
                # pool pressure: evict LRU unreferenced trie nodes and
                # retry — cached prefixes are a performance loan, never
                # a reason to starve admission
                if self.prefix.evict(fresh_needed - len(alc.free_blocks)):
                    fresh = alc.alloc(fresh_needed)
            if fresh is None:
                if match_blocks:
                    alc.free(match_blocks)  # drop the pins; stay queued
                return None
            if partial:
                # the shared tail block will be cloned into fresh[0]
                # before the first suffix write (engine-side donated
                # jit); the share on src is held until the clone lands
                req.pending_cow = (match_blocks[-1], fresh[0])
                req.kv_blocks = match_blocks[:-1] + [fresh[0]] + fresh[1:]
            else:
                req.pending_cow = None
                req.kv_blocks = match_blocks + fresh
        req.prefix_hit_tokens = match_t
        req.prefill_pos = match_t
        req.prefill_target = eff
        req.chunk_shape = 0
        self.queue.popleft()
        slot = self._free.popleft()
        self.slots[slot] = req
        self.admitted += 1
        if self.rt.enabled:
            self.rt.note(req.rid, "admit", float(self.clock()),
                         slot=slot, hit=match_t,
                         cow=req.pending_cow is not None,
                         replica=self.replica_idx)
        if match_t:
            self.prefix_hits += 1
            self.prefix_tokens_reused += match_t
        suffix = eff - match_t
        if match_t > 0 or (self.chunk_tokens and
                           suffix > self.chunk_tokens):
            # chunk path: the suffix runs through the chunk-prefill
            # program — chunk_tokens-wide steps when chunking is on, one
            # bucket-shaped chunk otherwise. Compiled shape floor 2: a
            # 1-row projection lowers as a matvec whose accumulation
            # differs from the GEMM's by ~1 ulp, one more way for a
            # cached stream to part from the cold one at a near tie.
            req.chunk_shape = max(
                2, self.chunk_tokens or bucket_for(suffix, self.buckets))
            self._chunk_turn = True
            return "chunked"
        req.prefill_pos = 0  # classic one-shot: the engine marks
        # completion (prefill_pos = target) only after the slot write
        return ("prefill", req, slot, bucket_for(eff, self.buckets))

    def next_action(self):
        """("prefill", request, slot, bucket_len) when a request can be
        admitted into a free slot — prefill takes priority so freed
        capacity never idles while work queues; ("prefill_chunk",
        request, slot, start, n_tokens, chunk_shape) for one chunk of an
        in-progress chunked/suffix prefill, alternating with ("decode",
        [(slot, request), ...]) over the decodable in-flight slots so a
        long prompt never head-of-line-blocks the continuous batch; else
        None. While ``draining`` (graceful SIGTERM shutdown) admission
        stops: in-progress prefills and decodes still run so in-flight
        requests finish, and the queue is left intact for the engine to
        hand back."""
        while self.queue and self._free and not self.draining:
            act = self._admit_head()
            if act is None:
                break  # pool pressure: decode on, recycling frees blocks
            if act != "chunked":
                return act
        chunking = [(i, r) for i, r in enumerate(self.slots)
                    if r is not None and r.prefilling]
        live = [(i, r) for i, r in enumerate(self.slots)
                if r is not None and not r.prefilling]
        if chunking and (self._chunk_turn or not live):
            slot, req = chunking[0]  # lowest slot — deterministic
            self._chunk_turn = False  # a decode turn comes next
            n = min(req.chunk_shape, req.prefill_target - req.prefill_pos)
            return ("prefill_chunk", req, slot, req.prefill_pos, n,
                    req.chunk_shape)
        if live:
            self._chunk_turn = True
            return ("decode", live)
        return None

    def chunk_done(self, slot: int, n_tokens: int) -> bool:
        """Record one completed prefill chunk for the request in
        ``slot``; returns True when its whole effective prompt is now in
        the pool (the engine then samples the first token and arms the
        slot for decode)."""
        req = self.slots[slot]
        assert req is not None, f"chunk for empty slot {slot}"
        req.prefill_pos += int(n_tokens)
        return req.prefill_pos >= req.prefill_target

    def release_cow(self, req: Request) -> None:
        """The engine's COW clone landed: drop the admission-held share
        on the source block (the clone in the request's table owns the
        divergent continuation now)."""
        if req.pending_cow is not None and self.allocator is not None:
            self.allocator.free([req.pending_cow[0]])
        req.pending_cow = None

    def commit_token(self, slot: int, token: int) -> bool:
        """Record one generated token for the request in ``slot``; returns
        True when the request finished (EOS or length) and the slot was
        recycled."""
        req = self.slots[slot]
        assert req is not None, f"decode token for empty slot {slot}"
        req.generated.append(int(token))
        # the first-token (TTFT) stamp lands HERE, at the commit point —
        # not in the engine's prefill branches. Any admission path that
        # commits its first token without a classic prefill step (a
        # zero-prefill full-prefix hit, a hedge twin resuming a copied
        # stream, a decode-path first commit) still gets stamped; a
        # migrated request keeps the stamp from its original commit.
        if not req.first_token_ms:
            req.first_token_ms = float(self.clock())
        if self.rt.enabled:
            self.rt.note(req.rid, "token", float(self.clock()),
                         occ=self.n_slots - len(self._free),
                         replica=self.replica_idx)
        if self.on_commit is not None:
            self.on_commit(req)
        if req.eos_id is not None and int(token) == int(req.eos_id):
            return self._finish(slot, "eos")
        if len(req.generated) >= req.max_new_tokens:
            return self._finish(slot, "length")
        return False

    def _release_blocks(self, req: Request, adopt: bool = True) -> None:
        """The ONE choke point returning a request's pool blocks to the
        allocator — every slot-freeing path (finish, evict, quarantine,
        hedge cancellation) funnels through it so a block can never leak
        or double-free. ISSUE 14: prefix-trie retention ALSO happens
        here — a fully-prefilled request's prompt blocks (including the
        partial tail, the copy-on-write sharing site) are adopted into
        the radix tree before the request's own references drop, so the
        cached KV outlives the request and the next shared-prefix
        admission pays no prefill. ``adopt=False`` on quarantine /
        decode-fault paths: suspected-poisoned KV must never enter the
        cache."""
        if self.allocator is not None:
            if req.pending_cow is not None:
                # the COW clone never ran (released before the first
                # suffix chunk): drop the admission-held source share
                self.allocator.free([req.pending_cow[0]])
                req.pending_cow = None
            if req.kv_blocks:
                if (adopt and self.prefix is not None
                        and req.prefill_target > 0
                        and req.prefill_pos >= req.prefill_target):
                    self.prefix.insert(
                        req.current_prompt()[:req.prefill_pos],
                        req.kv_blocks)
                elif not adopt and self.prefix is not None:
                    # poison-suspect release: the decode poisoning NaN'd
                    # this request's blocks IN PLACE — including any
                    # prompt blocks the trie eagerly cached at prefill
                    # completion. Purge them, or the victim's own retry
                    # re-matches its poisoned prefix (never recovering)
                    # and future shared-prefix admissions are served NaN
                    # KV.
                    self.prefix.invalidate(req.kv_blocks)
                self.allocator.free(req.kv_blocks)
        req.kv_blocks = []

    def _finish(self, slot: int, reason: str,
                outcome: str = "ok") -> bool:
        req = self.slots[slot]
        req.done = True
        req.finish_reason = reason
        req.outcome = outcome
        req.finish_ms = float(self.clock())
        if self.rt.enabled:
            self.rt.finish(req.rid, req.finish_ms, outcome,
                           reason=reason,
                           new_tokens=len(req.generated),
                           replica=self.replica_idx)
        self._release_blocks(req, adopt=outcome != "decode_fault")
        self.finished.append(req)
        self.slots[slot] = None
        self._free.append(slot)
        self.slot_epoch[slot] += 1
        self.recycled += 1
        if self.on_slot_freed is not None:
            self.on_slot_freed(slot)
        return True

    # ---------------------------------------------------------- resilience
    # ISSUE 9: the engine's deadline sweeps, decode-health quarantine and
    # graceful drain manipulate the slot pool through these — slot-state
    # invariants (one request per slot, freed slots fully re-prefilled
    # before any read) stay enforced in ONE place.
    def evict(self, slot: int, outcome: str) -> Request:
        """Terminate the request in ``slot`` with a failure ``outcome``
        (deadline_exceeded | decode_fault | preempted) and recycle the
        slot. The evicted request is finished — it lands in ``finished``
        with ``outcome`` set, never silently dropped."""
        req = self.slots[slot]
        assert req is not None, f"evict of empty slot {slot}"
        self.evicted += 1
        self._finish(slot, outcome, outcome=outcome)
        return req

    def drop_queued(self, req: Request, outcome: str) -> None:
        """Remove a still-queued request (it never held a slot) with a
        terminal ``outcome`` — the admission-time half of deadline
        enforcement."""
        if not remove_by_identity(self.queue, req):
            raise ValueError(f"request rid={req.rid} is not queued")
        req.done = True
        req.finish_reason = outcome
        req.outcome = outcome
        req.finish_ms = float(self.clock())
        if self.rt.enabled:
            self.rt.finish(req.rid, req.finish_ms, outcome,
                           reason=outcome,
                           new_tokens=len(req.generated),
                           replica=self.replica_idx)
        self._release_blocks(req)  # defensive: queued requests hold none
        self.finished.append(req)

    def quarantine(self, slot: int) -> Request:
        """Pull a decode-poisoned request out of ``slot`` for a retry on a
        fresh slot: the slot returns to the BACK of the free pool (so the
        retry prefers a different slot when one is available — its rows
        are fully overwritten by the next prefill either way) and the
        request re-enters the queue at the FRONT, keeping its committed
        tokens (``current_prompt`` re-prefills prompt + generated)."""
        req = self.slots[slot]
        assert req is not None, f"quarantine of empty slot {slot}"
        # adopt=False: this slot's KV is poison-suspect — it must never
        # enter the prefix cache (a poisoned trie would serve NaN KV to
        # every future shared-prefix admission)
        self._release_blocks(req, adopt=False)
        self.slots[slot] = None
        self._free.append(slot)
        self.slot_epoch[slot] += 1
        self.quarantined += 1
        if self.rt.enabled:
            self.rt.note(req.rid, "quarantine", float(self.clock()),
                         slot=slot, replica=self.replica_idx)
        self.queue.appendleft(req)
        if self.on_slot_freed is not None:
            self.on_slot_freed(slot)
        return req

    def cancel_slot(self, slot: int) -> Request:
        """Hedge-loss cancellation (ISSUE 11, serving/fleet.py): free the
        slot WITHOUT a terminal outcome and WITHOUT a ``finished`` entry —
        the cancelled copy is accounted by its winning hedge twin, so a
        ledger entry here would double-count the request. The slot's
        cache rows go stale exactly like an eviction's; the next prefill
        fully overwrites them before any read (the standing slot-pool
        invariant)."""
        req = self.slots[slot]
        assert req is not None, f"cancel of empty slot {slot}"
        self._release_blocks(req)
        self.slots[slot] = None
        self._free.append(slot)
        self.slot_epoch[slot] += 1
        self.cancelled += 1
        if self.on_slot_freed is not None:
            self.on_slot_freed(slot)
        return req

    def cancel_queued(self, req: Request) -> None:
        """Hedge-loss cancellation for a copy that never held a slot:
        identity-based removal from the queue, no ledger entry."""
        if not remove_by_identity(self.queue, req):
            raise ValueError(f"request rid={req.rid} is not queued")
        self.cancelled += 1

    def remove_finished(self, req: Request) -> bool:
        """Strike a request from the ``finished`` ledger (identity-based):
        the hedge loser may complete in the same router tick its twin
        wins, and exactly-one-outcome accounting then requires the
        loser's entry withdrawn. Returns True when an entry was
        removed."""
        for i, q in enumerate(self.finished):
            if q is req:
                del self.finished[i]
                self.cancelled += 1
                return True
        return False

    def pop_queued(self) -> List[Request]:
        """Drain handoff: hand back every still-queued request (outcome
        ``preempted``) for re-submission to another replica — they never
        started, so their state is clean."""
        out = list(self.queue)
        self.queue.clear()
        for r in out:
            r.outcome = "preempted"
        return out
