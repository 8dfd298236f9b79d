"""ServingEngine: the inference engine over a compiled FFModel.

This graduates ``model.predict``'s per-batch forward loop into a real
serving path (ISSUE 6; the reference snapshot's only inference artifact is
an *incomplete* Triton prototype, triton/README.md): prefill/decode split
with a first-class KV-cache pytree (serving/kvcache.py), Orca-style
continuous batching over a fixed slot pool (serving/scheduler.py), greedy
and temperature/top-k sampling (the Pallas top-k kernel where eligible),
and obs wiring (prefill/decode/schedule tracer events + the StepTelemetry
``serving`` block).

Static shapes everywhere: ONE decode compile serves every request mix
(asserted via the jit cache size — ``decode_compiles``), and prefill
compiles once per length bucket. The decode-state layout on a real mesh is
a *searched* axis: ``serving.search.serving_search`` prices replica- vs
tensor-parallel decode (KV sharded over heads) with the simulator's memory
accounting, and ``elastic_replan`` re-runs that search mid-serve when the
device pool changes — the in-flight DecodeState survives the hop, so
generation continues bit-identically (PR 4/5 carry-over: re-search and
keep serving).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ffconst import OperatorType
from ..obs.builds import build_mark, built_since, enter, leave
from ..obs.trace import in_setup_span, setup_span, span, step_span
from .kvcache import DecodeState, update_slot_entry
from .scheduler import (ContinuousBatchScheduler, Request, ServingRejection,
                        bucket_for, default_buckets)

def position_context_bound(executor, max_len: int) -> int:
    """The max supported context of a compiled autoregressive model:
    ``max_len`` bounded by the position-embedding table wherever one
    exists — positions beyond the table would CLAMP under jit
    (``jnp.take``) and silently reuse the last row's embedding. ONE
    implementation for every consumer (the serving engine's admission
    rejection AND the speculative decoder's scoring bound — ISSUE 12
    removed the old warn-and-clamp precisely so nothing aliases rows)."""
    bound = int(max_len)
    pos_guids = set(executor._position_const_guids())
    for node in executor.pcg.compute_nodes():
        if node.op.op_type == OperatorType.OP_EMBEDDING and any(
                g in pos_guids for g, _ in node.inputs):
            entries = int(node.op.attrs.get("num_entries", 0))
            if entries:
                bound = min(bound, entries)
    return bound


# per-token latency reservoir bound (ISSUE 9 satellite): the old unbounded
# list grew one float per token for the life of the serve loop — a
# traffic-serving process leaks. p50/p99 are computed over a sliding
# window of the most recent TOKEN_WALL_WINDOW walls instead (plenty for a
# stable tail estimate; the summary fields are unchanged).
TOKEN_WALL_WINDOW = 8192
# what one tick() can do: the ``kind`` of its ``serve_tick`` span
TICK_KINDS = ("prefill", "prefill_chunk", "decode", "idle")


@dataclasses.dataclass
class ServingStats:
    """Host-side counters of one serve() run — the bench serving_leg and
    the StepTelemetry ``serving`` block read these."""

    requests_served: int = 0
    tokens_generated: int = 0
    prefills: int = 0
    decode_steps: int = 0
    queue_depth_hwm: int = 0
    wall_s: float = 0.0
    # per-token latency distribution: decode tokens carry their step wall,
    # first tokens their prefill wall. Bounded ring (TOKEN_WALL_WINDOW):
    # percentiles describe the trailing window, not the whole run
    token_walls_s: Deque[float] = dataclasses.field(
        default_factory=lambda: deque(maxlen=TOKEN_WALL_WINDOW))
    # resilience ledger (ISSUE 9): every request leaves the system under
    # exactly one outcome (ok | deadline_exceeded | shed | decode_fault |
    # preempted); the counters mirror serving/resilience.py's events
    outcomes: Dict[str, int] = dataclasses.field(default_factory=dict)
    sheds: int = 0
    deadline_misses: int = 0
    quarantines: int = 0
    decode_retries: int = 0
    drains: int = 0
    replans: int = 0
    drained_returned: int = 0
    # decode HBM traffic accounting (ISSUE 12): analytic KV bytes the
    # decode attention reads, accumulated per step host-side: each live
    # slot's OCCUPIED blocks; bench's bytes-read/token column
    kv_bytes_read: int = 0
    # the flash_decode kernel's steps, counted in the same loop: the
    # steps of ONE layer's call, summed over decode steps — a grid step
    # a slot and a loop iteration a live key tile (an int8 pool's grid
    # keeps the tile axis: n_slots x the tiles of a table row,
    # kernels/flash_decode.py) — and those of them that fold a key of a
    # live slot; decode_grid_live_share() is their ratio — how much of
    # the kernel's stepping has work
    kv_tiles_grid: int = 0
    kv_tiles_live: int = 0
    # prefix cache + chunked prefill ledger (ISSUE 14,
    # serving/prefix.py): admissions that mapped a cached prefix, the
    # prompt tokens whose prefill compute was skipped vs actually
    # computed, trie evictions this run, and chunk-prefill dispatches —
    # the StepTelemetry ``serving_prefix`` block and the bench
    # shared-prompt sub-leg read these
    prefix_hits: int = 0
    prefix_tokens_reused: int = 0
    prefill_tokens_computed: int = 0
    cache_evictions: int = 0
    chunked_prefills: int = 0
    # speculative decoding (serving/speculative.py): per-round drafter
    # proposal/acceptance ledger; acceptance_rate feeds the bench column
    # and keeps the EWMA admission cost model honest
    spec_rounds: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0
    # host-overhead accounting (ISSUE 16, ROADMAP item 5): each tick's
    # wall splits into dispatch (tick start -> device call issued: action
    # selection, admission, chaos hooks), device (the blocking
    # prefill/chunk/decode call + result fetch), and bookkeeping (commit
    # loop, stats, trie inserts). host_overhead_fraction() is THE
    # measured baseline the async host runtime must beat — always-on
    # plain-float accumulation, it never touches the token streams
    host_dispatch_s: float = 0.0
    host_device_s: float = 0.0
    host_bookkeep_s: float = 0.0
    host_ticks: int = 0
    # async double-buffered runtime (ISSUE 17): host work performed
    # WHILE a device step was already in flight — off the critical path,
    # so it joins the denominator but never the numerator of
    # host_overhead_fraction (the sync loop leaves it 0, preserving the
    # PR 16 accounting identity). host_syncs counts BLOCKING host
    # transfers through the one decode fetch choke point — the async
    # steady-state contract is <= 1 per committed decode step
    host_overlap_s: float = 0.0
    host_syncs: int = 0
    # the wall of every tick() by what the tick did (the ``kind`` of its
    # ``serve_tick`` span: prefill | prefill_chunk | decode | idle) and how
    # many there were — which kind of tick the time between two tokens went
    # to; plain adds, read as deltas over a window like the buckets above
    tick_wall_s_by_kind: Dict[str, float] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(TICK_KINDS, 0.0))
    ticks_by_kind: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(TICK_KINDS, 0))
    # sequence-parallel decode (ISSUE 18): mean per-step occupied KV
    # bytes ONE shard chip holds — pool bytes at measured fill divided
    # by seq_shards. This is the recorded number behind the "KV provably
    # exceeds one chip" criterion: the bench asserts the undivided total
    # is above a real chip's HBM budget while this per-chip figure is
    # below it. Set at serve-loop finish; 0 until a decode step ran.
    kv_hbm_per_chip_bytes: int = 0
    # routed expert layers at decode shapes: counted on the device by
    # the decode step and fetched with its tokens (the sync loop in its
    # own tick; the async loop at the step's settle, a tick later) — the
    # (row, expert) pairs held here and the held experts that got at least one row, both summed
    # over layers and decode steps, and the fullest expert's rows over
    # its layer's mean in thousandths, the largest seen; the layer-steps
    # whose pairs fit the layer's row bound and took the bounded path
    # (ops/moe_ops.py) beside the layer-steps in all; the same two of the
    # prefill chunks (a chunk's counters stay on the device until its
    # prompt's last chunk fetches the first token)
    moe_pairs_here: int = 0
    moe_experts_live: int = 0
    moe_load_max_permille: int = 0
    moe_bounded_steps: int = 0
    moe_layer_steps: int = 0
    moe_chunk_bounded_steps: int = 0
    moe_chunk_layer_steps: int = 0
    # recurrent nodes (an LSTM carry, a state-space mixer's state, a gated
    # delta-rule mixer's matrix state a head): the bytes of slot-major
    # state the decode steps read plus wrote — every slot the program
    # stepped, live or free, at Op.slot_state_bytes a node — and the live
    # slots among them, both summed over decode steps
    recurrent_state_bytes: int = 0
    recurrent_slots_live: int = 0
    # that state as the chip RESTS it, all slots, once (no sum): the
    # leaves' bytes with the last dimension rounded up to 128 lanes and the
    # one before to a sublane tile — equal to n_slots x Op.slot_state_bytes
    # where nothing pads — and the most heads a row of it holds
    # (Op.slot_state_heads_a_row)
    recurrent_state_bytes_at_rest: int = 0
    state_heads_a_row: int = 0
    # latent-attention nodes: the pool rows the decode steps' latent reads
    # fold — each live slot's keys, a latent node's call — summed over
    # nodes and decode steps (times kvcache.latent_token_bytes: the bytes)
    latent_rows_read: int = 0
    # what the engine allocated for its decode state, by kind of cache,
    # once (no sum): "kv_pool" / "latent_pool" the paged pools of the
    # attention / latent-attention nodes, "recurrent_state" the slot-major
    # entries — the two kinds a hybrid graph holds side by side
    cache_bytes_by_kind: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # the one-shot prefills' rows as the program computed them (the
    # bucket's, padding included: a chunked recurrence and the matmuls pay
    # a padded row what they pay a real one) and the real ones among them
    prefill_rows: int = 0
    prefill_rows_real: int = 0
    # the programs built between start_serve and finish() (obs/builds.py),
    # their seconds tracing, lowering, loading and compiling, and how many
    # by name; set by finish(). Not zero after a warm-up: the process
    # recompiled under traffic, and built_by_name says which program
    programs_built: int = 0
    build_s: float = 0.0
    built_by_name: Dict[str, int] = dataclasses.field(default_factory=dict)

    def record_token(self, wall_s: float) -> None:
        self.token_walls_s.append(wall_s)

    def kv_bytes_per_token(self) -> Optional[float]:
        if not self.tokens_generated or not self.kv_bytes_read:
            return None
        return self.kv_bytes_read / self.tokens_generated

    def decode_grid_live_share(self) -> Optional[float]:
        """Live tiles over the steps of the decode attention kernel
        (slot steps + live tiles) — the fixed-cost term a serving
        search needs beside the bytes. None before a decode step ran."""
        if not self.kv_tiles_grid:
            return None
        return self.kv_tiles_live / self.kv_tiles_grid

    def acceptance_rate(self) -> Optional[float]:
        if not self.spec_proposed:
            return None
        return self.spec_accepted / self.spec_proposed

    def prefix_reuse_rate(self) -> Optional[float]:
        """Fraction of prefill tokens served from the prefix cache —
        the measured hit rate ``serving_search(prefill_reuse=)`` prices
        with. None before any prefill ran."""
        total = self.prefix_tokens_reused + self.prefill_tokens_computed
        if not total:
            return None
        return self.prefix_tokens_reused / total

    def count_outcome(self, outcome: str, n: int = 1) -> None:
        if n:
            self.outcomes[outcome] = self.outcomes.get(outcome, 0) + int(n)

    def tokens_per_s(self) -> float:
        return self.tokens_generated / self.wall_s if self.wall_s > 0 else 0.0

    def batch_occupancy(self, n_slots: int) -> float:
        """Fraction of decode-slot-steps that produced a kept token — the
        continuous-batching utilization headline (1.0 = every slot busy
        every step). First tokens come from prefill, not a decode slot,
        so they stay out of the numerator."""
        denom = self.decode_steps * n_slots
        return max(self.tokens_generated - self.prefills, 0) / denom \
            if denom else 0.0

    def host_overhead_fraction(self) -> Optional[float]:
        """Fraction of the serve loop's tick wall spent on the host
        (dispatch + bookkeeping) rather than waiting on the device —
        ROADMAP item 5's headline number. None before any tick ran.
        Overlapped host work (ISSUE 17: bookkeeping performed while the
        next step was already in flight) extends the wall the loop
        covered without costing the device anything, so it counts in
        the denominator only."""
        total = self.host_dispatch_s + self.host_device_s + \
            self.host_bookkeep_s + self.host_overlap_s
        if total <= 0.0:
            return None
        return (self.host_dispatch_s + self.host_bookkeep_s) / total

    def p50_token_ms(self) -> Optional[float]:
        if not self.token_walls_s:
            return None
        return float(np.percentile(list(self.token_walls_s), 50) * 1e3)

    def p99_token_ms(self) -> Optional[float]:
        if not self.token_walls_s:
            return None
        return float(np.percentile(list(self.token_walls_s), 99) * 1e3)

    def summary(self) -> Dict[str, Any]:
        out = {
            "requests_served": self.requests_served,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "decode_steps": self.decode_steps,
            "queue_depth_hwm": self.queue_depth_hwm,
            "wall_s": round(self.wall_s, 4),
            "tokens_per_s": round(self.tokens_per_s(), 2),
            "programs_built": self.programs_built,
        }
        if self.programs_built:
            out["build_s"] = round(self.build_s, 4)
            out["by_name"] = dict(self.built_by_name)
        p50, p99 = self.p50_token_ms(), self.p99_token_ms()
        if p50 is not None:
            out["p50_token_ms"] = round(p50, 3)
            out["p99_token_ms"] = round(p99, 3)
        if self.outcomes:
            out["outcomes"] = dict(self.outcomes)
        for k in ("sheds", "deadline_misses", "quarantines",
                  "decode_retries", "drains", "replans",
                  "drained_returned", "spec_rounds"):
            v = getattr(self, k)
            if v:
                out[k] = v
        kvpt = self.kv_bytes_per_token()
        if kvpt is not None:
            out["kv_bytes_per_token"] = round(kvpt, 1)
        if self.kv_tiles_grid:
            out["kv_tiles_grid"] = self.kv_tiles_grid
            out["kv_tiles_live"] = self.kv_tiles_live
            out["decode_grid_live_share"] = round(
                self.decode_grid_live_share(), 4)
        acc = self.acceptance_rate()
        if acc is not None:
            out["spec_acceptance"] = round(acc, 4)
        for k in ("prefix_hits", "prefix_tokens_reused",
                  "prefill_tokens_computed", "cache_evictions",
                  "chunked_prefills"):
            v = getattr(self, k)
            if v:
                out[k] = v
        reuse = self.prefix_reuse_rate()
        if reuse:
            out["prefix_reuse_rate"] = round(reuse, 4)
        hof = self.host_overhead_fraction()
        if hof is not None:
            out["host_overhead_fraction"] = round(hof, 4)
        if self.host_syncs:
            out["host_syncs"] = self.host_syncs
        if self.kv_hbm_per_chip_bytes:
            out["kv_hbm_per_chip_bytes"] = self.kv_hbm_per_chip_bytes
        for k in ("moe_pairs_here", "moe_experts_live",
                  "moe_load_max_permille", "moe_bounded_steps",
                  "moe_layer_steps", "moe_chunk_bounded_steps",
                  "moe_chunk_layer_steps", "recurrent_state_bytes",
                  "recurrent_slots_live", "recurrent_state_bytes_at_rest",
                  "state_heads_a_row", "prefill_rows",
                  "prefill_rows_real", "latent_rows_read"):
            if getattr(self, k):
                out[k] = getattr(self, k)
        if self.cache_bytes_by_kind:
            out["cache_bytes_by_kind"] = dict(self.cache_bytes_by_kind)
        return out


class ServingEngine:
    """Inference engine over a compiled autoregressive FFModel.

    Requirements on the graph (validated at construction): causal
    self-attention (``multihead_attention(..., causal=True)``) and/or LSTM
    recurrence as the only sequence-stateful ops, a per-token final output
    ``(batch, seq, vocab)``, and — for :meth:`generate` — a single integer
    token input. models/gpt2.py and models/transformer.py's
    ``build_transformer_decoder`` qualify; bidirectional encoders do not
    (incremental decode is undefined for them, and the engine says so).
    """

    # the one KV layout and the one decode numerics path, kept as
    # constants because benchmark/drivers/serve.py records both in a
    # run's info (ROADMAP.md D12: drop them with that reader)
    kv_cache = "paged"
    exact_decode = False

    @in_setup_span("engine_build")
    def __init__(self, model, n_slots: Optional[int] = None,
                 max_decode_len: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 max_queue: int = 64,
                 eos_id: Optional[int] = None,
                 kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 prefix_cache: Optional[str] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 serve_loop: Optional[str] = None,
                 seq_shards: Optional[int] = None,
                 context_buckets: Optional[Sequence[int]] = None):
        assert model.executor is not None, "call model.compile() first"
        self.model = model
        self.executor = model.executor
        cfg = model.config
        self.n_slots = int(n_slots or getattr(cfg, "max_inflight", 8))
        self.max_decode_len = int(max_decode_len or
                                  getattr(cfg, "max_decode_len", 128))
        # the caller-requested value, so FFModel.generate's engine-cache
        # check can compare against what the caller ASKED for
        self.requested_max_decode_len = self.max_decode_len
        self.max_queue = max_queue
        self.eos_id = eos_id
        # serve-loop runtime (ISSUE 17, docs/serving.md "Async
        # runtime"): "sync" (default) blocks on each decode step's host
        # transfer before dispatching the next; "async" double-buffers —
        # step k+1 is enqueued on-device while step k's (tokens, ok)
        # transfer is in flight, commits land at transfer ARRIVAL. Both
        # run the same device programs; async must match sync
        # stream for stream (tier-1 pins it)
        self.serve_loop = str(serve_loop or
                              getattr(cfg, "serve_loop", "sync") or "sync")
        if self.serve_loop not in ("sync", "async"):
            raise ValueError(
                f"serve_loop must be 'sync' or 'async', got "
                f"{self.serve_loop!r}")
        # paged KV cache (ISSUE 12, docs/serving.md "Paged KV cache"):
        # one block pool per KV entry + per-slot block tables
        self.kv_block_size = int(kv_block_size or
                                 getattr(cfg, "kv_block_size", 16))
        self.kv_dtype = str(kv_dtype or getattr(cfg, "kv_dtype", "native"))
        kv_pool_blocks = int(kv_pool_blocks if kv_pool_blocks is not None
                             else getattr(cfg, "kv_pool_blocks", 0))
        from .kvcache import (KV_DTYPES, blocks_per_slot,
                              parse_context_buckets)

        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{self.kv_dtype!r}")
        # sequence-parallel decode (ISSUE 18, docs/decode_perf.md
        # "Sequence-parallel decode"): the gathered extent is scored as
        # seq_shards contiguous key segments merged by the flash segment
        # combine — a static trace-time choice that joins the decode jit
        # key. context_buckets routes admitted requests to the searched
        # per-bucket shard width (serving_search picks seq_shards per
        # bucket from the ICI closed forms).
        self.seq_shards = int(seq_shards if seq_shards is not None
                              else getattr(cfg, "seq_shards", 1) or 1)
        if self.seq_shards < 1:
            raise ValueError(
                f"seq_shards must be >= 1, got {self.seq_shards}")
        self.context_buckets = parse_context_buckets(
            context_buckets if context_buckets is not None
            else getattr(cfg, "context_buckets", "") or "")
        # prefix cache + chunked prefill (ISSUE 14, serving/prefix.py,
        # docs/serving.md "Prefix cache & chunked prefill"): the radix
        # trie defaults ON for attention-only graphs — in tier-1 its hit
        # path gives the cold path's streams; chunking is opt-in via
        # --prefill-chunk-tokens
        self.prefill_chunk_tokens = int(
            prefill_chunk_tokens if prefill_chunk_tokens is not None
            else getattr(cfg, "prefill_chunk_tokens", 0) or 0)
        prefix_mode = str(prefix_cache or
                          getattr(cfg, "prefix_cache", "on") or "on")
        if prefix_mode not in ("on", "off"):
            raise ValueError(
                f"prefix_cache must be 'on' or 'off', got {prefix_mode!r}")
        # max supported context: bounded by the position-embedding table
        # when it is shorter than the pool capacity; admission
        # REJECTS beyond it (the old warn-and-clamp is gone, ISSUE 12
        # satellite)
        self._validate_graph()
        recurrent = [f"{n.name}: {n.op.op_type.name}"
                     for n in self.executor.pcg.compute_nodes()
                     if n.op.slot_state_bytes() > 0]
        if recurrent:
            # a recurrent state (the LSTM carry, a state-space mixer's
            # state, a gated delta-rule mixer's matrix state a head) is a
            # summary, not per-token pool rows: there is no
            # block to share or chunk (ISSUE 14 scope — attention-only
            # stateful graphs; ROADMAP.md Reach R8 has what is missing)
            from ..ops.base import no_chunk_carry

            for asked, on in (
                    ("--prefill-chunk-tokens", self.prefill_chunk_tokens),
                    ("--prefix-cache on", prefix_cache == "on")):
                if on:
                    raise ValueError(no_chunk_carry(
                        recurrent[0], "its slot-major state", asked))
            prefix_mode = "off"
        self.max_context = position_context_bound(self.executor,
                                                  self.max_decode_len)
        self._prefix = None
        from .scheduler import BlockAllocator

        mb = blocks_per_slot(self.max_decode_len, self.kv_block_size)
        self.max_blocks_per_slot = mb
        # auto pool: full capacity (every slot at max_len) + the
        # garbage block — --kv-pool-blocks decouples occupancy from
        # max_len (admission then waits on FREE BLOCKS, not slots).
        # Chunked prefill adds one live chunk's worth of headroom
        # (the FF006 law: one max-context request PLUS one chunk)
        chunk_blocks = (-(-self.prefill_chunk_tokens //
                          self.kv_block_size)
                        if self.prefill_chunk_tokens else 0)
        self.kv_pool_blocks = kv_pool_blocks or (
            self.n_slots * mb + 1 + chunk_blocks)
        # ShardLint FF006 paged shape laws — statically, zero compile
        from ..analysis import (AnalysisReport, StaticAnalysisError,
                                check_paged_kv)

        import jax

        diags = check_paged_kv(
            self.executor.pcg,
            block_size=self.kv_block_size,
            pool_blocks=self.kv_pool_blocks,
            max_blocks_per_slot=mb,
            max_context=self.max_context,
            prefill_chunk_tokens=self.prefill_chunk_tokens,
            seq_shards=self.seq_shards,
            n_devices=jax.device_count(),
            context_buckets=self.context_buckets)
        if diags:
            raise StaticAnalysisError(
                AnalysisReport(diagnostics=diags, checked=("FF006",)),
                context="paged KV configuration")
        self.block_allocator = BlockAllocator(self.kv_pool_blocks,
                                              self.kv_block_size)
        if prefix_mode == "on":
            from .prefix import PrefixCache

            self._prefix = PrefixCache(
                self.block_allocator, self.kv_block_size,
                max_blocks=int(
                    prefix_cache_blocks
                    if prefix_cache_blocks is not None
                    else getattr(cfg, "prefix_cache_blocks", 0) or 0))
        self.buckets = tuple(buckets) if buckets else \
            default_buckets(self.max_decode_len)
        self.state: Optional[DecodeState] = None
        self._last_tokens = None  # (n_slots, 1) device int32
        self._write_slot_fn = None
        self._clear_slot_fn = None
        # filled by _ensure_state: which cache entries live in the block
        # pool (vs slot-major) — the one pagedness classification
        self._paged_entry_names: set = set()
        self._samplers: Dict = {}
        self.stats = ServingStats()
        self.plan = None  # ServingPlan from the last (re)search, if any
        self._search_sim = None  # warm Simulator for elastic re-search
        # resilience (ISSUE 9, serving/resilience.py): the admission
        # controller's EWMA cost model lives on the ENGINE so it warms
        # across serve() runs; resilience_clock (ms) overrides the time
        # base of every deadline/drain decision (deterministic tests);
        # drained_requests holds the queued requests a graceful SIGTERM
        # drain handed back for re-submission
        from .resilience import AdmissionController

        self.admission = AdmissionController()
        self.resilience_clock = None
        self.drained_requests: List[Request] = []
        self._last_guard = False
        # a routed graph's counters of the newest decode step, on the device
        self._step_counters = None
        # resilience state accumulated by pre-serve admit() calls (shed
        # counts, deadline arming) — consumed by the next serve() so the
        # ledger never loses events to a throwaway policy object
        self._pending_resilience = None

    # ------------------------------------------------------------ validation
    def _validate_graph(self) -> None:
        pcg = self.executor.pcg
        # ShardLint pre-serve pass (ISSUE 7): the FF005 serving-state
        # reachability rule promotes the fused-stateful runtime refusal
        # into a static diagnostic with a rule ID and fix hint. ONE
        # detection implementation either way — with --static-analysis
        # off the same checker still backstops the engine (it must never
        # decode history-free garbage), just phrased as the plain
        # runtime refusal without a rule ID.
        from ..analysis import check_serving_graph

        diags = check_serving_graph(pcg)
        if diags:
            if (getattr(self.model.config, "static_analysis", "on")
                    or "on") != "off":
                raise NotImplementedError(
                    "; ".join(d.format_line() for d in diags))
            d = diags[0]
            raise NotImplementedError(
                f"{d.node}: {d.message}; recompile without --fusion "
                "to serve")
        final = pcg.nodes[self.executor.final_guid]
        out = final.out_shapes[self.executor.final_out_idx]
        if len(out) != 3:
            raise ValueError(
                f"serving needs a per-token final output (batch, seq, "
                f"vocab); {final.name} produces {out} — pooled/classifier "
                "heads cannot be decoded token by token")
        for node in pcg.compute_nodes():
            ot = node.op.op_type
            if ot == OperatorType.OP_SDPA:
                raise NotImplementedError(
                    f"{node.name}: OP_SDPA graphs (torch frontend) have no "
                    "serving decode path yet; build with "
                    "multihead_attention(causal=True)")
            # fused regions hiding stateful/position sub-ops were already
            # refused above via analysis.check_serving_graph (FF005) —
            # the single implementation of that judgement
            if ot == OperatorType.OP_MULTIHEAD_ATTENTION:
                if not node.op.attrs.get("causal", False):
                    raise ValueError(
                        f"{node.name}: serving requires causal=True "
                        "attention (bidirectional attention cannot be "
                        "decoded incrementally)")
                if len({g for g, _ in node.inputs}) != 1:
                    raise ValueError(
                        f"{node.name}: serving decode supports "
                        "self-attention only (q, k, v from one producer)")
            # NOTE: the position-table context bound lives in
            # position_context_bound() — __init__ records it as
            # self.max_context and scheduler.submit rejects any request
            # whose prompt + max_new exceeds it (typed ServingRejection
            # naming the max supported context; ISSUE 12 satellite
            # replacing the old warn-and-clamp)

    def _token_input_check(self) -> None:
        ins = self.executor.pcg.input_nodes()
        from ..ffconst import DataType

        if len(ins) != 1 or ins[0].op.attrs.get("dtype") not in (
                DataType.DT_INT32, DataType.DT_INT64):
            raise ValueError(
                "generate() needs a single integer token input; this graph "
                f"has {len(ins)} input(s) — drive prefill/decode steps "
                "directly (executor.make_prefill_step/make_decode_step) "
                "for custom input schemes")

    # -------------------------------------------------------------- obs hooks
    def _tracer(self):
        return self.model._obs_tracer()

    @property
    def decode_compiles(self) -> Optional[int]:
        """Entries in the decode step's jit cache — the recompile-free
        contract is exactly ``== 1`` after warmup (asserted in tier-1).
        The key includes the guard mode of the last serve (guarded and
        unguarded decode are distinct programs, each with its own
        one-entry contract)."""
        fn = self.executor._serving_jits.get(
            ("decode", self.max_decode_len, self._last_guard,
             self.kv_block_size, self.kv_dtype, self.seq_shards))
        if fn is None:
            return None
        try:
            return int(fn._cache_size())
        except Exception:
            return None

    # ------------------------------------------------------------ device fns
    def _decode_fn(self, guard: bool = False):
        return self.executor.make_decode_step(
            self.max_decode_len, self.kv_block_size, guard=guard,
            kv_dtype=self.kv_dtype, seq_shards=self.seq_shards)

    def _prefill_fn(self, bucket: int):
        return self.executor.make_prefill_step(bucket, self.max_decode_len)

    def _write_slot_program(self):
        """The jitted slot writer ``(state, last, cache, slot, length,
        token, table_row) -> (state, last)``, state and last donated:
        KV entries are scattered into the table row's pool blocks
        (quantizing for int8 layouts), other stateful entries land
        slot-major, and the slot's cursor, table row and pending first
        token are set. Every index is traced: one compile."""
        if self._write_slot_fn is None:
            from ..execution.executor import named_jit
            from .kvcache import scatter_prefill_kv

            bs = self.kv_block_size
            # the ONE pagedness decision: the entry-name set recorded by
            # _ensure_state when it built the pool (a second structural
            # classifier here could silently disagree for a future
            # stateful op's cache shape)
            kv_names = self._paged_entry_names

            def write(state, last, cache, slot, length, token, table_row):
                caches = {}
                for name in state.caches:
                    if name in kv_names:
                        caches[name] = scatter_prefill_kv(
                            state.caches[name], cache[name], table_row,
                            bs)
                    else:
                        caches[name] = update_slot_entry(
                            state.caches[name], cache[name], slot)
                lengths = state.lengths.at[slot].set(length)
                tables = state.block_tables.at[slot].set(table_row)
                last = last.at[slot, 0].set(token)
                return DecodeState(caches=caches, lengths=lengths,
                                   block_tables=tables), last

            self._write_slot_fn = named_jit("write", write,
                                            donate_argnums=(0, 1))
        return self._write_slot_fn

    def _write_slot(self, cache, slot: int, length: int, token,
                    table_row) -> None:
        """Insert one prefilled request into the decode batch: cache rows,
        length cursor and the pending first token — one jitted scatter
        (``_write_slot_program``), slot/length/token and ``table_row``
        traced, so neither slot nor block choice recompiles."""
        import jax.numpy as jnp

        self.state, self._last_tokens = self._write_slot_program()(
            self.state, self._last_tokens, cache,
            jnp.int32(slot), jnp.int32(length), jnp.int32(token),
            jnp.asarray(table_row, jnp.int32))

    def _clear_slot_tables(self, slot: int) -> None:
        """Reset a freed slot's device-side block-table row (all GARBAGE)
        and length cursor (0). Fired by the scheduler on EVERY
        slot-freeing path: without it the freed slot's stale row keeps
        scattering its discarded per-step tokens into blocks the
        allocator may already have handed to a NEW request in a
        different slot — KV corruption with no error (the garbage-block
        safety argument only covers never-admitted slots). One tiny
        donated jit; slot traced, so recycling never recompiles."""
        import jax
        import jax.numpy as jnp

        from .resilience import state_buffers_lost

        if self.state is None or state_buffers_lost(self.state):
            return  # no pool (or a dead one about to be rebuilt)
        if self._clear_slot_fn is None:
            def clear(state, slot):
                return DecodeState(
                    caches=state.caches,
                    lengths=state.lengths.at[slot].set(0),
                    block_tables=state.block_tables.at[slot].set(0))

            self._clear_slot_fn = jax.jit(clear, donate_argnums=(0,))
        self.state = self._clear_slot_fn(self.state, jnp.int32(slot))

    def _table_row_for(self, req) -> np.ndarray:
        """The (max_blocks_per_slot,) int32 block-table row for an
        admitted request: its allocated blocks, GARBAGE_BLOCK beyond."""
        row = np.zeros((self.max_blocks_per_slot,), np.int32)
        if req.kv_blocks:
            row[:len(req.kv_blocks)] = req.kv_blocks
        return row

    # ------------------------------------------------- prefix cache (ISSUE 14)
    def _chunk_fn(self, chunk_shape: int):
        return self.executor.make_chunk_prefill_step(
            int(chunk_shape), self.max_decode_len, self.kv_block_size,
            self.kv_dtype)

    def _cow_clone_program(self):
        """The jitted copy-on-write clone ``(state, src, dst) -> state``,
        state donated, block ids traced — the ``_clear_slot_tables``
        idiom, so COW never recompiles."""
        if getattr(self, "_cow_clone_fn", None) is None:
            import jax

            from .kvcache import clone_kv_block

            paged_names = set(self._paged_entry_names)

            def clone(state, src, dst):
                caches = {
                    name: clone_kv_block(entry, src, dst)
                    if name in paged_names else entry
                    for name, entry in state.caches.items()}
                return DecodeState(caches=caches, lengths=state.lengths,
                                   block_tables=state.block_tables)

            self._cow_clone_fn = jax.jit(clone, donate_argnums=(0,))
        return self._cow_clone_fn

    def _cow_clone(self, src: int, dst: int) -> None:
        """Copy-on-write clone: duplicate pool block ``src`` into the
        freshly-allocated ``dst`` across every paged cache entry (int8
        scale arrays included) before the cloner's first divergent
        write. The sharer's block is read, never written: its rows stay
        bitwise untouched (tests/test_prefix_cache.py pins the
        isolation)."""
        import jax.numpy as jnp

        if self.state is None:
            return  # no pool yet: nothing to clone from
        self.state = self._cow_clone_program()(
            self.state, jnp.int32(src), jnp.int32(dst))

    def _set_slot_meta(self, slot: int, length: int, token: int,
                       table_row: np.ndarray) -> None:
        """Arm a chunk-prefilled slot for decode: set its device-side
        length cursor, block-table row and pending first token — the
        pool rows were already written by the chunks, so this is the
        ``_write_slot`` tail without the pool scatter. Traced indices:
        no recompiles."""
        import jax
        import jax.numpy as jnp

        if getattr(self, "_set_slot_meta_fn", None) is None:
            def meta(state, last, slot, length, token, table_row):
                return (DecodeState(caches=state.caches,
                                    lengths=state.lengths.at[slot].set(
                                        length),
                                    block_tables=state.block_tables.at[
                                        slot].set(table_row)),
                        last.at[slot, 0].set(token))

            self._set_slot_meta_fn = jax.jit(meta, donate_argnums=(0, 1))
        self.state, self._last_tokens = self._set_slot_meta_fn(
            self.state, self._last_tokens, jnp.int32(slot),
            jnp.int32(length), jnp.int32(token),
            jnp.asarray(table_row, jnp.int32))

    def _ensure_state_bootstrap(self) -> None:
        """A chunk action needs the pool, but the pool structure comes
        from a prefill cache and none has run yet (first-ever admission
        went straight to the chunk path). The pool needs the cache's
        STRUCTURE alone (``_ensure_state`` builds zeroed pools from it):
        trace the smallest bucket's prefill for its shapes and hand over
        zeros. The program itself is not run — a long-document engine's
        one bucket covers its longest prompt, no admission of a chunked
        engine uses it, and at that length it may not fit the chip."""
        import jax
        import jax.numpy as jnp

        if self.state is not None:
            return
        with setup_span("kv_pool_alloc"):
            b0 = self.buckets[0]
            shapes = jax.eval_shape(
                self._prefill_fn(b0), self.model.params,
                [jax.ShapeDtypeStruct((1, b0), jnp.int32)],
                jax.ShapeDtypeStruct((1,), jnp.int32))[2]
            # placed with the weights (committed; whole on every chip of
            # their mesh), as a prefill's cache would be: the slot writer's
            # output below then carries the placement every later step's
            # state will
            where = jax.tree.leaves(self.model.params)[0].sharding
            if isinstance(where, jax.sharding.NamedSharding):
                where = jax.sharding.NamedSharding(
                    where.mesh, jax.sharding.PartitionSpec())
            cache = jax.tree.map(
                lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype),
                                         where),
                shapes)
            self._alloc_state(cache)
            # normalize through the classic slot writer — a value-level
            # no-op (dummy cache scattered at an all-garbage row, slot 0,
            # length 0, token 0) whose OUTPUT carries the same committed
            # placement every later step input will: the chunk program then
            # compiles exactly once per shape (an uncommitted first input
            # would key a second fastpath entry)
            self._write_slot(cache, 0, 0, 0,
                             table_row=np.zeros((self.max_blocks_per_slot,),
                                                np.int32))
            if self._prefix is not None:
                # an engine that admits by chunks meets a shared partial
                # block as soon as two prompts share a prefix: compile the
                # copy-on-write clone with the pool, on the garbage block
                # (copied onto itself), not under the first such admission
                from .kvcache import GARBAGE_BLOCK

                self._cow_clone(GARBAGE_BLOCK, GARBAGE_BLOCK)

    def prefix_peek(self, tokens, cap: Optional[int] = None) -> int:
        """Longest cached-prefix length (tokens) the engine's trie holds
        for ``tokens`` — no LRU touch, no counters. The fleet router's
        cache-affinity term (ISSUE 14: route a request to the replica
        whose trie holds its longest prefix); 0 for prefix-less
        engines."""
        if self._prefix is None:
            return 0
        n = len(tokens)
        return self._prefix.peek(tokens, cap=n - 1 if cap is None
                                 else cap)

    def _ensure_state(self, prefill_cache) -> None:
        """Allocate the slot-pool DecodeState lazily from the first
        prefill's cache structure (zeros; every slot's rows are fully
        overwritten by its admission prefill before any read): the
        block POOL per KV entry (``kvcache.new_kv_pool``: K and V of a
        head side by side, + the f32 scales for int8), a slot-major
        entry for every other stateful op, and the all-garbage block
        tables."""
        if self.state is not None:
            return
        with setup_span("kv_pool_alloc"):
            self._alloc_state(prefill_cache)

    def _alloc_state(self, prefill_cache) -> None:
        import jax
        import jax.numpy as jnp

        from .kvcache import is_prefill_kv_entry, new_kv_pool

        if self._prefix is not None and self._prefix.n_blocks:
            # building a FRESH pool (first admission after a device-loss
            # rebuild): every cached block id would dangle into zeroed
            # arrays — drop the trie, returning its references, before
            # anything can match stale pointers
            self._prefix.clear(free=True)
        n = self.n_slots
        caches = {}
        self._paged_entry_names = set()
        for name, entry in prefill_cache.items():
            if is_prefill_kv_entry(entry):
                self._paged_entry_names.add(name)
                caches[name] = new_kv_pool(
                    entry, self.kv_pool_blocks, self.kv_block_size,
                    self.kv_dtype)
            else:
                caches[name] = jax.tree.map(
                    lambda leaf: jnp.zeros((n,) + leaf.shape[1:],
                                           leaf.dtype), entry)
        tables = jnp.zeros((n, self.max_blocks_per_slot), jnp.int32)
        self.state = DecodeState(caches=caches,
                                 lengths=jnp.zeros((n,), jnp.int32),
                                 block_tables=tables)
        self._last_tokens = jnp.zeros((n, 1), jnp.int32)

    def _sampler(self, temperature: float, top_k: int):
        """Jitted ``(logits (S, V), base_rng, tag_counts (S, 2) int32) ->
        tokens (S,)`` — one row per slot, each row drawing from its own
        stream ``fold_in(fold_in(base, tag), count)``. The folds happen
        IN-JIT so the decode hot loop dispatches one fused program, not
        2·slots host-side fold_in calls per token. Greedy when
        temperature <= 0; otherwise top-k filtered categorical at
        ``temperature`` — through the Pallas row top-k kernel when the
        shape qualifies (kernels/topk.py), ``lax.top_k`` otherwise."""
        import jax
        import jax.numpy as jnp

        greedy = temperature <= 0.0
        key = ("greedy",) if greedy else ("sample", float(temperature),
                                          int(top_k))
        fn = self._samplers.get(key)
        if fn is not None:
            return fn
        if greedy:
            def sample(logits, base_rng, tag_counts):
                with jax.named_scope("sample"):
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            temp = float(temperature)
            k = int(top_k)

            def row_rng(base_rng, tc):
                return jax.random.fold_in(
                    jax.random.fold_in(base_rng, tc[0]), tc[1])

            def sample(logits, base_rng, tag_counts):
                with jax.named_scope("sample"):
                    return draw(logits, base_rng, tag_counts)

            def draw(logits, base_rng, tag_counts):
                rngs = jax.vmap(lambda tc: row_rng(base_rng, tc))(
                    tag_counts)
                if k > 0:
                    from ..kernels.topk import (pallas_topk,
                                                should_use_pallas_topk)

                    if should_use_pallas_topk(logits, k, opt_in=True):
                        vals, idx = pallas_topk(logits, k)
                    else:
                        vals, idx = jax.lax.top_k(logits, k)
                    choice = jax.vmap(
                        lambda v, r: jax.random.categorical(r, v / temp))(
                            vals, rngs)
                    return jnp.take_along_axis(
                        idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
                return jax.vmap(
                    lambda lg, r: jax.random.categorical(r, lg / temp))(
                        logits, rngs).astype(jnp.int32)

        fn = jax.jit(sample)
        self._samplers[key] = fn
        return fn

    # ------------------------------------------------------------- main loop
    def _make_resilience(self, chaos):
        from .resilience import ServingResilience

        return ServingResilience(self.model.config, chaos=chaos,
                                 controller=self.admission,
                                 clock=self.resilience_clock)

    def _attach_kv_accounting(self, sched: ContinuousBatchScheduler
                              ) -> None:
        """Bind the engine's paged-KV bookkeeping to a scheduler: the
        block allocator (admission allocates, recycling frees) and the
        max supported context (admission rejects beyond the position
        table, ISSUE 12 satellite). Idempotent."""
        sched.allocator = self.block_allocator
        sched.on_slot_freed = self._clear_slot_tables
        # prefix cache + chunked prefill (ISSUE 14): admission walks
        # the trie and long suffixes/prompts take the chunk path
        sched.prefix = self._prefix
        sched.chunk_tokens = self.prefill_chunk_tokens
        if self.max_context < sched.max_len:
            sched.max_context = self.max_context

    def admit(self, sched: ContinuousBatchScheduler, req: Request,
              resilience=None) -> None:
        """Resilient admission (ISSUE 9): deadline stamp + shed-policy
        gate + scheduler submit. Raises ``OverloadError`` (shed) or
        ``QueueFullError`` (hard queue wall) — both ``ServingRejection``,
        so callers write one except clause. Without an explicit
        ``resilience``, events accumulate on a pending policy object the
        next ``serve()`` consumes — a pre-serve shed or deadline stamp is
        never lost to a throwaway."""
        self._attach_kv_accounting(sched)
        self._stamp_context_bucket(req)
        res = resilience
        if res is None:
            if self._pending_resilience is None:
                self._pending_resilience = self._make_resilience(None)
            res = self._pending_resilience
        res.admit(sched, req)

    def _stamp_context_bucket(self, req: Request) -> None:
        """Admission half of the ISSUE 18 context-length routing: stamp
        the request with the smallest searched bucket covering its max
        context (prompt + decode budget); beyond every bucket it takes
        the largest — mirroring ``ServingPlan.seq_shards_for``, so the
        stamped bucket is the one whose searched seq_shards the request
        decodes under. No-op without buckets (or if already stamped by
        a router upstream)."""
        if not self.context_buckets or req.context_bucket is not None:
            return
        need = int(req.prompt_len + req.max_new_tokens)
        for b in self.context_buckets:
            if need <= b:
                req.context_bucket = b
                return
        req.context_bucket = self.context_buckets[-1]

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, eos_id: Optional[int] = None,
                 seed: int = 0, chaos=None,
                 deadline_ms: Optional[float] = None) -> List[List[int]]:
        """Generate continuations for ``prompts`` (token-id sequences)
        through the continuous-batching loop; returns the generated token
        lists in submission order. Deterministic for a given (prompts,
        sampling params, seed) regardless of slot timing. ``deadline_ms``
        stamps each request with a relative completion budget (defaulted
        from ``--request-timeout-ms``); a request shed at admission or
        evicted/drained mid-serve returns its partial (possibly empty)
        continuation, with ``Request.outcome`` recording why — read
        ``self.stats.outcomes`` / ``self.drained_requests`` for the
        ledger."""
        self._token_input_check()
        res = self._make_resilience(chaos)
        sched = ContinuousBatchScheduler(
            n_slots=self.n_slots, max_queue=max(len(prompts),
                                                self.max_queue),
            buckets=self.buckets, max_len=self.max_decode_len,
            clock=res.clock)
        sched.shed_policy = res.shed_policy
        self._attach_kv_accounting(sched)
        reqs = []
        for i, p in enumerate(prompts):
            r = Request(prompt=np.asarray(p, dtype=np.int32),
                        max_new_tokens=max_new_tokens,
                        eos_id=self.eos_id if eos_id is None else eos_id,
                        rng_tag=i, deadline_ms=deadline_ms)
            self._stamp_context_bucket(r)
            try:
                res.admit(sched, r)
            except ServingRejection:
                pass  # r.outcome == "shed"; ledger picks it up in serve()
            reqs.append(r)
        self.serve(sched, temperature=temperature, top_k=top_k, seed=seed,
                   chaos=chaos, resilience=res)
        return [list(r.generated) for r in reqs]

    def start_serve(self, sched: ContinuousBatchScheduler,
                    temperature: float = 0.0, top_k: int = 0,
                    seed: int = 0, chaos=None, resilience=None,
                    publish_telemetry: bool = True) -> "_ServeLoop":
        """Begin a serve run without driving it to completion: returns
        the :class:`_ServeLoop` whose ``tick()`` advances exactly one
        scheduler action (a prefill or one decode step). This is the
        hook the fleet router (``serving/fleet.py``, ISSUE 11) uses to
        interleave N replicas' progress in one host loop; standalone
        ``serve()`` is exactly ``start_serve`` + ``while tick()`` +
        ``finish()``.

        ISSUE 17: ``--serve-loop async`` returns the double-buffered
        :class:`_AsyncServeLoop` instead — same contract, but one decode
        step's result may be IN FLIGHT between ticks (``settle()``
        forces arrival; ``finish()`` always settles first)."""
        cls = _AsyncServeLoop if self.serve_loop == "async" else _ServeLoop
        return cls(self, sched, temperature=temperature,
                   top_k=top_k, seed=seed, chaos=chaos,
                   resilience=resilience,
                   publish_telemetry=publish_telemetry)

    def serve(self, sched: ContinuousBatchScheduler,
              temperature: float = 0.0, top_k: int = 0,
              seed: int = 0, chaos=None, resilience=None) -> ServingStats:
        """Drive the scheduler until queue and slots drain. One decode
        step advances EVERY live slot one token (iteration-level
        batching); prefills are interleaved the moment a slot frees.

        Resilience (ISSUE 9, serving/resilience.py): the loop installs
        the flag-only SIGTERM/SIGINT handler from ``resilience/session.py``
        — a preemption signal turns into a graceful drain (admission
        stops, in-flight requests finish within ``--drain-grace-s``,
        queued ones are handed back via ``self.drained_requests``). When
        any resilience feature is armed (deadlines, a shed policy, or a
        ``ChaosPlan``) every decode iteration additionally sweeps expired
        deadlines and runs the guarded decode step, whose per-slot
        isfinite verdict quarantines only a poisoned slot (retry on a
        fresh slot per ``--decode-retry-budget``) while co-batched
        streams continue bit-identically. A device-loss error triggers
        the existing ``elastic_replan`` automatically with bounded
        backoff. A plain serve (nothing armed) pays none of the
        per-iteration costs."""
        from ..resilience.session import ResilienceSession

        loop = self.start_serve(sched, temperature=temperature,
                                top_k=top_k, seed=seed, chaos=chaos,
                                resilience=resilience)
        session = ResilienceSession(self.model, signals_only=True)
        session.install_signal_handlers()
        try:
            while True:
                if session.preempted:
                    # flag-only handler fired: graceful drain — stop
                    # admitting, let in-flight requests finish inside the
                    # grace window, hand the queue back
                    loop.request_drain(session=session)
                if not loop.tick():
                    break
        finally:
            session.close()
        return loop.finish()

    # ------------------------------------------------------ resilience hooks
    def health_probe(self, prompt: Sequence[int] = (1, 2, 3)) -> bool:
        """One prefill dispatch + finite-logits verdict, touching neither
        the scheduler nor the slot-pool DecodeState: the fleet router's
        active health check (ISSUE 11). A replica whose compute produces
        non-finite next-token logits for a trivial prompt — or whose
        dispatch raises — fails the probe; the circuit breaker decides
        what that means. The probe reuses the smallest prefill bucket's
        already-compiled program, so a steady-state probe costs one
        dispatch, not a compile."""
        import jax
        import jax.numpy as jnp

        try:
            bucket = self.buckets[0]
            eff = max(1, min(len(prompt), bucket))
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :eff] = np.asarray(prompt[:eff], np.int32)
            _logits, last, _cache = self._prefill_fn(bucket)(
                self.model.params, [jnp.asarray(ids)],
                jnp.asarray([eff], jnp.int32))
            return bool(np.all(np.isfinite(
                np.asarray(jax.device_get(last)))))
        except Exception:
            return False

    def reset_decode_pool(self) -> None:
        """Drop the slot-pool DecodeState (replica kill / rejoin in the
        fleet): the next admission prefill rebuilds it from scratch via
        ``_ensure_state`` — committed tokens live host-side on each
        Request, so nothing user-visible is lost. The block allocator
        is reset with it (no block of the discarded pool is live
        anymore; survivors' re-prefills allocate fresh tables)."""
        self.state = None
        self._last_tokens = None
        if self._prefix is not None:
            # the cached blocks die with the pool arrays; the allocator
            # reset below forgets refcounts wholesale, so the trie just
            # drops its nodes without per-block decrements
            self._prefix.clear(free=False)
        self.block_allocator.reset()

    # ------------------------------------------------------ KV accounting
    def _rest_itemsize(self) -> int:
        """Bytes of an element of what the serving programs rest between
        steps (K/V rows, a conv tail): the compute dtype's where the graph
        computes in a reduced one, else 0 — each node's own dtype."""
        cd = self.executor._compute_jnp_dtype()
        return 0 if cd is None else int(np.dtype(cd).itemsize)

    def _kv_row_bytes(self) -> int:
        """Analytic KV bytes ONE token's row costs across every attention
        node — heads * (kdim + vdim) * element size (int8 layouts add the
        two f32 per-(token, head) scales); a latent node's one stored row,
        once a token whatever its heads. The decode bytes-read/token
        bench column and the admission-honesty math both price from
        this."""
        if getattr(self, "_kv_row_bytes_cache", None) is None:
            from .kvcache import node_token_bytes

            self._kv_row_bytes_cache = sum(
                node_token_bytes(node.op, self.kv_dtype,
                                 self._rest_itemsize())
                for node in self.executor.pcg.compute_nodes())
        return self._kv_row_bytes_cache

    def _recurrent_slot_bytes(self) -> int:
        """Bytes of slot-major state ONE slot holds across every
        recurrent node (``Op.slot_state_bytes``); 0 for an
        attention-only graph."""
        if getattr(self, "_recurrent_slot_bytes_cache", None) is None:
            self._recurrent_slot_bytes_cache = sum(
                node.op.slot_state_bytes(self._rest_itemsize())
                for node in self.executor.pcg.compute_nodes())
        return self._recurrent_slot_bytes_cache

    def _recurrent_at_rest(self) -> Tuple[int, int]:
        """``(bytes, heads a row)`` of the slot-major state as the chip
        rests it: every leaf's bytes in whole ``(sublanes, 128)`` tiles
        of its dtype, and the most heads a recurrent node packs into a
        row (``Op.slot_state_heads_a_row``)."""
        if getattr(self, "_recurrent_at_rest_cache", None) is None:
            import jax

            from .kvcache import tiled_bytes

            slot_major = [entry for name, entry in self.state.caches.items()
                          if name not in self._paged_entry_names]
            self._recurrent_at_rest_cache = (
                sum(tiled_bytes(leaf.shape, leaf.dtype.itemsize)
                    for leaf in jax.tree.leaves(slot_major)),
                max((node.op.slot_state_heads_a_row()
                     for node in self.executor.pcg.compute_nodes()
                     if node.op.slot_state_bytes() > 0), default=0))
        return self._recurrent_at_rest_cache

    def _kv_tiling(self) -> Tuple[int, bool]:
        """``(P, tiled_grid)``: the table entries one step of the decode
        attention kernel folds (kernels/flash_decode.py ``tile_blocks``)
        and whether the tile axis is on its grid (an int8 pool), from
        the first KV pool of the decode state; a whole row for a model
        without one."""
        if getattr(self, "_kv_tiling_cache", None) is None:
            from ..kernels.flash_decode import tile_blocks, tiles_on_grid
            from .kvcache import _pool_scales

            tiling = (self.max_blocks_per_slot, False)
            if self._paged_entry_names:
                pool, _scales = _pool_scales(
                    self.state.caches[min(self._paged_entry_names)])
                tiling = (tile_blocks(pool.shape, pool.dtype.itemsize,
                                      self.max_blocks_per_slot),
                          tiles_on_grid(pool.dtype))
            self._kv_tiling_cache = tiling
        return self._kv_tiling_cache

    def _count_decode_kv(self, stats: ServingStats, live) -> None:
        """One decode step's attention read, counted: the analytic KV
        bytes — each live slot's OCCUPIED blocks (the flash-decode
        kernel's actual traffic, O(true_length)) — and the kernel's
        steps, per layer's call: a grid step a slot and a loop
        iteration a tile that holds a key of a live slot (an int8
        pool: every slot's tiles, the grid it keeps)."""
        bs = self.kv_block_size
        tile_blocks, tiled_grid = self._kv_tiling()
        tile = tile_blocks * bs
        toks = tiles_live = 0
        for _slot, req in live:
            keys = req.effective_len + 1
            toks += -(-keys // bs) * bs
            tiles_live += -(-keys // tile)
        stats.kv_bytes_read += toks * self._kv_row_bytes()
        stats.kv_tiles_live += tiles_live
        stats.kv_tiles_grid += (
            self.n_slots * -(-self.max_blocks_per_slot // tile_blocks)
            if tiled_grid else self.n_slots + tiles_live)

    def _count_decode_recurrent(self, stats: ServingStats, n_live: int
                                ) -> Dict[str, int]:
        """One decode step's recurrent state, counted: the step reads and
        writes the state of EVERY slot (static shapes: a free slot's zeros
        too). Returns the step's two numbers, for its ``serve_tick`` span;
        empty for an attention-only graph."""
        slot = self._recurrent_slot_bytes()
        if not slot:
            return {}
        moved = 2 * slot * self.n_slots
        stats.recurrent_state_bytes += moved
        stats.recurrent_slots_live += n_live
        stats.recurrent_state_bytes_at_rest, stats.state_heads_a_row = \
            self._recurrent_at_rest()
        return {"recurrent_state_bytes": moved,
                "recurrent_slots_live": n_live}

    def _count_decode_latent(self, stats: ServingStats, live,
                             in_flight: int = 0) -> Dict[str, int]:
        """One decode step's latent reads, counted: every latent node
        folds each live slot's rows (its keys: the context and the token
        the step writes — ``effective_len`` before the step's own token is
        committed; ``in_flight``: live slots whose previous token is not
        committed yet, the async loop's). Returns the step's number, for
        its ``serve_tick`` span; empty for a graph without a latent
        node."""
        n_latent = len(self._latent_node_names())
        if not n_latent:
            return {}
        rows = n_latent * (in_flight + sum(
            req.effective_len for _slot, req in live))
        stats.latent_rows_read += rows
        return {"latent_rows_read": rows}

    def _latent_node_names(self) -> frozenset:
        if getattr(self, "_latent_node_names_cache", None) is None:
            from ..ffconst import OperatorType

            self._latent_node_names_cache = frozenset(
                node.name for node in self.executor.pcg.compute_nodes()
                if node.op.op_type == OperatorType.OP_LATENT_ATTENTION)
        return self._latent_node_names_cache

    def cache_bytes_by_kind(self) -> Dict[str, int]:
        """Bytes of the decode state as allocated, by kind of cache (see
        ``ServingStats.cache_bytes_by_kind``); empty before the state is
        built."""
        import jax

        if self.state is None:
            return {}
        latent = self._latent_node_names()
        out: Dict[str, int] = {}
        for name, entry in self.state.caches.items():
            kind = "recurrent_state" \
                if name not in self._paged_entry_names \
                else "latent_pool" if name in latent else "kv_pool"
            out[kind] = out.get(kind, 0) + sum(
                int(leaf.nbytes) for leaf in jax.tree.leaves(entry))
        return out

    def _sweep_deadlines(self, sched, res, tracer) -> None:
        """Deadline enforcement at the iteration boundary: expired queued
        requests are dropped before they cost a prefill; expired in-flight
        requests are evicted and their slot recycled (outcome
        ``deadline_exceeded`` either way)."""
        now = res.clock()
        for req in [r for r in sched.queue if r.expired(now)]:
            res.deadline_misses += 1
            sched.drop_queued(req, "deadline_exceeded")
            if tracer.enabled:
                tracer.event("deadline_exceeded", rid=req.rid, queued=True)
        for slot, req in enumerate(list(sched.slots)):
            if req is not None and req.expired(now):
                res.deadline_misses += 1
                sched.evict(slot, "deadline_exceeded")
                if tracer.enabled:
                    tracer.event("deadline_exceeded", rid=req.rid,
                                 slot=slot,
                                 tokens=len(req.generated))

    def _quarantine(self, sched, res, slot: int, req, tracer) -> None:
        """Decode-health verdict said this slot's logits are non-finite:
        quarantine the slot, retry the request on a fresh slot while its
        retry budget lasts (re-prefilling prompt + committed tokens so the
        stream continues exactly where it stopped), abort it with outcome
        ``decode_fault`` once the budget is spent."""
        res.quarantines += 1
        retryable = req.retries_used < res.decode_retry_budget
        if retryable:
            try:
                bucket_for(req.effective_len, sched.buckets)
            except ValueError:
                retryable = False  # committed stream outgrew the buckets
        if retryable:
            req.retries_used += 1
            res.decode_retries += 1
            sched.quarantine(slot)
            if tracer.enabled:
                tracer.event("decode_quarantine", rid=req.rid, slot=slot,
                             retry=req.retries_used,
                             tokens=len(req.generated))
        else:
            res.decode_faults += 1
            sched.evict(slot, "decode_fault")
            if tracer.enabled:
                tracer.event("decode_fault", rid=req.rid, slot=slot,
                             retries_used=req.retries_used)

    def _dispatch_decode(self, params, res, chaos, k: int, guard: bool,
                         tracer):
        """One decode dispatch with device-loss failover: a scripted
        (``ChaosPlan.drop_devices_at``) or real device-loss error triggers
        ``elastic_replan`` onto the survivors with bounded linear backoff.
        When the DecodeState survives the hop (chaos injection, or an
        error raised before the donated buffers were consumed) generation
        resumes from it bit-identically; when it did NOT (a real loss
        mid-execution — the buffers were donated to the failed dispatch
        or lived on the lost chips) ``DecodeStateLostError`` tells the
        serve loop to rebuild the pool and re-prefill every live stream
        from its host-side committed tokens instead of retrying into an
        'Array has been deleted'. Returns ``(logits, ok_vec-or-None)``."""
        import jax

        from .resilience import (DecodeStateLostError, DeviceLossError,
                                 looks_like_device_loss,
                                 state_buffers_lost)

        attempt = 0
        while True:
            try:
                if chaos is not None:
                    n = chaos.maybe_drop_devices(k)
                    if n is not None:
                        raise DeviceLossError(n)
                decode = self._decode_fn(guard=guard)
                logits, self.state, *rest = decode(
                    params, [self._last_tokens], self.state)
                ok = rest.pop(0) if guard else None
                # a routed graph's counters of this step, still on the
                # device: the sync loop fetches them with the tokens
                self._step_counters = rest[0] if rest else None
                return logits, ok
            except Exception as e:  # noqa: BLE001 — filtered just below
                if not looks_like_device_loss(e):
                    raise
                surviving = e.n_dev if isinstance(e, DeviceLossError) \
                    else len(jax.devices())
                attempt += 1
                if attempt > res.max_replan_attempts:
                    raise
                if tracer.enabled:
                    tracer.event("serving_device_loss", step=k,
                                 surviving=surviving, attempt=attempt)
                # first retry is immediate; repeats back off linearly
                if attempt > 1 and res.replan_backoff_s > 0:
                    time.sleep(res.replan_backoff_s * (attempt - 1))
                self.elastic_replan(surviving)
                res.replans += 1
                if state_buffers_lost(self.state, self._last_tokens):
                    raise DecodeStateLostError(
                        f"DecodeState lost with the device at step {k} "
                        "(buffers donated to the failed dispatch or "
                        "resident on the lost chips); re-prefilling live "
                        "streams from committed tokens") from e

    def _merge_telemetry(self, sched, stats: ServingStats) -> None:
        """Publish the run into a StepTelemetry ``serving`` block (mirrors
        the resilience / strategy_safety blocks) when a sink wants one."""
        tracer = self._tracer()
        tel = self.model._make_telemetry(tracer, batch_size=self.n_slots,
                                         phase="serving")
        self.model._telemetry = tel or getattr(self.model, "_telemetry",
                                               None)
        if tel is None:
            return
        for w in stats.token_walls_s:
            tel.record_step(w)
        tel.requests_served = stats.requests_served
        tel.tokens_generated = stats.tokens_generated
        tel.queue_depth_hwm = stats.queue_depth_hwm
        tel.serving_p50_token_ms = stats.p50_token_ms()
        tel.serving_p99_token_ms = stats.p99_token_ms()
        tel.serving_tokens_per_s = round(stats.tokens_per_s(), 2)
        # host-overhead accounting (ISSUE 16, ROADMAP item 5)
        tel.serving_host_overhead_fraction = stats.host_overhead_fraction()
        # per-shard-chip KV residency (ISSUE 18) — only once a decode
        # step measured the fill
        tel.serving_kv_hbm_per_chip_bytes = \
            stats.kv_hbm_per_chip_bytes or None
        # the decode attention kernel's grid and how much of it had work
        tel.serving_kv_tiles_grid = stats.kv_tiles_grid
        tel.serving_kv_tiles_live = stats.kv_tiles_live
        tel.serving_moe_pairs_here = stats.moe_pairs_here
        tel.serving_moe_experts_live = stats.moe_experts_live
        tel.serving_moe_load_max_permille = stats.moe_load_max_permille
        tel.serving_moe_bounded_steps = stats.moe_bounded_steps
        tel.serving_moe_layer_steps = stats.moe_layer_steps
        tel.serving_recurrent_state_bytes = stats.recurrent_state_bytes
        tel.serving_recurrent_slots_live = stats.recurrent_slots_live
        tel.serving_recurrent_state_bytes_at_rest = \
            stats.recurrent_state_bytes_at_rest
        tel.serving_state_heads_a_row = stats.state_heads_a_row
        tel.serving_prefill_rows = stats.prefill_rows
        tel.serving_prefill_rows_real = stats.prefill_rows_real
        tel.serving_latent_rows_read = stats.latent_rows_read
        tel.serving_cache_bytes_by_kind = dict(stats.cache_bytes_by_kind)
        # serving_resilience block (ISSUE 9): the outcome ledger + event
        # counters, mirroring the resilience/strategy_safety blocks
        tel.serving_outcomes = dict(stats.outcomes)
        tel.serving_sheds = stats.sheds
        tel.serving_deadline_misses = stats.deadline_misses
        tel.serving_quarantines = stats.quarantines
        tel.serving_drains = stats.drains
        tel.serving_replans = stats.replans
        # serving_prefix block (ISSUE 14): the prefix-cache/chunked-
        # prefill ledger, mirroring the serving_resilience block
        tel.serving_prefix_hits = stats.prefix_hits
        tel.serving_prefix_tokens_reused = stats.prefix_tokens_reused
        tel.serving_prefill_tokens_computed = stats.prefill_tokens_computed
        tel.serving_cache_evictions = stats.cache_evictions
        tel.serving_chunked_prefills = stats.chunked_prefills
        tel.finalize()
        # the record opened here, at the run's end: its builds are the run's
        tel.programs_built, tel.build_s, tel.built_by_name = \
            stats.programs_built, stats.build_s, dict(stats.built_by_name)
        if self.model.config.telemetry_file:
            tel.write(self.model.config.telemetry_file)

    # ---------------------------------------------------------------- elastic
    def elastic_replan(self, n_dev: int):
        """Mid-serve re-search (PR 4/5 carry-over): a replica that lost
        chips re-runs the serving-objective search on the surviving device
        count — reusing the warm delta-cost Simulator. The searched plan
        is RECORDED (``self.plan``; ``plan.to_strategy`` materializes
        executor shardings) — applying it to a live multi-chip mesh
        (reshard weights + DecodeState onto the new layout) is the
        follow-on; what this models today is the migration's control path:
        the serving jits are deliberately dropped and recompiled, and the
        in-flight DecodeState must survive that hop untouched, so
        generation resumes exactly where it stopped (tier-1 asserts
        bit-identical continuations across a replan)."""
        from .search import serving_search

        # price prefill with the MEASURED prefix-cache hit rate of the
        # run so far (ISSUE 14: the latency-bounded objective sees the
        # real expected prefill cost, not the cold-cache worst case)
        reuse = self.stats.prefix_reuse_rate() or 0.0
        plan = serving_search(self.executor.pcg, self.model.config, n_dev,
                              sim=self._search_sim, prefill_reuse=reuse)
        self._search_sim = plan.sim
        self.plan = plan
        # drop and rebuild the serving jits — the migration recompile the
        # bit-identity contract is tested against; samplers and the slot
        # writer are state-shape-stable and survive
        self.executor._serving_jits = {}
        tracer = self._tracer()
        if tracer.enabled:
            tracer.event("serving_replan", n_dev=n_dev,
                         mesh=list(plan.mesh_shape),
                         tokens_per_s=round(plan.sim_tokens_per_s, 1))
        return plan


def _state_lost(state) -> bool:
    from .resilience import state_buffers_lost

    return state_buffers_lost(state)


class _TickPhase:
    """The region of a tick that is open right now: ``to(name)`` ends it
    and begins the next (flat, back to back, as ``_acct_tick``'s buckets
    are), ``close()`` ends it. Each is a ``span`` of obs/trace.SPANS.
    ``tick_args`` is what the tick learns about itself on the way, for
    its ``serve_tick`` span."""

    __slots__ = ("tracer", "cur", "tick_args")

    def __init__(self, tracer):
        self.tracer = tracer
        self.cur = None
        self.tick_args: Dict[str, Any] = {}

    def to(self, name: str, **args) -> None:
        self.close()
        self.cur = span(name, tracer=self.tracer, **args)
        self.cur.__enter__()

    def set_metadata(self, **args) -> None:
        self.cur.set_metadata(**args)

    def close(self) -> None:
        cur, self.cur = self.cur, None
        if cur is not None:
            cur.__exit__(None, None, None)


class _ServeLoop:
    """One serve() run's loop state, advanced one scheduler action at a
    time (ISSUE 11 refactor: the monolithic serve loop became
    start_serve/tick/finish so the fleet router can interleave N
    replicas' progress in a single host loop while each replica keeps
    the exact PR 9 per-iteration semantics — deadline sweeps, guarded
    decode, quarantine-retry, drain, device-loss failover).

    Contract: ``tick()`` performs exactly one action (one prefill, or
    one decode step advancing every live slot) and returns True;
    returning False means the scheduler has nothing to do *right now* —
    standalone ``serve()`` treats that as completion, the fleet may
    dispatch more work and tick again. ``finish()`` closes the ledger
    exactly once (idempotent)."""

    def __init__(self, engine: ServingEngine,
                 sched: ContinuousBatchScheduler,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 chaos=None, resilience=None,
                 publish_telemetry: bool = True):
        import jax

        eng = self.engine = engine
        self.sched = sched
        self.publish_telemetry = publish_telemetry
        self.tracer = eng._tracer()
        self.params = eng.model.params
        self.sampler = eng._sampler(temperature, top_k)
        self.stats = eng.stats = ServingStats()
        pending = eng._pending_resilience
        res = self.res = resilience or pending or \
            eng._make_resilience(chaos)
        eng._pending_resilience = None  # consumed
        if pending is not None and res is not pending:
            # pre-serve admit() calls ledgered their sheds (and deadline
            # arming) on the pending object; carry them into the object
            # this serve reports from so no rejection goes uncounted
            res.sheds += pending.sheds
            res._saw_deadline = res._saw_deadline or pending._saw_deadline
        if chaos is not None:
            res.chaos = chaos
        self.chaos = res.chaos
        # a caller-built resilience arrives with a cold default
        # controller; carry the engine's warm per-token EWMA across so
        # post-replan/rebuild shedding isn't blind for the first window
        # (no-op when the caller's controller is already warm)
        if res.controller is not eng.admission:
            res.controller.warm_start(eng.admission)
        sched.shed_policy = res.shed_policy
        eng._attach_kv_accounting(sched)
        # ONE time base: submit stamps were taken with the scheduler's
        # clock, so every sweep/drain decision reads the same clock — a
        # mismatched engine.resilience_clock on a caller-built scheduler
        # would otherwise make expired() compare across time bases
        res.clock = sched.clock
        # requests submitted straight to the scheduler (sched.submit, the
        # PR 6 pattern) never passed res.admit: stamp config-default
        # deadlines and arm the sweeps for any caller-set deadline_ms so
        # the documented enforcement does not depend on the entry point
        for r in list(sched.queue) + [s for s in sched.slots
                                      if s is not None]:
            res.stamp_deadline(r)
        self.res_active = res.armed
        self.guard = bool(self.res_active)
        eng._last_guard = self.guard
        eng.drained_requests = []
        self.base_rng = jax.random.PRNGKey(seed)
        self.step_no = 0
        self.storm_seq = 0
        self.draining = False
        self.drain_deadline_ms = None
        self.finished = False
        # prefix cache (ISSUE 14): a trie that outlived its pool (the
        # caller dropped eng.state, or buffers died with a device) must
        # be cleared BEFORE the first admission can match stale block
        # ids into the zeroed rebuild
        if eng._prefix is not None and eng._prefix.n_blocks and (
                eng.state is None or _state_lost(eng.state)):
            eng._prefix.clear(free=True)
        # per-run deltas against persistent counters — the trie (and a
        # caller-reused scheduler) outlive this run, so finish()
        # reports differences, not totals
        self._chunk_walls: Dict[int, float] = {}
        # the routing counters the last fetch brought, for its tick's span
        self._moe_tick: Dict[str, int] = {}
        # a routed graph's prefill chunks' [bounded, layers] since the last
        # fetch, on the device
        self._chunk_moe = None
        self._prefix_hits0 = sched.prefix_hits
        self._prefix_reused0 = sched.prefix_tokens_reused
        self._evictions0 = (eng._prefix.evictions
                            if eng._prefix is not None else 0)
        # what this run builds (obs/builds.py): counted from here, and
        # "serve" is the phase of a program built at a tick's first call
        self._built_from = build_mark()
        enter("serve")
        self.t0 = time.perf_counter()

    # ---------------------------------------------------------------- drain
    def request_drain(self, session=None) -> None:
        """The graceful-drain transition (SIGTERM in serve(),
        ``fleet.drain`` in the router): admission stops, in-flight
        requests get the grace window, queued ones are handed back at
        ``finish()``. Idempotent — repeat calls are no-ops."""
        if self.draining:
            return
        sched, res = self.sched, self.res
        self.draining = True
        sched.draining = True
        res.drains += 1
        if session is not None:
            session.note_preemption(self.stats.decode_steps)
        self.drain_deadline_ms = res.clock() + res.drain_grace_s * 1e3
        if self.tracer.enabled:
            self.tracer.event("serving_drain",
                              step=self.stats.decode_steps,
                              queued=sched.queued, active=sched.active,
                              grace_s=res.drain_grace_s)

    # -------------------------------------------------- pending transfers
    def settle(self) -> None:
        """Force any in-flight decode result to arrive and commit — the
        async runtime's explicit drain point (ISSUE 17). Every path
        that must observe settled scheduler/ledger state calls it:
        ``finish()``, the drain-grace eviction, the fleet's
        harvest/kill/migration, and the DecodeStateLost rebuild. The
        sync loop never has a pending transfer, so this is a no-op."""
        self._settle_pending()

    def _settle_pending(self) -> None:
        return None

    def _fetch(self, toks, ok_vec, counters=None):
        """The ONE blocking host-transfer choke point for decode results
        (ISSUE 17 satellite: the formerly separate guarded/unguarded
        ``device_get`` call sites unified). Both the sync loop and the
        async runtime's pending-transfer settle route through here, so
        counting blocking host syncs means counting THIS
        (``stats.host_syncs``; the async steady-state contract is <= 1
        per committed decode step). Returns ``(tokens (n_slots,)
        np.int32, ok (n_slots,) bool-or-None)``."""
        import jax

        self.stats.host_syncs += 1
        # the guarded step's per-slot finite verdict and a routed graph's
        # step counters ride the same device_get as the tokens — still a
        # single blocking sync (None fetches as None)
        toks_host, ok_host, c = jax.device_get((toks, ok_vec, counters))
        if c is not None:
            pairs, live, load, bounded, layers = (int(v) for v in c)
            st = self.stats
            st.moe_pairs_here += pairs
            st.moe_experts_live += live
            st.moe_load_max_permille = max(st.moe_load_max_permille, load)
            st.moe_bounded_steps += bounded
            st.moe_layer_steps += layers
            self._moe_tick = {"moe_pairs_here": pairs,
                              "moe_experts_live": live,
                              "moe_load_max_permille": load,
                              "moe_bounded_steps": bounded,
                              "moe_layer_steps": layers}
        return np.asarray(toks_host), (None if ok_host is None
                                       else np.asarray(ok_host))

    # ----------------------------------------------------------------- tick
    def _acct_tick(self, t_tick: float, t_dev: float,
                   dev_s: float) -> None:
        """Host-overhead accounting (ISSUE 16, ROADMAP item 5): split
        this tick's wall into dispatch (tick entry -> device call
        issued), device (the blocking call + fetch) and bookkeeping
        (device return -> now). Plain float adds — always on, never
        touches the token streams."""
        st = self.stats
        st.host_dispatch_s += max(t_dev - t_tick, 0.0)
        st.host_device_s += dev_s
        st.host_bookkeep_s += max(
            time.perf_counter() - t_dev - dev_s, 0.0)
        st.host_ticks += 1

    def tick(self) -> bool:
        """Perform ONE scheduler action. Returns False when there is
        nothing to do right now (queue empty + no live slot, or the
        drain grace just expired and evicted the stragglers).

        One ``serve_tick`` span (obs/trace.SPANS) with the tick's
        ``kind``; inside it the regions ``_acct_tick`` accounts for are
        spans that begin and end where its clock is read, so the buckets
        and the spans cannot disagree."""
        phase = _TickPhase(self.tracer)
        with step_span("serve_tick", self.step_no,
                       tracer=self.tracer) as tick_sp:
            phase.to("tick_dispatch")
            t_tick = time.perf_counter()
            try:
                kind, worked = self._tick(t_tick, phase)
            finally:
                phase.close()
            tick_sp.set_metadata(kind=kind, **phase.tick_args)
            st = self.stats
            st.tick_wall_s_by_kind[kind] += time.perf_counter() - t_tick
            st.ticks_by_kind[kind] += 1
        return worked

    def _tick(self, t_tick: float, phase: "_TickPhase"):
        """``tick``'s body, entered inside ``tick_dispatch``: (the tick's
        kind, whether it did anything)."""
        import jax
        import jax.numpy as jnp

        eng, sched, res = self.engine, self.sched, self.res
        stats, tracer = self.stats, self.tracer
        if self.draining and sched.active and \
                res.clock() > self.drain_deadline_ms:
            # grace exhausted: stragglers are evicted (outcome
            # preempted), never silently dropped. In-flight tokens land
            # first (async): a token the device already produced inside
            # the grace window belongs to the stream
            self._settle_pending()
            for slot, r in enumerate(list(sched.slots)):
                if r is not None:
                    sched.evict(slot, "preempted")
            return "idle", False
        if self.res_active and res.deadlines_armed:
            eng._sweep_deadlines(sched, res, tracer)
        action = sched.next_action()
        if action is None:
            phase.close()
            return "idle", self._idle()
        if action[0] == "prefill":
            _, req, slot, bucket = action
            if self.res_active and req.expired(res.clock()):
                # expired while queued but swept into a slot in the same
                # iteration: evict before paying prefill
                res.deadline_misses += 1
                sched.evict(slot, "deadline_exceeded")
                return "idle", True
            t_p = time.perf_counter()
            # effective prompt = prompt + committed tokens: empty suffix
            # for a fresh request, the full committed stream for a
            # decode-fault retry (or cross-replica migration) re-prefill
            eff = req.effective_len
            phase.to("prefill", rid=req.rid, bucket=bucket, slot=slot,
                     prompt_len=eff)
            cur = req.current_prompt()
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :eff] = cur
            _logits, last, cache = eng._prefill_fn(bucket)(
                self.params, [jnp.asarray(ids)],
                jnp.asarray([eff], jnp.int32))
            eng._ensure_state(cache)
            # per-request rng: deterministic under co-scheduling — the
            # stream depends on (submission tag, tokens emitted), not
            # slot timing or the replica serving it; a retry/migration
            # resumes its stream exactly where it stopped
            tag = req.rng_tag if req.rng_tag is not None else req.rid
            tok = int(jax.device_get(
                self.sampler(last, self.base_rng,
                             np.asarray([[tag, len(req.generated)]],
                                        np.int32))[0]))
            wall = time.perf_counter() - t_p
            phase.to("tick_bookkeep")
            stats.prefills += 1
            stats.prefill_tokens_computed += eff
            stats.prefill_rows += bucket
            stats.prefill_rows_real += eff
            phase.tick_args.update(prefill_rows=bucket,
                                   prefill_rows_real=eff)
            stats.record_token(wall)
            stats.tokens_generated += 1
            # first_token_ms is stamped at the commit point
            # (ContinuousBatchScheduler.commit_token) — the one stamp
            # site every first-commit path passes through
            if req.first_token_step is None:
                req.first_token_step = self.step_no
            if not sched.commit_token(slot, tok):
                with span("slot_write", tracer=tracer):
                    eng._write_slot(cache, slot, eff, tok,
                                    table_row=eng._table_row_for(req))
                # mark completion (the pool holds the prompt's KV now)
                # and eagerly cache the FULL prompt blocks so same-batch
                # shared-prefix admissions already hit; the partial tail
                # is adopted later, at release, so the request's own
                # decode writes into it never trigger a self-COW
                req.prefill_pos = req.prefill_target
                if eng._prefix is not None and req.kv_blocks:
                    full = eff // eng.kv_block_size
                    if full:
                        eng._prefix.insert(cur[:full * eng.kv_block_size],
                                           req.kv_blocks[:full])
            self._acct_tick(t_tick, t_p, wall)
            return "prefill", True
        if action[0] == "prefill_chunk":
            # chunked prefill / prefix-suffix prefill (ISSUE 14): one
            # fixed-width chunk of ONE slot's prompt, co-scheduled with
            # the other slots' decode steps (the scheduler alternates),
            # so a long prompt never head-of-line-blocks the batch and a
            # trie-hit admission computes only its suffix
            _, req, slot, start, n, shape = action
            if self.res_active and req.expired(res.clock()):
                res.deadline_misses += 1
                sched.evict(slot, "deadline_exceeded")
                self._chunk_walls.pop(req.rid, None)
                return "idle", True
            t_p = time.perf_counter()
            phase.to("prefill_chunk", rid=req.rid, slot=slot, start=start,
                     tokens=n, hit=req.prefix_hit_tokens)
            eng._ensure_state_bootstrap()
            if req.pending_cow is not None:
                # first divergent write into a shared partial tail
                # block: clone it before this chunk touches it
                src, dst = req.pending_cow
                eng._cow_clone(src, dst)
                sched.release_cow(req)
                if tracer.enabled:
                    tracer.event("prefix_cow_clone", rid=req.rid,
                                 slot=slot, src=src, dst=dst)
            cur = req.current_prompt()
            ids = np.zeros((1, shape), np.int32)
            ids[0, :n] = cur[start:start + n]
            row = eng._table_row_for(req)
            last, eng.state, *counters = eng._chunk_fn(shape)(
                self.params, [jnp.asarray(ids)], eng.state,
                jnp.asarray(row, jnp.int32), jnp.int32(start),
                jnp.int32(n))
            if counters:
                # a routed graph's [bounded, layers] of this chunk, summed
                # on the device until a prompt's last chunk syncs anyway
                self._chunk_moe = counters[0][3:] + (
                    0 if self._chunk_moe is None else self._chunk_moe)
            stats.prefill_tokens_computed += n
            stats.chunked_prefills += 1
            done = sched.chunk_done(slot, n)
            wall = time.perf_counter() - t_p
            phase.set_metadata(done=int(done))
            self._chunk_walls[req.rid] = \
                self._chunk_walls.get(req.rid, 0.0) + wall
            if sched.rt.enabled:
                sched.rt.note(req.rid, "chunk", float(res.clock()),
                              start=start, tokens=n,
                              replica=sched.replica_idx)
            if not done:
                phase.to("tick_bookkeep")
                self._acct_tick(t_tick, t_p, wall)
                return "prefill_chunk", True
            eff = req.prefill_target
            tag = req.rng_tag if req.rng_tag is not None else req.rid
            tok, moe = jax.device_get((
                self.sampler(last, self.base_rng,
                             np.asarray([[tag, len(req.generated)]],
                                        np.int32))[0], self._chunk_moe))
            tok = int(tok)
            if moe is not None:
                self._chunk_moe = None
                stats.moe_chunk_bounded_steps += int(moe[0])
                stats.moe_chunk_layer_steps += int(moe[1])
                phase.set_metadata(moe_bounded_steps=int(moe[0]),
                                   moe_layer_steps=int(moe[1]))
            phase.to("tick_bookkeep")
            stats.prefills += 1
            stats.record_token(self._chunk_walls.pop(req.rid, wall))
            stats.tokens_generated += 1
            # first_token_ms lands at the commit point (commit_token)
            if req.first_token_step is None:
                req.first_token_step = self.step_no
            if eng._prefix is not None and req.kv_blocks:
                full = eff // eng.kv_block_size
                if full:
                    eng._prefix.insert(cur[:full * eng.kv_block_size],
                                       req.kv_blocks[:full])
            if not sched.commit_token(slot, tok):
                # arm the slot for decode: the chunks already wrote the
                # pool rows, so only the device-side cursor/table/token
                # remain (the row stayed garbage during chunking — the
                # decode steps running between chunks wrote this slot's
                # discarded tokens into the garbage block, never into
                # its real blocks)
                with span("slot_write", tracer=tracer):
                    eng._set_slot_meta(slot, eff, tok, row)
            self._acct_tick(t_tick, t_p, wall)
            return "prefill_chunk", True
        # decode: one token for every live slot — through the sync
        # (reference) or async (double-buffered) _tick_decode variant
        return "decode", self._tick_decode(t_tick, action[1], phase)

    def _idle(self) -> bool:
        """No scheduler action is available right now. The async loop
        may still hold an in-flight result whose arrival IS the
        remaining work (an EOS frees a slot, a quarantine requeues);
        the sync loop is simply done."""
        return False

    # ---------------------------------------------------- decode building
    # blocks shared by the sync reference and the async runtime — ONE
    # implementation of chaos injection, device-loss rebuild, sampling
    # and the commit point, so the two loops can only diverge in WHEN
    # the commit happens, never in WHAT it does
    def _chaos_hooks(self, k: int) -> None:
        """Scripted chaos at the decode-step boundary ``k``. The async
        runtime keys ``k`` on its DISPATCH counter: at injection time
        the sync loop's ``stats.decode_steps`` equals its dispatch
        count, so the same script fires at the same logical step in
        both loops."""
        eng, sched, res = self.engine, self.sched, self.res
        chaos, tracer = self.chaos, self.tracer
        if chaos is None:
            return
        chaos.maybe_preempt_serving(k)
        for p in chaos.maybe_storm(k):
            r = Request(prompt=np.asarray(p, np.int32),
                        max_new_tokens=chaos.storm_max_new_tokens,
                        eos_id=eng.eos_id,
                        rng_tag=1_000_000 + self.storm_seq)
            self.storm_seq += 1
            try:
                res.admit(sched, r)
            except ServingRejection:
                pass  # counted by the controller; outcome shed
        if eng.state is not None:
            eng.state, poisoned = chaos.maybe_poison_decode(
                k, eng.state)
            if poisoned is not None and tracer.enabled:
                tracer.event("decode_poison", step=k, slot=poisoned)

    def _rebuild_lost_state(self, k: int) -> None:
        """The slot pool died with the device. Committed tokens are
        host-side on each Request, so recovery is the quarantine-retry
        path applied to EVERY live stream: back to the queue front,
        re-prefilled onto the rebuilt pool (rng streams key on (tag,
        tokens_emitted) — continuations are unchanged). A stream whose
        committed length outgrew the prefill buckets cannot re-enter
        and is evicted (preempted). Drop the dead state FIRST: the
        quarantine path's on_slot_freed hook must see an empty pool,
        not deleted buffers."""
        eng, sched, tracer = self.engine, self.sched, self.tracer
        eng.state = None
        eng._last_tokens = None
        if eng._prefix is not None:
            # the cached blocks died with the pool: drop the trie
            # BEFORE the quarantined requests re-enter admission, or
            # their re-prefills would map stale block ids into the
            # zeroed rebuild
            eng._prefix.clear(free=True)
        # EVERY occupied slot re-enters — mid-chunk prefills included
        # (their partially-written pool rows died with the pool;
        # re-admission restarts the prefill, re-walking the trie,
        # which _ensure_state cleared alongside the pool)
        requeued = 0
        for slot, req in enumerate(list(sched.slots)):
            if req is None:
                continue
            requeued += 1
            try:
                bucket_for(req.effective_len, sched.buckets)
            except ValueError:
                sched.evict(slot, "preempted")
                continue
            sched.quarantine(slot)
        if tracer.enabled:
            tracer.event("serving_state_rebuild", step=k,
                         requeued=requeued)

    def _sample(self, live, logits, pending=None):
        """Sample every slot's next token on device and feed the result
        back as the next step's input (``_last_tokens`` — set from the
        DEVICE array, never a host copy, which is what lets the async
        runtime dispatch k+1 before k's transfer lands). Per-slot rng
        streams depend on (submission tag, tokens emitted), never on
        slot index or batch composition — built as ONE host numpy
        array, folded in-jit. ``pending``: the async runtime's
        in-flight step — a slot whose previous token is still
        uncommitted samples at count+1, the count it will have when
        that token lands (a pending token that ends up discarded —
        EOS, quarantine — discards this draw too, so the +1 can never
        desync a stream)."""
        eng, sched = self.engine, self.sched
        tag_counts = np.zeros((eng.n_slots, 2), np.int32)
        for s, r in live:
            tag_counts[s, 0] = r.rng_tag if r.rng_tag is not None \
                else r.rid
            tag_counts[s, 1] = len(r.generated)
        if pending is not None:
            for (s, r), e in zip(pending.live, pending.epochs):
                if sched.slots[s] is r and sched.slot_epoch[s] == e:
                    tag_counts[s, 1] += 1
        toks = self.sampler(logits, self.base_rng, tag_counts)
        eng._last_tokens = toks[:, None]
        return toks

    def _commit_arrival(self, live, epochs, toks_host, ok_host,
                        wall: float) -> None:
        """THE commit point: one settled decode step's bookkeeping —
        token commits (EOS/length recycling inside ``commit_token``),
        quarantine verdicts, latency/ledger stats, reqtrace stamps. The
        sync loop runs it immediately after its blocking fetch; the
        async runtime runs it at transfer ARRIVAL, one step behind
        dispatch, with ``epochs`` guarding against slots recycled while
        the result was in flight."""
        eng, sched, res = self.engine, self.sched, self.res
        stats, tracer = self.stats, self.tracer
        stats.decode_steps += 1
        self.step_no += 1
        eng._count_decode_kv(stats, live)
        if self.res_active:
            res.controller.observe_step(
                wall, len(live),
                tenants=[r.tenant for _s, r in live if r.tenant])
        for i, (slot, req) in enumerate(live):
            if epochs is not None and (
                    sched.slots[slot] is not req
                    or sched.slot_epoch[slot] != epochs[i]):
                # the slot was recycled while this result was in flight
                # (EOS/length/deadline/quarantine at the previous
                # settle): the one-deep pipeline's extra draw is
                # discarded — exactly one terminal outcome per request
                continue
            if ok_host is not None and not bool(ok_host[slot]):
                # poisoned slot: quarantine it alone — the token is NOT
                # committed, neighbors proceed untouched
                eng._quarantine(sched, res, slot, req, tracer)
                continue
            stats.tokens_generated += 1
            stats.record_token(wall)
            sched.commit_token(slot, int(toks_host[slot]))
        if tracer.enabled:
            tracer.complete("decode_step", wall, step=self.step_no,
                            live_slots=len(live))

    def _tick_decode(self, t_tick: float, live, phase) -> bool:
        """One decode step, fully synchronous — the reference
        implementation the async runtime must match stream-for-stream:
        dispatch, BLOCK on the host transfer, commit."""
        from .resilience import DecodeStateLostError

        eng, res = self.engine, self.res
        k = self.stats.decode_steps  # the chaos-script step index
        self._chaos_hooks(k)
        t_d = time.perf_counter()
        phase.to("decode_dispatch")
        try:
            logits, ok_vec = eng._dispatch_decode(
                self.params, res, self.chaos, k, self.guard, self.tracer)
        except DecodeStateLostError:
            phase.to("tick_bookkeep")
            self._rebuild_lost_state(k)
            self._acct_tick(t_tick, t_d, 0.0)
            return True
        toks = self._sample(live, logits)
        phase.to("fetch_tokens")
        toks_host, ok_host = self._fetch(toks, ok_vec, eng._step_counters)
        phase.tick_args.update(self._moe_tick)
        self._moe_tick = {}
        phase.tick_args.update(
            eng._count_decode_recurrent(self.stats, len(live)))
        phase.tick_args.update(eng._count_decode_latent(self.stats, live))
        wall = time.perf_counter() - t_d
        phase.to("tick_bookkeep")
        self._commit_arrival(live, None, toks_host, ok_host, wall)
        self._acct_tick(t_tick, t_d, wall)
        return True

    # --------------------------------------------------------------- finish
    def finish(self, ledger_drained: bool = True) -> ServingStats:
        """Close the run exactly once: drain handoff, the outcome ledger
        (every request that entered the system leaves under exactly one
        outcome), telemetry.

        ``ledger_drained`` (ISSUE 20 bugfix): the drain handoff used to
        hand ``engine.drained_requests`` back with only ``outcome``
        stamped — no reqtrace terminal — so a drained rid's timeline
        stayed open forever across a drain followed by a crash. The
        standalone engine path (default True) closes those timelines
        as ``preempted`` here; the requests themselves stay clean for
        re-submission elsewhere. The FLEET passes False: its requeue
        branch clears ``outcome`` and re-admits the request, and
        reqtrace's first-terminal-wins would otherwise pin a premature
        "preempted" on a stream that goes on to finish "ok" — the fleet
        ledgers (and journals) its own drain handoffs at ITS terminal
        instead."""
        eng, sched, res = self.engine, self.sched, self.res
        stats, tracer = self.stats, self.tracer
        if self.finished:
            return stats
        self.finished = True
        leave("serve")
        stats.programs_built, stats.build_s, stats.built_by_name = \
            built_since(self._built_from)
        if self.draining:
            eng.drained_requests = sched.pop_queued()
            if ledger_drained and sched.rt.enabled:
                for r in eng.drained_requests:
                    sched.rt.finish(r.rid, float(sched.clock()),
                                    "preempted", reason="drain",
                                    new_tokens=len(r.generated),
                                    replica=sched.replica_idx)
            if tracer.enabled:
                tracer.event("serving_drain_done",
                             returned=len(eng.drained_requests),
                             finished=len(sched.finished))
        stats.wall_s = time.perf_counter() - self.t0
        # clean (outcome ok) completions only — evicted/failed requests
        # are accounted in the outcome ledger below, not as "served"
        stats.requests_served = sum(
            1 for r in sched.finished if (r.outcome or "ok") == "ok")
        stats.queue_depth_hwm = sched.queue_depth_hwm
        # outcome ledger: every request that entered the system leaves
        # under exactly one outcome
        for r in sched.finished:
            stats.count_outcome(r.outcome or "ok")
        stats.count_outcome("shed", res.sheds)
        stats.count_outcome("preempted", len(eng.drained_requests))
        stats.sheds = res.sheds
        stats.deadline_misses = res.deadline_misses
        stats.quarantines = res.quarantines
        stats.decode_retries = res.decode_retries
        stats.drains = res.drains
        stats.replans = res.replans
        stats.drained_returned = len(eng.drained_requests)
        # prefix-cache ledger (ISSUE 14): deltas vs the loop-start
        # snapshots — the trie and a caller-reused scheduler persist
        stats.prefix_hits = sched.prefix_hits - self._prefix_hits0
        stats.prefix_tokens_reused = \
            sched.prefix_tokens_reused - self._prefix_reused0
        if eng._prefix is not None:
            stats.cache_evictions = \
                eng._prefix.evictions - self._evictions0
        # per-shard-chip KV residency (ISSUE 18): mean per-step occupied
        # KV bytes / seq_shards — each shard chip holds one contiguous
        # 1/seq_shards run of every slot's blocks, so the measured-fill
        # pool bytes divide evenly across the seq mesh axis
        if stats.decode_steps and stats.kv_bytes_read:
            stats.kv_hbm_per_chip_bytes = int(
                stats.kv_bytes_read / stats.decode_steps
                / max(eng.seq_shards, 1))
        stats.cache_bytes_by_kind = eng.cache_bytes_by_kind()
        if self.publish_telemetry:
            eng._merge_telemetry(sched, stats)
            if tracer.enabled and eng.model.config.trace_file:
                tracer.write(eng.model.config.trace_file)
        return stats


@dataclasses.dataclass
class _PendingStep:
    """One in-flight decode step of the async runtime (ISSUE 17): the
    device arrays whose host transfer is pending, plus everything the
    commit needs when the result lands. ``epochs`` snapshots the slot
    incarnation counters at DISPATCH time — a slot recycled while the
    result was in flight discards its entry at settle (the one-deep
    pipeline's extra draw), identity checked per (slot, request,
    epoch)."""

    toks: Any
    ok_vec: Any
    live: List
    epochs: List[int]
    t_d: float
    # a routed graph's step counters, on the device: fetched with the
    # tokens at settle, for the NEXT tick's ``serve_tick`` span
    counters: Any = None


class _AsyncServeLoop(_ServeLoop):
    """The double-buffered serve loop behind ``--serve-loop async``
    (ISSUE 17, docs/serving.md "Async runtime"): decode step k+1 is
    dispatched on-device while step k's ``(tokens, ok_vec)`` transfer
    is still in flight, and ALL commit-point bookkeeping — token
    commits, EOS/length recycling, quarantine verdicts, reqtrace
    stamps — fires at transfer ARRIVAL, one step behind dispatch,
    overlapped with step k+1's device execution. The host Python loop
    leaves the decode critical path: the only blocking host sync per
    committed step is the settle's fetch (``stats.host_syncs`` pins
    it).

    What makes the one-deep pipeline safe:

    * the decode feedback token is read from the DEVICE array
      (``_last_tokens = toks[:, None]`` in ``_sample``) — dispatch k+1
      never needs k's host copy;
    * per-slot rng streams key on (tag, tokens_emitted), with pending
      in-flight tokens counted (+1), so sampled streams are bitwise
      the sync loop's regardless of commit lag;
    * the extra in-flight step a finishing/quarantined slot runs
      writes only at positions >= the adopted prefix extent of blocks
      released at settle, and every released block is fully
      re-prefilled (data-dependency ordered through the donated state)
      before any read — the standing overwrite-before-read invariant;
    * slot-epoch guards discard in-flight results for recycled slots
      (``ContinuousBatchScheduler.slot_epoch``).

    Drain points — everything that must observe settled state calls
    ``settle()`` first: ``finish()``, the drain-grace eviction, the
    idle transition, the DecodeStateLost rebuild, and the fleet's
    harvest/kill/migration paths (serving/fleet.py)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._pending: Optional[_PendingStep] = None
        # chaos scripts key on DISPATCH order: at injection time the
        # sync loop's stats.decode_steps equals its dispatch count, so
        # a dispatch counter reproduces the exact injection points
        # (stats.decode_steps lags one settle behind here)
        self.dispatch_no = 0

    # ---------------------------------------------------------- settling
    def _settle_step(self, p: _PendingStep, commit_span: str) -> float:
        """Block until ``p``'s transfer lands (``fetch_tokens``), then
        run the commit point under the span of the bucket its time is
        accounted to (``commit_span``). Returns the seconds actually
        spent BLOCKED (the only part of the settle that is device wait,
        not host work)."""
        t_s = time.perf_counter()
        with span("fetch_tokens", tracer=self.tracer):
            toks_host, ok_host = self._fetch(p.toks, p.ok_vec, p.counters)
        blocked = time.perf_counter() - t_s
        self.stats.host_device_s += blocked
        wall = time.perf_counter() - p.t_d
        with span(commit_span, tracer=self.tracer):
            self._commit_arrival(p.live, p.epochs, toks_host, ok_host,
                                 wall)
        return blocked

    def _settle_pending(self) -> None:
        """The explicit drain point (``settle()``): force the in-flight
        step to arrive and commit. Outside the decode hot path nothing
        overlaps the commit work, so it lands in the bookkeep bucket."""
        p, self._pending = self._pending, None
        if p is None:
            return
        t0 = time.perf_counter()
        blocked = self._settle_step(p, "tick_bookkeep")
        self.stats.host_bookkeep_s += max(
            time.perf_counter() - t0 - blocked, 0.0)

    def _idle(self) -> bool:
        if self._pending is None:
            return False
        # the in-flight step IS the remaining work: its arrival commits
        # tokens, frees slots, possibly requeues a quarantined stream —
        # the next tick sees a live scheduler again
        self._settle_pending()
        return True

    # ------------------------------------------------------------- decode
    def _tick_decode(self, t_tick: float, live, phase) -> bool:
        """One double-buffered decode step: dispatch k+1 FIRST (device
        starts immediately), then settle k's pending transfer and do
        its commit bookkeeping while k+1 executes. Steady state: one
        blocking host sync (the settle fetch) per committed step.

        Spans: tick entry -> step issued is ``tick_dispatch``
        (``decode_dispatch`` nested in it); behind a step in flight the
        tick says ``pipelined=1`` and that wall is counted in
        ``host_overlap_s``, not ``host_dispatch_s``. Everything after
        the issue is ``tick_overlap`` except the settle's blocking
        ``fetch_tokens``."""
        from .resilience import DecodeStateLostError

        eng, res, stats = self.engine, self.res, self.stats
        # with a step already in flight the device stays busy through
        # this tick's prework — host work only hits the critical path
        # when the pipeline is empty (first step of a burst)
        pipelined = self._pending is not None
        if pipelined:
            phase.tick_args["pipelined"] = 1
        phase.tick_args.update(
            eng._count_decode_recurrent(stats, len(live)))
        phase.tick_args.update(eng._count_decode_latent(
            stats, live, len(self._pending.live) if pipelined else 0))
        k = self.dispatch_no  # chaos keys on dispatch order
        self._chaos_hooks(k)
        t_d = time.perf_counter()
        try:
            with span("decode_dispatch", tracer=self.tracer):
                logits, ok_vec = eng._dispatch_decode(
                    self.params, res, self.chaos, k, self.guard,
                    self.tracer)
        except DecodeStateLostError:
            # settle FIRST: at this logical point the sync loop had
            # already committed step k-1's tokens — the rebuild's
            # re-prefills must resume from the same committed streams.
            # A scripted loss leaves the pending buffers alive; a real
            # loss that killed them too loses that step's tokens (the
            # requests re-prefill one token earlier — still a valid
            # stream position)
            phase.close()
            try:
                self._settle_pending()
            except Exception:
                self._pending = None  # buffers died with the device
            self._rebuild_lost_state(k)
            stats.host_dispatch_s += max(t_d - t_tick, 0.0)
            stats.host_ticks += 1
            return True
        issued = time.perf_counter()
        phase.to("tick_overlap")
        if pipelined:
            stats.host_overlap_s += max(issued - t_tick, 0.0)
        else:
            stats.host_dispatch_s += max(issued - t_tick, 0.0)
        # the device is busy with step k from here on: the sampler
        # dispatch, the early transfer start and the PREVIOUS step's
        # entire commit bookkeeping all overlap its execution — that is
        # the double buffer. Only the settle's blocking fetch counts as
        # device wait
        with span("decode_dispatch", tracer=self.tracer):
            toks = self._sample(live, logits, pending=self._pending)
        ok_arr = (ok_vec,) if ok_vec is not None else ()
        for arr in (toks,) + ok_arr:
            try:
                arr.copy_to_host_async()  # start D2H behind the compute
            except (AttributeError, TypeError):
                pass  # backend without async host copies: settle blocks
        prev, self._pending = self._pending, _PendingStep(
            toks=toks, ok_vec=ok_vec, live=list(live),
            epochs=[self.sched.slot_epoch[s] for s, _ in live], t_d=t_d,
            counters=eng._step_counters)
        self.dispatch_no += 1
        phase.close()
        blocked = (self._settle_step(prev, "tick_overlap")
                   if prev is not None else 0.0)
        # the settled step's routing counters, a step behind their tick
        phase.tick_args.update(self._moe_tick)
        self._moe_tick = {}
        stats.host_overlap_s += max(
            time.perf_counter() - issued - blocked, 0.0)
        stats.host_ticks += 1
        return True

    # ------------------------------------------------------------- finish
    def finish(self, ledger_drained: bool = True) -> ServingStats:
        self._settle_pending()
        return super().finish(ledger_drained=ledger_drained)
