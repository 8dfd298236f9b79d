"""Step and search telemetry: machine-readable training-run records.

``StepTelemetry`` is filled by ``FFModel.fit``/``eval``: per-step wall time,
loss/metric history, samples/sec, the programs the run built and their
seconds (``obs/builds.py``), estimated MFU from the analytic cost model, and
the XLA-compiled peak memory (``Executor.train_step_memory_analysis``). The
summary is a plain-JSON dict written to ``--telemetry-file``.

``SearchLog`` is the Unity/MCMC per-iteration log (candidate cost,
accept/reject, temperature, best-so-far), streamed as JSONL when
``--search-log`` is set and mirrored to the process tracer — the machine-
readable replacement for watching the search's debug logging scroll by
(reference: the strategy-export workflow plus Legion Prof's search phase).
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

from .builds import build_mark, built_since
from .trace import get_tracer

def detect_peak_flops() -> Optional[float]:
    """Per-chip peak bf16 FLOP/s of the current backend from the one
    generation table (``machine_model.TPU_GENERATIONS``), or None off-TPU
    (an MFU against a CPU 'peak' would be meaningless). A TPU whose
    device_kind is not in the table is an error, not a default."""
    from ..search.machine_model import (TPU_GENERATIONS,
                                        local_tpu_generation)

    gen = local_tpu_generation()
    return None if gen is None else TPU_GENERATIONS[gen][0]


def model_flops_per_step(pcg, backward: bool = True) -> int:
    """Analytic model FLOPs for one training step from the existing per-op
    cost hooks (Op.flops; reference: measure_operator_cost's analytical
    side). Backward is costed as 2x forward — the standard grad-of-matmul
    accounting the simulator also uses."""
    total = 0
    for node in pcg.compute_nodes():
        in_shapes = [pcg.nodes[g].out_shapes[i] for g, i in node.inputs]
        try:
            total += int(node.op.flops(in_shapes, list(node.out_shapes)))
        except Exception:
            continue  # ops without a cost hook contribute 0
    return total * 3 if backward else total


class StepTelemetry:
    """Accumulates per-step records host-side; nothing device-facing happens
    here (the caller hands in already-transferred host scalars)."""

    def __init__(self, batch_size: int = 0, phase: str = "train"):
        self.phase = phase
        self.batch_size = batch_size
        self.step_wall_s: List[float] = []
        self.loss_history: List[float] = []
        self.epoch_loss: List[float] = []
        self.metric_history: List[Dict[str, float]] = []
        self.flops_per_step: Optional[int] = None
        self.peak_flops: Optional[float] = None
        self.device_memory: Optional[Dict[str, int]] = None
        self.total_wall_s: float = 0.0
        # the programs built while this record was open (obs/builds.py):
        # from ``build_mark`` (the run's entry: fit() and the serve loop set
        # theirs) to ``finalize()``
        self.build_mark: int = build_mark()
        self.programs_built: int = 0
        self.build_s: float = 0.0
        self.built_by_name: Dict[str, int] = {}
        # resilience counters (ISSUE 4): filled by the fit loop's
        # ResilienceSession at close — fault events (non-finite steps,
        # preemption signals), recovery events (resume/rollback/flush),
        # steps the sentinel skipped, checkpoints committed, and the step
        # the run last resumed/rolled back to
        self.fault_events: int = 0
        self.recovery_events: int = 0
        self.skipped_steps: int = 0
        self.checkpoints_saved: int = 0
        self.last_resume_step: Optional[int] = None
        # strategy-safety counters (ISSUE 5): filled by the fit loop's
        # StrategyCascade — compile-time fallbacks taken, parallel-
        # correctness audits run/failed, and the strategy the run actually
        # trained under (which may not be the search winner)
        self.strategy_fallbacks: int = 0
        self.audit_runs: int = 0
        self.audit_failures: int = 0
        self.final_strategy: Optional[str] = None
        # static-analysis counters (ISSUE 7): ShardLint runs from cascade
        # stage 0 — analyses run, candidates statically rejected, and the
        # rule IDs (FF001..FF006) that fired
        self.static_checks: int = 0
        self.static_rejects: int = 0
        self.static_rules: List[str] = []
        # calibration counters (ISSUE 8): filled by the fit loop's
        # CalibrationLoop after each ProfiledStep pass — profiled key
        # count, sim-vs-measured aggregate/worst ratios, keys outside the
        # --drift-tolerance band, recalibrations applied (with the exact
        # delta-cost cache invalidation count) and the post-repair ratio
        self.calib_profiled_keys: int = 0
        self.calib_aggregate_ratio: Optional[float] = None
        self.calib_worst_key: Optional[str] = None
        self.calib_worst_ratio: Optional[float] = None
        self.calib_out_of_band: int = 0
        self.calib_tolerance: Optional[float] = None
        self.calib_recalibrations: int = 0
        self.calib_invalidated: int = 0
        self.calib_ratio_after: Optional[float] = None
        # serving counters (ISSUE 6): filled by the ServingEngine after a
        # serve() run — requests completed, tokens emitted, the bounded
        # admission queue's high-water mark and the per-token latency
        # percentiles, mirroring the resilience / strategy_safety blocks
        self.requests_served: int = 0
        self.tokens_generated: int = 0
        self.queue_depth_hwm: int = 0
        self.serving_p50_token_ms: Optional[float] = None
        self.serving_p99_token_ms: Optional[float] = None
        self.serving_tokens_per_s: Optional[float] = None
        # host-overhead split (ISSUE 16): fraction of serve-loop wall the
        # HOST spent dispatching + bookkeeping (vs blocked on the device)
        # — the ROADMAP "host overhead" baseline, per engine and fleet
        self.serving_host_overhead_fraction: Optional[float] = None
        # sequence-parallel decode (ISSUE 18): mean per-step occupied KV
        # bytes one shard chip holds (pool bytes at measured fill /
        # seq_shards) — the recorded number behind "KV provably exceeds
        # one chip"
        self.serving_kv_hbm_per_chip_bytes: Optional[int] = None
        # the decode attention kernel's steps: slot steps plus live key
        # tiles of one layer's call summed over decode steps, and the
        # live tiles among them (ServingStats.kv_tiles_grid / _live)
        self.serving_kv_tiles_grid: int = 0
        self.serving_kv_tiles_live: int = 0
        # routed expert layers at decode shapes (ServingStats.moe_*)
        self.serving_moe_pairs_here: int = 0
        self.serving_moe_experts_live: int = 0
        self.serving_moe_load_max_permille: int = 0
        self.serving_moe_bounded_steps: int = 0
        self.serving_moe_layer_steps: int = 0
        # recurrent nodes' slot-major state the decode steps read plus
        # wrote, and the live slots among those stepped (ServingStats)
        self.serving_recurrent_state_bytes: int = 0
        self.serving_recurrent_slots_live: int = 0
        # that state as the chip rests it (lane and sublane padding in),
        # once, and the most heads a row of it holds
        self.serving_recurrent_state_bytes_at_rest: int = 0
        self.serving_state_heads_a_row: int = 0
        # latent nodes' pool rows the decode steps folded, and the decode
        # state's allocated bytes by kind of cache (ServingStats)
        self.serving_latent_rows_read: int = 0
        self.serving_cache_bytes_by_kind: Dict[str, int] = {}
        # one-shot prefills: the buckets' rows computed, and the real ones
        self.serving_prefill_rows: int = 0
        self.serving_prefill_rows_real: int = 0
        # serving-resilience counters (ISSUE 9): the outcome ledger of a
        # serve() run (every request under exactly one of ok |
        # deadline_exceeded | shed | decode_fault | preempted) plus the
        # shed/deadline/quarantine/drain/replan event counts — filled by
        # ServingEngine._merge_telemetry
        self.serving_outcomes: Dict[str, int] = {}
        self.serving_sheds: int = 0
        self.serving_deadline_misses: int = 0
        self.serving_quarantines: int = 0
        self.serving_drains: int = 0
        self.serving_replans: int = 0
        # prefix-cache / chunked-prefill counters (ISSUE 14): the
        # ``serving_prefix`` block — trie hits, prompt tokens whose
        # prefill was served from cache vs computed, LRU evictions and
        # chunk-prefill dispatches — filled by
        # ServingEngine._merge_telemetry
        self.serving_prefix_hits: int = 0
        self.serving_prefix_tokens_reused: int = 0
        self.serving_prefill_tokens_computed: int = 0
        self.serving_cache_evictions: int = 0
        self.serving_chunked_prefills: int = 0
        # fleet counters (ISSUE 11): the multi-replica router's run —
        # fleet-wide outcome ledger, per-replica dispatch split,
        # migrations/hedges/failovers and the health machinery's
        # probe/circuit activity — filled by ServingFleet._merge_telemetry
        self.fleet_replicas: int = 0
        self.fleet_ticks: int = 0
        self.fleet_requests: int = 0
        self.fleet_tokens_generated: int = 0
        self.fleet_outcomes: Dict[str, int] = {}
        self.fleet_sheds: int = 0
        self.fleet_dispatches: List[int] = []
        self.fleet_migrations: int = 0
        self.fleet_hedges: int = 0
        self.fleet_hedge_twin_wins: int = 0
        self.fleet_affinity_hits: int = 0
        self.fleet_probes: int = 0
        self.fleet_circuit_opens: int = 0
        self.fleet_failovers: int = 0
        self.fleet_health_transitions: int = 0
        self.fleet_host_overhead_fraction: Optional[float] = None
        # multi-tenant + autoscale (ISSUE 19): per-tenant rows
        # {tenant: {requests, tokens, outcomes}} and the autoscaler's
        # action counts — filled by ServingFleet._merge_telemetry
        self.fleet_tenants: Dict[str, Any] = {}
        self.fleet_quota_sheds: int = 0
        self.fleet_autoscale_ups: int = 0
        self.fleet_autoscale_downs: int = 0
        # request-journal counters (ISSUE 20): the ``serving_journal``
        # block — write-ahead records appended / group-commit fsyncs /
        # rids replayed at recovery / door dedupe hits / segments
        # compacted away / torn-tail records truncated on open, plus the
        # recovery wall — filled by ServingFleet._merge_telemetry when
        # --request-journal is on
        self.journal_appended: int = 0
        self.journal_syncs: int = 0
        self.journal_replayed: int = 0
        self.journal_dedupe_hits: int = 0
        self.journal_compacted_segments: int = 0
        self.journal_truncated_records: int = 0
        self.journal_recovery_wall_s: float = 0.0
        self._t_start = time.perf_counter()

    # -- recording ----------------------------------------------------------
    def record_step(self, wall_s: float, loss: Optional[float] = None,
                    metrics: Optional[Dict[str, float]] = None) -> None:
        self.step_wall_s.append(wall_s)
        if loss is not None:
            self.loss_history.append(float(loss))
        if metrics:
            self.metric_history.append(
                {k: float(v) for k, v in metrics.items()})

    def record_epoch(self, loss: Optional[float] = None) -> None:
        if loss is not None:
            self.epoch_loss.append(float(loss))

    def finalize(self) -> None:
        self.total_wall_s = time.perf_counter() - self._t_start
        self.programs_built, self.build_s, self.built_by_name = \
            built_since(self.build_mark)

    # -- derived numbers ----------------------------------------------------
    @property
    def steps(self) -> int:
        return len(self.step_wall_s)

    def first_step_s(self) -> Optional[float]:
        """First-step wall time — it holds the step program's one build."""
        return self.step_wall_s[0] if self.step_wall_s else None

    def steady_step_s(self) -> Optional[float]:
        """Median steady-state step time, compile step excluded. None when
        only the compile step was recorded — deriving throughput/MFU from a
        wall that is mostly XLA compile would be silently misleading."""
        rest = sorted(self.step_wall_s[1:])
        return rest[len(rest) // 2] if rest else None

    def samples_per_sec(self) -> Optional[float]:
        st = self.steady_step_s()
        if not st or not self.batch_size:
            return None
        return self.batch_size / st

    def mfu(self) -> Optional[float]:
        st = self.steady_step_s()
        if not st or not self.flops_per_step or not self.peak_flops:
            return None
        return (self.flops_per_step / st) / self.peak_flops

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "phase": self.phase,
            "steps": self.steps,
            "batch_size": self.batch_size,
            "total_wall_s": round(self.total_wall_s, 4),
            "loss_history": self.loss_history,
            "epoch_loss": self.epoch_loss,
            # what the run built, by the registry (obs/builds.py): not zero
            # after a warm-up = it recompiled, and by_name says what
            "programs_built": self.programs_built,
        }
        if self.programs_built:
            out["build_s"] = round(self.build_s, 6)
            out["by_name"] = dict(self.built_by_name)
        if self.step_wall_s:
            out["first_step_s"] = round(self.first_step_s(), 6)
            steady = self.steady_step_s()
            if steady is not None:
                out["steady_step_s"] = round(steady, 6)
            # build_s under the name it had as a difference of step walls
            out["compile_overhead_s"] = round(self.build_s, 6)
        sps = self.samples_per_sec()
        if sps is not None:
            out["samples_per_sec"] = round(sps, 2)
        if self.flops_per_step:
            out["model_flops_per_step"] = self.flops_per_step
        mfu = self.mfu()
        if mfu is not None:
            out["estimated_mfu"] = round(mfu, 4)
            out["peak_flops"] = self.peak_flops
        if self.device_memory:
            out["device_memory"] = self.device_memory
        if self.metric_history:
            out["metric_history"] = self.metric_history
        if (self.fault_events or self.recovery_events or self.skipped_steps
                or self.checkpoints_saved
                or self.last_resume_step is not None):
            res: Dict[str, Any] = {
                "fault_events": self.fault_events,
                "recovery_events": self.recovery_events,
                "skipped_steps": self.skipped_steps,
                "checkpoints_saved": self.checkpoints_saved,
            }
            if self.last_resume_step is not None:
                res["last_resume_step"] = self.last_resume_step
            out["resilience"] = res
        if (self.strategy_fallbacks or self.audit_runs
                or self.final_strategy is not None):
            ss: Dict[str, Any] = {
                "fallbacks": self.strategy_fallbacks,
                "audit_runs": self.audit_runs,
                "audit_failures": self.audit_failures,
            }
            if self.final_strategy is not None:
                ss["final_strategy"] = self.final_strategy
            out["strategy_safety"] = ss
        if self.static_checks:
            out["strategy_static"] = {
                "checks": self.static_checks,
                "rejects": self.static_rejects,
                "rules": list(self.static_rules),
            }
        if self.calib_profiled_keys:
            cal: Dict[str, Any] = {
                "profiled_keys": self.calib_profiled_keys,
                "out_of_band": self.calib_out_of_band,
                "recalibrations": self.calib_recalibrations,
                "invalidated_entries": self.calib_invalidated,
            }
            if self.calib_aggregate_ratio is not None:
                cal["aggregate_ratio"] = round(self.calib_aggregate_ratio, 4)
            if self.calib_worst_key is not None:
                cal["worst_key"] = self.calib_worst_key
            if self.calib_worst_ratio is not None:
                cal["worst_ratio"] = round(self.calib_worst_ratio, 4)
            if self.calib_tolerance is not None:
                cal["tolerance"] = self.calib_tolerance
            if self.calib_ratio_after is not None:
                cal["ratio_after"] = round(self.calib_ratio_after, 4)
            out["calibration"] = cal
        if self.requests_served or self.tokens_generated:
            sv: Dict[str, Any] = {
                "requests_served": self.requests_served,
                "tokens_generated": self.tokens_generated,
                "queue_depth_hwm": self.queue_depth_hwm,
            }
            if self.serving_tokens_per_s is not None:
                sv["tokens_per_s"] = self.serving_tokens_per_s
            if self.serving_p50_token_ms is not None:
                sv["p50_token_ms"] = round(self.serving_p50_token_ms, 3)
            if self.serving_p99_token_ms is not None:
                sv["p99_token_ms"] = round(self.serving_p99_token_ms, 3)
            if self.serving_host_overhead_fraction is not None:
                sv["host_overhead_fraction"] = round(
                    self.serving_host_overhead_fraction, 4)
            if self.serving_kv_hbm_per_chip_bytes is not None:
                sv["kv_hbm_per_chip_bytes"] = \
                    int(self.serving_kv_hbm_per_chip_bytes)
            if self.serving_kv_tiles_grid:
                sv["kv_tiles_grid"] = self.serving_kv_tiles_grid
                sv["kv_tiles_live"] = self.serving_kv_tiles_live
                sv["decode_grid_live_share"] = round(
                    self.serving_kv_tiles_live
                    / self.serving_kv_tiles_grid, 4)
            if self.serving_moe_pairs_here:
                sv["moe_pairs_here"] = self.serving_moe_pairs_here
                sv["moe_experts_live"] = self.serving_moe_experts_live
                sv["moe_load_max_permille"] = \
                    self.serving_moe_load_max_permille
                sv["moe_bounded_steps"] = self.serving_moe_bounded_steps
                sv["moe_layer_steps"] = self.serving_moe_layer_steps
            if self.serving_recurrent_state_bytes:
                sv["recurrent_state_bytes"] = \
                    self.serving_recurrent_state_bytes
                sv["recurrent_slots_live"] = \
                    self.serving_recurrent_slots_live
                sv["recurrent_state_bytes_at_rest"] = \
                    self.serving_recurrent_state_bytes_at_rest
                sv["state_heads_a_row"] = self.serving_state_heads_a_row
            if self.serving_prefill_rows:
                sv["prefill_rows"] = self.serving_prefill_rows
                sv["prefill_rows_real"] = self.serving_prefill_rows_real
            if self.serving_latent_rows_read:
                sv["latent_rows_read"] = self.serving_latent_rows_read
            if self.serving_cache_bytes_by_kind:
                sv["cache_bytes_by_kind"] = dict(
                    self.serving_cache_bytes_by_kind)
            out["serving"] = sv
        if self.fleet_replicas:
            total = max(sum(self.fleet_outcomes.values()), 1)
            fl: Dict[str, Any] = {
                "replicas": self.fleet_replicas,
                "ticks": self.fleet_ticks,
                "requests": self.fleet_requests,
                "tokens_generated": self.fleet_tokens_generated,
                "outcomes": dict(self.fleet_outcomes),
                "shed_rate": round(self.fleet_sheds / total, 4),
                "dispatches": list(self.fleet_dispatches),
                "migrations": self.fleet_migrations,
                "hedges": self.fleet_hedges,
                "hedge_twin_wins": self.fleet_hedge_twin_wins,
                "affinity_hits": self.fleet_affinity_hits,
                "probes": self.fleet_probes,
                "circuit_opens": self.fleet_circuit_opens,
                "failovers": self.fleet_failovers,
                "health_transitions": self.fleet_health_transitions,
            }
            if self.fleet_host_overhead_fraction is not None:
                fl["host_overhead_fraction"] = round(
                    self.fleet_host_overhead_fraction, 4)
            if self.fleet_tenants:
                fl["tenants"] = {t: dict(v) for t, v
                                 in self.fleet_tenants.items()}
            if self.fleet_quota_sheds:
                fl["quota_sheds"] = self.fleet_quota_sheds
            if self.fleet_autoscale_ups or self.fleet_autoscale_downs:
                fl["autoscale"] = {"ups": self.fleet_autoscale_ups,
                                   "downs": self.fleet_autoscale_downs}
            out["fleet"] = fl
        if (self.serving_prefix_hits or self.serving_prefix_tokens_reused
                or self.serving_prefill_tokens_computed
                or self.serving_cache_evictions
                or self.serving_chunked_prefills):
            total = (self.serving_prefix_tokens_reused
                     + self.serving_prefill_tokens_computed)
            out["serving_prefix"] = {
                "hits": self.serving_prefix_hits,
                "tokens_reused": self.serving_prefix_tokens_reused,
                "tokens_computed": self.serving_prefill_tokens_computed,
                "reuse_rate": round(
                    self.serving_prefix_tokens_reused / total, 4)
                if total else 0.0,
                "evictions": self.serving_cache_evictions,
                "chunked_prefills": self.serving_chunked_prefills,
            }
        if (self.serving_outcomes or self.serving_sheds
                or self.serving_deadline_misses or self.serving_quarantines
                or self.serving_drains or self.serving_replans):
            total = max(sum(self.serving_outcomes.values()), 1)
            out["serving_resilience"] = {
                "outcomes": dict(self.serving_outcomes),
                "shed_rate": round(self.serving_sheds / total, 4),
                "deadline_miss_rate": round(
                    self.serving_deadline_misses / total, 4),
                "quarantines": self.serving_quarantines,
                "drains": self.serving_drains,
                "replans": self.serving_replans,
            }
        if self.journal_appended or self.journal_replayed:
            out["serving_journal"] = {
                "appended": self.journal_appended,
                "syncs": self.journal_syncs,
                "replayed": self.journal_replayed,
                "dedupe_hits": self.journal_dedupe_hits,
                "compacted_segments": self.journal_compacted_segments,
                "truncated_records": self.journal_truncated_records,
                "recovery_wall_s": round(
                    self.journal_recovery_wall_s, 6),
            }
        return out

    def write(self, path: str) -> str:
        from .trace import atomic_write_json

        return atomic_write_json(path, self.summary())


def peak_memory_bytes(ma) -> Optional[int]:
    """XLA's compiled peak (``CompiledMemoryStats.peak_memory_in_bytes``),
    or None when the backend reports no stats or a zero peak."""
    if ma is None:
        return None
    return int(ma.peak_memory_in_bytes) or None


def capture_memory_analysis(executor, params, opt_state, xs, labels
                            ) -> Optional[Dict[str, int]]:
    """Best-effort XLA compiled-memory capture for the telemetry record.
    Never raises: memory stats are advisory and some backends don't expose
    them."""
    try:
        ma = executor.train_step_memory_analysis(params, opt_state, xs,
                                                 labels)
        if ma is None:
            return None
        out = {}
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, field, None)
            if v is not None:
                out[field] = int(v)
        peak = peak_memory_bytes(ma)
        if peak is not None:
            out["peak_memory_in_bytes"] = peak
        return out or None
    except Exception:
        return None


class SearchLog:
    """Per-iteration search telemetry sink. Every ``log()`` lands as a JSONL
    line (when ``path`` is set) and as an instant event on the process tracer
    (when tracing is enabled) — one call site, both sinks. Safe to construct
    unconditionally: with no path and tracing disabled it degrades to a
    counter."""

    def __init__(self, path: Optional[str] = None, kind: str = "unity"):
        self.path = path
        self.kind = kind
        self.iterations = 0
        # per-event-type record counts (e.g. "candidate", "xfer",
        # "pipeline_candidate"): unity_search derives its candidates/sec
        # metric from these, so the rate in the final record always matches
        # what the log actually streamed
        self.counts: Dict[str, int] = {}
        self._fh = None  # set BEFORE open(): __del__ must find the attr
        # even when open() raises on a bad path
        if path:
            # line-buffered: the log is for WATCHING a live search (tail
            # -f) and must survive a mid-search kill
            self._fh = open(path, "a", buffering=1)

    def log(self, **rec) -> None:
        self.iterations += 1
        ev = rec.get("event")
        if ev:
            self.counts[ev] = self.counts.get(ev, 0) + 1
        rec.setdefault("search", self.kind)
        rec.setdefault("iter", self.iterations)
        if self._fh is not None:
            self._fh.write(json.dumps(rec, default=str) + "\n")
        tracer = get_tracer()
        if tracer.enabled:
            tracer.event(f"{self.kind}_iter", **rec)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.flush()
            self._fh.close()
            self._fh = None

    def __del__(self):
        # a search that raises mid-run drops its SearchLog frame without
        # reaching the explicit close(); refcount collection closes the fd
        # (writes are line-buffered, so no records are lost either way)
        self.close()
