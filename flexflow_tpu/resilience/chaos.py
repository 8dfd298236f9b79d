"""Deterministic fault injection: the resilience paths must be testable.

Real failures (a TPU preemption SIGTERM, a NaN'd loss, bit rot in a
checkpoint) are rare and non-deterministic; this harness scripts them so
every recovery path runs on CPU in the fast test tier:

* ``ChaosPlan(nan_at_steps={K})`` — poison the batch of step K with NaN
  (the sentinel sees a genuinely non-finite loss/grad, exactly as a real
  divergence would produce one).
* ``ChaosPlan(preempt_at_step=M)`` — deliver a real ``SIGTERM`` to the
  process right before step M dispatches, driving the same signal handler
  a preemptible TPU pool would (``Model.fit`` installs it; the step
  finishes, a final checkpoint is flushed, fit returns).
* ``corrupt_checkpoint(path)`` — truncate / bit-flip / un-commit a written
  checkpoint, for exercising the commit-marker and checksum defenses.
* ``ChaosPlan(fail_compiles=N)`` — the strategy-safety cascade's
  compile check (resilience/fallback.py) raises a scripted XLA-compile
  failure for the first N candidates, driving the ranked-fallback path.
* ``ChaosPlan(wrong_reshard=True)`` — a wrong-reshard defect for the
  strategy-safety layer, in one of three modes
  (``wrong_reshard_mode``):

  - ``"duplicate"`` (graph-level): :func:`inject_wrong_reshard`
    inserts a REAL doubled-reduction node into the candidate PCG —
    statically visible (the analyzer's FF001 names it with zero step
    executions) AND dynamically real (the node scales its value by
    ``wrong_reshard_factor`` under a multi-device mesh, so the
    parallel-correctness audit's probe diverges from the single-device
    reference exactly like a double-counted allreduce). The static check
    and the dynamic audit are exercised against the same concrete
    defect. Note the end-to-end loss/grad-norm movement is damped by the
    loss (softmax shift tolerance): with the default ``--audit-tol``
    0.05 pass ``wrong_reshard_factor >= 3`` for a reliably-failing
    audit; the static FF001 catch is factor-independent.
  - ``"drop"`` (graph-level): remove a real reduction edge — the
    unreduced-partial FF001 class. Statically caught; dynamically
    invisible under XLA SPMD (the partitioner re-derives the psum from
    the shardings), which is precisely why the static check exists.
  - ``"scale"`` (legacy, the default): the auditor merely scales the
    candidate's reported grad norm by ``wrong_reshard_factor`` — no
    graph change; works on any graph, including pure-dp plans with no
    reduction to break.

Pass a plan to ``Model.fit(..., chaos=plan)``. Injection is once-per-step
by default so a run that rolls back and re-executes step K replays it
*clean* — the transient-fault model under which recovery must reconverge
to the uninterrupted trajectory. The strategy-safety injections follow the
same once model: the NEXT candidate compiles/audits clean, so the cascade
lands on a working fallback.
"""
from __future__ import annotations

import os
import signal
from typing import Iterable, List, Optional

from ..execution.checkpoint import COMMIT_MARKER, read_meta


class ChaosPlan:
    """Scripted fault schedule for one training run.

    Steps are global 0-based step indices (the value ``step_count`` holds
    as the step is about to dispatch). With ``once=True`` (default) each
    scripted fault fires a single time even if the step is re-executed
    after a rollback — the transient-fault model.
    """

    def __init__(self, nan_at_steps: Iterable[int] = (),
                 preempt_at_step: Optional[int] = None,
                 preempt_signal: int = signal.SIGTERM,
                 once: bool = True,
                 fail_compiles: int = 0,
                 wrong_reshard: bool = False,
                 wrong_reshard_factor: float = 2.0,
                 wrong_reshard_mode: str = "scale",
                 poison_decode_at: Optional[dict] = None,
                 storm_queue: Optional[dict] = None,
                 storm_max_new_tokens: int = 4,
                 preempt_serving_at: Optional[int] = None,
                 drop_devices_at: Optional[dict] = None):
        self.nan_at_steps = {int(s) for s in nan_at_steps}
        self.preempt_at_step = (None if preempt_at_step is None
                                else int(preempt_at_step))
        self.preempt_signal = preempt_signal
        self.once = once
        self.injected_nan_steps: List[int] = []
        self.preempted_at: Optional[int] = None
        self._nan_done: set = set()
        # serving extensions (ISSUE 9, serving/resilience.py): step indices
        # are DECODE-step counts of the serve loop (the serving analog of
        # the training step index). poison_decode_at {step: slot} NaN's one
        # slot's KV cache before that decode step dispatches (the guarded
        # decode's isfinite verdict sees genuinely non-finite logits, as a
        # flaky HBM bank would produce); storm_queue {step: [prompt, ...]}
        # submits a scripted burst through the engine's admission control
        # (driving shed-vs-accept deterministically); preempt_serving_at
        # delivers a REAL SIGTERM before that decode step (graceful-drain
        # path); drop_devices_at {step: surviving_n_dev} raises a scripted
        # device loss (auto elastic_replan path).
        self.poison_decode_at = {int(k): int(v) for k, v in
                                 (poison_decode_at or {}).items()}
        self.storm_queue = {int(k): list(v) for k, v in
                            (storm_queue or {}).items()}
        self.storm_max_new_tokens = int(storm_max_new_tokens)
        self.preempt_serving_at = (None if preempt_serving_at is None
                                   else int(preempt_serving_at))
        self.drop_devices_at = {int(k): int(v) for k, v in
                                (drop_devices_at or {}).items()}
        self.poisoned_decode_steps: List[int] = []
        self.storms_injected = 0
        self.serving_preempted_at: Optional[int] = None
        self.devices_dropped: List[int] = []
        self._decode_poison_done: set = set()
        self._storm_done: set = set()
        self._drop_done: set = set()
        # strategy-safety injections (resilience/fallback.py, audit.py)
        self.fail_compiles = int(fail_compiles)
        self.compile_failures_injected = 0
        self.wrong_reshard = bool(wrong_reshard)
        self.wrong_reshard_factor = float(wrong_reshard_factor)
        if wrong_reshard_mode not in ("scale", "drop", "duplicate"):
            raise ValueError(
                f"wrong_reshard_mode must be scale|drop|duplicate, got "
                f"{wrong_reshard_mode!r}")
        self.wrong_reshard_mode = wrong_reshard_mode
        self.wrong_reshards_injected = 0
        self.injected_defect = ""  # description of the graph-level defect

    # -- hooks called by Model.fit ------------------------------------------
    def poison_batch(self, step: int, bx):
        """Replace the first floating-point input of step ``step`` with NaN
        (dtype-preserving, so the jitted step does not retrace)."""
        if step not in self.nan_at_steps or \
                (self.once and step in self._nan_done):
            return bx
        import jax.numpy as jnp

        bx = list(bx)
        for i, a in enumerate(bx):
            if jnp.issubdtype(a.dtype, jnp.floating):
                bx[i] = a * jnp.asarray(float("nan"), dtype=a.dtype)
                self._nan_done.add(step)
                self.injected_nan_steps.append(step)
                return bx
        raise ValueError(
            "ChaosPlan.nan_at_steps needs a floating-point model input to "
            f"poison; step {step}'s batch has dtypes "
            f"{[str(a.dtype) for a in bx]}")

    # -- hooks called by the strategy-safety cascade / auditor --------------
    def strategy_chaos_pending(self) -> bool:
        """Any strategy-safety injection still pending? (What arms the
        fallback cascade's pre-fit verification.)"""
        return (self.compile_failures_injected < self.fail_compiles
                or (self.wrong_reshard
                    and (not self.once
                         or self.wrong_reshards_injected == 0)))

    def consume_compile_failure(self) -> bool:
        """True while scripted compile failures remain: the cascade's
        compile check treats it exactly like XLA rejecting the plan. Each
        call consumes one injection, so candidate N+fail_compiles compiles
        clean and the cascade lands on it."""
        if self.compile_failures_injected < self.fail_compiles:
            self.compile_failures_injected += 1
            return True
        return False

    def consume_wrong_reshard(self) -> float:
        """Grad-norm factor the auditor applies to the CANDIDATE probe —
        != 1.0 while a ``"scale"``-mode injection is pending, simulating a
        plan whose miscompiled resharding double-counts the gradient
        allreduce (loss matches the reference, the grad norm is off by
        the factor). Graph-level modes return 1.0: their defect is a real
        node in the graph (``apply_wrong_reshard``), not a reporting
        tweak. With ``once=True`` it fires on a single audit, so the
        cascade's next candidate audits clean."""
        if self.wrong_reshard and self.wrong_reshard_mode == "scale" and \
                (not self.once or self.wrong_reshards_injected == 0):
            self.wrong_reshards_injected += 1
            return self.wrong_reshard_factor
        return 1.0

    def graph_defect_pending(self) -> bool:
        """A graph-level wrong-reshard injection (mode drop/duplicate)
        that has not been applied yet — the cascade applies it to the
        model's live PCG at the top of ``preverify``."""
        return (self.wrong_reshard
                and self.wrong_reshard_mode in ("drop", "duplicate")
                and (not self.once or self.wrong_reshards_injected == 0))

    def apply_wrong_reshard(self, ffmodel) -> str:
        """Mutate the model's live PCG with the scripted reshard defect
        (``inject_wrong_reshard``). A graph with no reduction edge to
        break (e.g. a pure-dp plan) degrades to the legacy ``"scale"``
        simulation with a warning, so the injection never silently does
        nothing. Returns a description of what was injected."""
        try:
            desc = inject_wrong_reshard(ffmodel.pcg, ffmodel.strategy,
                                        mode=self.wrong_reshard_mode,
                                        factor=self.wrong_reshard_factor)
        except ValueError as e:
            import warnings

            warnings.warn(
                f"ChaosPlan wrong_reshard_mode="
                f"{self.wrong_reshard_mode!r}: no injection site ({e}); "
                "falling back to the legacy grad-norm scale simulation")
            self.wrong_reshard_mode = "scale"
            return ""
        self.wrong_reshards_injected += 1
        self.injected_defect = desc
        return desc

    def maybe_preempt(self, step: int) -> None:
        """Deliver the scripted preemption signal before step ``step``
        dispatches. Goes through ``os.kill`` so the REAL installed signal
        handler runs — the fit loop then finishes the in-flight step,
        flushes a final checkpoint and returns, exactly the TPU
        grace-window protocol."""
        if self.preempt_at_step is None or self.preempted_at is not None \
                or step != self.preempt_at_step:
            return
        self.preempted_at = step
        os.kill(os.getpid(), self.preempt_signal)

    # -- hooks called by the serving engine (ISSUE 9) -----------------------
    def maybe_poison_decode(self, step: int, state):
        """NaN one slot's KV-cache rows before decode step ``step``
        dispatches; returns ``(state, slot-or-None)``. Poisoning the cache
        (not the logits post-hoc) means the guarded decode step's fused
        isfinite check judges genuinely non-finite arithmetic — the same
        contract as ``poison_batch`` for the training sentinel. Floating
        leaves only (length cursors stay intact); batch-row independence
        of the decode ops keeps every other slot bitwise-untouched."""
        slot = self.poison_decode_at.get(step)
        if slot is None or (self.once and step in self._decode_poison_done):
            return state, None
        self._decode_poison_done.add(step)
        self.poisoned_decode_steps.append(step)
        return poison_decode_state(state, slot), slot

    def maybe_storm(self, step: int) -> List:
        """Scripted queue storm: the prompt burst to submit through the
        engine's admission control before decode step ``step`` (empty list
        when nothing is scheduled). Determinism: same script + same
        engine state -> same shed/accept pattern."""
        if step not in self.storm_queue or \
                (self.once and step in self._storm_done):
            return []
        self._storm_done.add(step)
        self.storms_injected += 1
        return list(self.storm_queue[step])

    def maybe_preempt_serving(self, step: int) -> None:
        """Deliver the scripted preemption signal before decode step
        ``step`` — through ``os.kill`` so the REAL flag-only handler
        (resilience/session.py) runs; the serve loop then drains
        gracefully exactly as a TPU-pool SIGTERM would make it."""
        if self.preempt_serving_at is None \
                or self.serving_preempted_at is not None \
                or step != self.preempt_serving_at:
            return
        self.serving_preempted_at = step
        os.kill(os.getpid(), self.preempt_signal)

    def maybe_drop_devices(self, step: int) -> Optional[int]:
        """Scripted device loss before decode step ``step``: returns the
        surviving device count (the engine raises ``DeviceLossError`` and
        auto-replans onto it) or None."""
        n = self.drop_devices_at.get(step)
        if n is None or (self.once and step in self._drop_done):
            return None
        self._drop_done.add(step)
        self.devices_dropped.append(step)
        return n


def poison_decode_state(state, slot: int):
    """NaN one slot's KV-cache rows of a serving ``DecodeState`` — the
    shared injection primitive behind ``ChaosPlan.maybe_poison_decode``
    (scripted per-step poison) and ``FleetChaosPlan``'s scripted replica
    degrade (a sustained poison *rate* on one replica, ISSUE 11).
    Floating leaves only; every other slot stays bitwise-untouched.

    The victim's rows live in POOL blocks (ISSUE 12), so
    the poison targets exactly the blocks its block-table row occupies
    (``tables[slot, :ceil(len/bs)]``) — never the shared GARBAGE block,
    whose contents must stay finite (a NaN there would leak into every
    co-batched slot's masked-out ``0 * garbage`` contributions and break
    the quarantine isolation this chaos exists to test)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..serving.kvcache import DecodeState

    tables = np.asarray(state.block_tables)
    length = int(np.asarray(state.lengths)[slot])
    caches = dict(state.caches)
    # block_size from any pool leaf (n_blocks, h, bs, hd); a slot
    # with no occupied block (never prefilled) has nothing to poison
    for name, entry in state.caches.items():
        leaves = jax.tree_util.tree_leaves(entry)
        pool_like = [lf for lf in leaves if lf.ndim == 4]
        if not pool_like:
            # slot-major entries (LSTM carry): NaN the slot's own row
            caches[name] = jax.tree.map(
                lambda lf: lf.at[slot].set(
                    jnp.asarray(float("nan"), lf.dtype))
                if jnp.issubdtype(lf.dtype, jnp.floating) else lf,
                entry)
            continue
        if length < 1:
            # never-admitted slot: it occupies NO pool block, so
            # there is nothing to poison — indexing by the slot
            # number here would NaN pool block == slot, which may
            # belong to a LIVE request in another slot
            continue
        bs = int(pool_like[0].shape[2])
        used = -(-length // bs)
        row = tables[slot, :used]
        # never the GARBAGE block (index 0): every co-batched slot's
        # masked-out reads touch it, and a freed slot's cleared row
        # points entirely at it
        row = row[row != 0]
        if row.size == 0:
            continue
        blocks = jnp.asarray(row, jnp.int32)

        def nanify(leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            return leaf.at[blocks].set(
                jnp.asarray(float("nan"), leaf.dtype))

        caches[name] = jax.tree.map(nanify, entry)
    return DecodeState(caches=caches, lengths=state.lengths,
                       block_tables=state.block_tables)


class FleetChaosPlan(ChaosPlan):
    """Scripted fleet-level fault schedule (ISSUE 11, serving/fleet.py).

    Extends :class:`ChaosPlan` with replica-granular faults, keyed on the
    router's FLEET TICK counter (one tick = every live replica advanced
    one scheduler action) — the fleet analog of the serving extensions'
    decode-step keys. All once-semantics, all runnable on CPU in tier-1:

    * ``kill_replica_at={tick: replica}`` — the replica dies abruptly
      mid-decode (DecodeState lost with its mesh); the router migrates
      its in-flight streams to survivors (re-prefilled from host-side
      committed tokens) and re-routes its queue.
    * ``degrade_replica_at={tick: replica}`` — from that tick on, every
      ``degrade_poison_every``-th decode step on the replica NaNs one
      live slot's KV rows (a sustained decode-poison rate, as a flaky
      HBM bank would produce): the quarantine-rate passive signal should
      open the replica's circuit breaker. Cleared by ``rejoin_at``.
    * ``partition_at={tick: replica}`` — router↔replica dispatches raise
      timeouts for ``partition_ticks`` ticks (the replica itself is
      healthy; the router just cannot reach it).
    * ``drain_replica_at={tick: replica}`` — scripted ``fleet.drain``
      (the rolling zero-downtime restart path).
    * ``rejoin_at={tick: replica}`` — a killed/drained/degraded replica
      re-enters through half-open probation (probe decode gates it).
    * ``traffic_step_at={tick: (per_tick, ticks)}`` — a sustained
      traffic step (ISSUE 19): starting at ``tick``, inject ``per_tick``
      synthetic ``storm_tenant`` requests through the REAL fleet door
      every tick for ``ticks`` ticks — the scripted 4x surge the
      autoscaler must absorb.
    * ``tenant_storm_at={tick: (tenant, n)}`` — a one-shot burst of
      ``n`` requests from one tenant (once-semantics like every other
      fleet fault), for proving WFQ isolation under a misbehaving
      neighbor.
    * ``crash_at={tick: mode}`` — whole-PROCESS death mid-serve
      (ISSUE 20): ``"sigkill"`` delivers a real ``SIGKILL`` to the
      current process (run the fleet in a child process for this mode);
      ``"hard"`` is the tier-1 CPU in-process stand-in — the fleet
      drops its journal group-commit buffer and raises
      :class:`~flexflow_tpu.serving.fleet.FleetCrashed` past every
      drain/finish path, so nothing gets to flush. Recovery goes
      through ``ServingFleet.recover()`` on the journal directory.
    """

    def __init__(self, kill_replica_at: Optional[dict] = None,
                 degrade_replica_at: Optional[dict] = None,
                 partition_at: Optional[dict] = None,
                 drain_replica_at: Optional[dict] = None,
                 rejoin_at: Optional[dict] = None,
                 partition_ticks: int = 8,
                 degrade_poison_every: int = 1,
                 traffic_step_at: Optional[dict] = None,
                 tenant_storm_at: Optional[dict] = None,
                 crash_at: Optional[dict] = None,
                 storm_tenant: str = "batch",
                 fleet_storm_max_new: int = 8,
                 fleet_storm_prompt_tokens: int = 3,
                 **kw):
        super().__init__(**kw)
        self.kill_replica_at = {int(k): int(v) for k, v in
                                (kill_replica_at or {}).items()}
        self.degrade_replica_at = {int(k): int(v) for k, v in
                                   (degrade_replica_at or {}).items()}
        self.partition_at = {int(k): int(v) for k, v in
                             (partition_at or {}).items()}
        self.drain_replica_at = {int(k): int(v) for k, v in
                                 (drain_replica_at or {}).items()}
        self.rejoin_at = {int(k): int(v) for k, v in
                          (rejoin_at or {}).items()}
        self.partition_ticks = int(partition_ticks)
        self.degrade_poison_every = max(int(degrade_poison_every), 1)
        self.traffic_step_at = {
            int(k): (int(v[0]), int(v[1]))
            for k, v in (traffic_step_at or {}).items()}
        self.tenant_storm_at = {
            int(k): (str(v[0]), int(v[1]))
            for k, v in (tenant_storm_at or {}).items()}
        self.crash_at = {int(k): str(v) for k, v in
                         (crash_at or {}).items()}
        self.storm_tenant = str(storm_tenant)
        self.fleet_storm_max_new = int(fleet_storm_max_new)
        self.fleet_storm_prompt_tokens = int(fleet_storm_prompt_tokens)
        self.storm_requests_injected = 0
        self.replicas_killed: List[int] = []
        self.replicas_degraded: List[int] = []
        self.replicas_partitioned: List[int] = []
        self.replicas_drained: List[int] = []
        self.replicas_rejoined: List[int] = []
        self.crashes_fired: List[str] = []
        self._fleet_done: set = set()

    def _fire(self, table: dict, tick: int, kind: str,
              log: List[int]) -> Optional[int]:
        replica = table.get(tick)
        if replica is None or (self.once and (kind, tick) in
                               self._fleet_done):
            return None
        self._fleet_done.add((kind, tick))
        log.append(replica)
        return replica

    def maybe_kill_replica(self, tick: int) -> Optional[int]:
        return self._fire(self.kill_replica_at, tick, "kill",
                          self.replicas_killed)

    def maybe_degrade_replica(self, tick: int) -> Optional[int]:
        return self._fire(self.degrade_replica_at, tick, "degrade",
                          self.replicas_degraded)

    def maybe_partition_replica(self, tick: int) -> Optional[int]:
        return self._fire(self.partition_at, tick, "partition",
                          self.replicas_partitioned)

    def maybe_drain_replica(self, tick: int) -> Optional[int]:
        return self._fire(self.drain_replica_at, tick, "drain",
                          self.replicas_drained)

    def maybe_rejoin_replica(self, tick: int) -> Optional[int]:
        return self._fire(self.rejoin_at, tick, "rejoin",
                          self.replicas_rejoined)

    def maybe_crash(self, tick: int) -> Optional[str]:
        """Process-death mode to fire this tick (``"hard"`` or
        ``"sigkill"``), or None. Same once-semantics as every other
        fleet fault."""
        mode = self.crash_at.get(int(tick))
        if mode is None or (self.once and ("crash", tick) in
                            self._fleet_done):
            return None
        self._fleet_done.add(("crash", tick))
        self.crashes_fired.append(mode)
        return mode

    def maybe_fleet_storm(self, tick: int) -> List[tuple]:
        """``[(tenant, n), ...]`` to inject at the fleet door this tick
        (ISSUE 19). One-shot storms honor the once-semantics key; a
        traffic step fires on every tick inside its window (each window
        tick is its own key, so ``once`` replays stay deterministic)."""
        tick = int(tick)
        out: List[tuple] = []
        burst = self.tenant_storm_at.get(tick)
        if burst is not None and not (self.once and
                                      ("tenant_storm", tick)
                                      in self._fleet_done):
            self._fleet_done.add(("tenant_storm", tick))
            out.append(burst)
        for start, (per_tick, n_ticks) in self.traffic_step_at.items():
            if start <= tick < start + n_ticks and not (
                    self.once and ("traffic_step", tick)
                    in self._fleet_done):
                self._fleet_done.add(("traffic_step", tick))
                out.append((self.storm_tenant, per_tick))
        self.storm_requests_injected += sum(n for _t, n in out)
        return out


class _InjectedReductionOp:
    """A REAL doubled-reduction node (lazy subclass factory below): its
    forward scales the value by ``chaos_factor`` — but only under a
    multi-device mesh, exactly like a double-counted allreduce, whose
    damage exists only in the parallel plan. The single-device audit
    reference therefore computes the TRUE value and the divergence is
    caught dynamically, while the analyzer's FF001 names the node
    statically (it is an OP_REDUCTION whose input is not a partial sum)."""

    def __new__(cls, *args, **kwargs):
        from ..parallel.parallel_op import ReductionOp

        class _Injected(ReductionOp):
            def forward(self, params, inputs, ctx):
                x = inputs[0]
                n_dev = (int(ctx.mesh.devices.size)
                         if ctx.mesh is not None else 1)
                factor = float(self.attrs.get("chaos_factor", 2.0))
                if n_dev > 1 and factor != 1.0:
                    import jax.numpy as jnp

                    x = x * jnp.asarray(factor, dtype=x.dtype)
                return [x]

        return _Injected(*args, **kwargs)


def inject_wrong_reshard(pcg, strategy, mode: str = "duplicate",
                         factor: float = 2.0) -> str:
    """Mutate ``pcg`` IN PLACE with a graph-level wrong-reshard defect.

    ``mode="duplicate"``: insert a :class:`_InjectedReductionOp` on the
    output edge of the first reduction site — an explicit ``OP_REDUCTION``
    node (a searched plan after ``insert_parallel_ops``) or a partial-sum
    producer whose ``output_spec`` performs the reduce (a hand/spec-based
    plan) — modelling a duplicated reduction edge. ``mode="drop"``:
    remove that reduction — splice out the ``OP_REDUCTION`` node, or strip
    the producer's reducing ``output_spec`` — modelling a dropped
    reduction edge (statically FF001-unreduced; numerically invisible
    under XLA SPMD, which is why only the static check can catch it).

    Raises ``ValueError`` when the graph has no reduction site (nothing
    to break — e.g. a pure data-parallel plan). Returns a description
    naming the defect and the node, mirroring the analyzer's diagnostic.
    """
    from ..analysis.interp import _partial_axes_produced
    from ..ffconst import OperatorType

    node_strats = strategy.node_strategies if strategy is not None else {}
    site = None  # (node, kind): kind in ("reduction", "producer")
    for node in pcg.compute_nodes():
        if node.op.op_type == OperatorType.OP_REDUCTION and \
                pcg.consumers(node.guid):
            site = (node, "reduction")
            break
    if site is None:
        for node in pcg.compute_nodes():
            ns = node_strats.get(node.guid)
            if _partial_axes_produced(node, ns) and \
                    ns is not None and ns.output_spec is not None and \
                    pcg.consumers(node.guid):
                site = (node, "producer")
                break
    if site is None:
        raise ValueError(
            "no reduction edge to break: the graph has no OP_REDUCTION "
            "node and no partial-sum producer with consumers")
    node, kind = site

    if mode == "drop":
        if kind == "reduction":
            src = node.inputs[0]
            for c in pcg.consumers(node.guid):
                cn = pcg.nodes[c]
                cn.inputs = [src if g == node.guid else (g, i)
                             for g, i in cn.inputs]
            del pcg.nodes[node.guid]
            pcg._order.remove(node.guid)
            node_strats.pop(node.guid, None)
            return (f"dropped reduction node '{node.name}' (consumers "
                    "splice through to its unreduced input)")
        ns = node_strats[node.guid]
        ns.output_spec = None
        return (f"dropped the reducing output constraint of partial-sum "
                f"producer '{node.name}'")

    if mode != "duplicate":
        raise ValueError(f"unknown graph defect mode {mode!r}")
    if kind == "reduction":
        axes = tuple(node.op.attrs.get("axes") or ())
        degree = int(node.op.attrs.get("degree", 2) or 2)
    else:
        axes = tuple(_partial_axes_produced(node,
                                            node_strats.get(node.guid)))
        axis_size = dict(zip(tuple(strategy.axis_names),
                             (int(s) for s in strategy.mesh_shape)))
        degree = int(axis_size.get(axes[0], 2)) if axes else 2
    op = _InjectedReductionOp(
        f"chaos_dup_reduction_{node.guid}",
        {"dim": 0, "degree": degree, "axes": axes,
         "chaos_factor": float(factor)},
        node.op.data_type, num_inputs=1)
    consumers = pcg.consumers(node.guid)
    first = pcg.nodes[consumers[0]]
    slot = [s for s, (g, _i) in enumerate(first.inputs)
            if g == node.guid][0]
    new = pcg.insert_node_on_edge(consumers[0], slot, op)
    # insert_node_on_edge rewires exactly one slot; a consumer referencing
    # the reduction output in SEVERAL input slots (e.g. add(r, r)) must
    # have all of them routed through the injected node, like the
    # consumers[1:] rewiring below — else one edge bypasses the defect
    first.inputs = [(new.guid, 0) if g == node.guid else (g, i)
                    for g, i in first.inputs]
    for c in consumers[1:]:
        cn = pcg.nodes[c]
        cn.inputs = [(new.guid, 0) if g == node.guid else (g, i)
                     for g, i in cn.inputs]
    return (f"duplicated the reduction after '{node.name}' as "
            f"'{new.op.name}' (x{factor:g} under a multi-device mesh)")


def corrupt_checkpoint(path: str, mode: str = "truncate") -> str:
    """Deterministically damage a committed checkpoint; returns a
    description of what was done.

    * ``truncate`` — cut the largest checksummed payload file in half
      (a killed copy / torn write).
    * ``flip``     — flip one byte in the middle of that file (bit rot).
    * ``uncommit`` — delete the commit marker (a writer that died between
      staging and commit; ``latest_checkpoint`` must skip the dir).
    """
    path = os.path.abspath(path)
    if mode == "uncommit":
        os.remove(os.path.join(path, COMMIT_MARKER))
        return f"removed {COMMIT_MARKER} from {path}"
    sums = read_meta(path).get("checksums", {})
    if not sums:
        raise ValueError(f"{path}: no checksummed payload files")
    # deterministic victim: the largest file, name as tie-break
    rel = max(sorted(sums), key=lambda r: (sums[r][1], r))
    fp = os.path.join(path, rel)
    size = os.path.getsize(fp)
    if mode == "truncate":
        with open(fp, "r+b") as f:
            f.truncate(max(size // 2, 0))
        return f"truncated {rel} from {size} to {max(size // 2, 0)} bytes"
    if mode == "flip":
        with open(fp, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")
        return f"flipped byte {size // 2} of {rel}"
    raise ValueError(f"unknown corruption mode {mode!r}")
