"""Fleet of fault domains (ISSUE 11, flexflow_tpu/serving/fleet.py,
docs/fleet.md): multi-replica routing with health-checked failover,
cross-replica request migration (unchanged token streams), hedged retries that never double-count, fleet-level shedding
with a floored retry_after_ms, rolling drain/rejoin, per-replica plan
lint, and the fleet-wide exactly-one-outcome ledger under scripted
chaos — all deterministic on CPU."""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.resilience import ChaosPlan, FleetChaosPlan
from flexflow_tpu.serving import (FLEET_MIN_RETRY_AFTER_MS, OUTCOMES,
                                  OverloadError, Request, ServingEngine,
                                  ServingFleet, ServingRejection)
from flexflow_tpu.serving.scheduler import ContinuousBatchScheduler


@pytest.fixture(scope="module")
def gpt2():
    cfg = GPT2Config.tiny(batch_size=8)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg


def _prompts(n, seed=0, lo=3, hi=6):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 100, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _baseline(ff, cfg, prompts, max_new):
    return ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len).generate(
                             prompts, max_new_tokens=max_new)


def _fleet(ff, cfg, **kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_decode_len", cfg.seq_len)
    return ServingFleet(ff, **kw)


# ------------------------------------------------------------- clean routing
def test_clean_fleet_matches_single_replica_bitwise(gpt2):
    """Load-aware dispatch over 2 replicas produces the SAME streams as
    one engine (rng keys on submission tag, not placement), spreads
    traffic across both fault domains, and the fleet ledger closes with
    every request ok."""
    ff, cfg = gpt2
    prompts = _prompts(8, seed=1)
    base = _baseline(ff, cfg, prompts, 6)
    fleet = _fleet(ff, cfg)
    outs = fleet.generate(prompts, max_new_tokens=6)
    assert outs == base, "fleet streams diverged from one-engine run"
    st = fleet.stats
    assert st.outcomes == {"ok": 8}
    assert all(d > 0 for d in st.dispatches), "a replica got no traffic"
    assert sum(st.dispatches) == 8


# ------------------------------------------------- failover + migration
def test_kill_replica_migrates_bitwise_ledger_and_recovery(gpt2):
    """Acceptance (ISSUE 11): kill_replica_at fires mid-decode — the
    fleet completes every request, the exactly-one-outcome ledger is
    conserved, migrated continuations are bitwise-equal to an
    undisturbed single-replica run, aggregate throughput recovers to
    >= (N-1)/N of the pre-kill rate within the probe interval, and the
    dead replica receives zero further dispatches."""
    ff, cfg = gpt2
    prompts = _prompts(10, seed=2)
    base = _baseline(ff, cfg, prompts, 8)
    fleet = _fleet(ff, cfg)
    chaos = FleetChaosPlan(kill_replica_at={4: 0})
    outs = fleet.generate(prompts, max_new_tokens=8, chaos=chaos)
    st = fleet.stats
    assert chaos.replicas_killed == [0]
    assert outs == base, "migrated continuations diverged"
    assert st.outcomes == {"ok": 10}
    assert sum(st.outcomes.values()) == 10  # ledger conserved
    assert st.migrations >= 1, "no in-flight stream migrated"
    assert st.failovers == 1
    assert fleet.replicas[0].health == "dead"
    # the dead replica gets zero further dispatches: every migrated
    # stream and requeued request re-dispatched onto the survivor, so
    # total dispatches = 10 first-tries + the re-dispatches
    assert st.dispatches[0] + st.dispatches[1] == \
        10 + st.migrations + st.requeued
    assert st.dispatches[1] >= st.migrations
    # throughput recovery: trailing mean tokens/tick back to >= 1/2 of
    # pre-kill within the probe interval (N=2)
    rec = st.recovery_ticks(st.kill_ticks[0], frac=0.5)
    assert rec is not None and rec <= fleet.health_probe_every, \
        f"throughput did not recover within the probe interval ({rec})"


def test_replica_fatal_error_migrates_instead_of_crashing(gpt2):
    """An error the engine's own failover cannot absorb kills only that
    fault domain: its work migrates and the fleet finishes clean."""
    ff, cfg = gpt2
    prompts = _prompts(6, seed=3)
    base = _baseline(ff, cfg, prompts, 6)
    fleet = _fleet(ff, cfg)
    orig = fleet.replicas[0].engine._dispatch_decode
    state = {"fired": False}

    def boom(*a, **kw):
        if not state["fired"] and \
                fleet.replicas[0].loop.stats.decode_steps >= 2:
            state["fired"] = True
            raise RuntimeError("replica mesh fell off the network")
        return orig(*a, **kw)

    fleet.replicas[0].engine._dispatch_decode = boom
    outs = fleet.generate(prompts, max_new_tokens=6)
    assert state["fired"]
    assert outs == base
    assert fleet.stats.outcomes == {"ok": 6}
    assert fleet.replicas[0].health == "dead"
    assert fleet.stats.failovers == 1


# ------------------------------------------------------- circuit breaker
def test_circuit_open_zero_dispatch_until_probe_passes(gpt2):
    """Acceptance (ISSUE 11): a circuit-open replica receives ZERO
    dispatches until its half-open probe passes — and once it does, the
    replica re-enters rotation."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    sick = fleet.replicas[1]
    # white-box: open the circuit with the half-open probe scheduled a
    # few ticks out; until the probe passes every dispatch must go to
    # replica 0
    sick.circuit.state = "open"
    sick.circuit.opens = 1
    sick.circuit.half_open_at = 6
    sick.health = "quarantined"
    outs = fleet.generate(_prompts(8, seed=4), max_new_tokens=6)
    st = fleet.stats
    assert all(len(o) == 6 for o in outs)
    # probe fired at tick 6 and passed (healthy engine), replica re-entered
    assert sick.circuit.state == "closed"
    assert any(t[1] == 1 and t[3] == "healthy" and t[4] == "probe_pass"
               for t in st.health_transitions), st.health_transitions
    probe_tick = min(t[0] for t in st.health_transitions
                     if t[1] == 1 and t[4] == "probe_pass")
    assert probe_tick >= 6
    # every dispatch before the probe went to replica 0: replica 1's
    # first dispatch (if any) can only have happened after re-entry, so
    # with 8 short requests mostly routed early, replica 0 dominates
    assert st.dispatches[0] >= st.dispatches[1]
    assert st.probes >= 1


def test_degraded_replica_quarantined_queue_rescued(gpt2):
    """A sustained decode-poison rate (degrade_replica_at) drives the
    passive quarantine signal: the circuit opens, the sick replica's
    queued requests are rescued to healthy replicas, and completed
    streams stay bitwise-equal to an undisturbed run."""
    ff, cfg = gpt2
    prompts = _prompts(10, seed=5)
    base = _baseline(ff, cfg, prompts, 8)
    fleet = _fleet(ff, cfg)
    chaos = FleetChaosPlan(degrade_replica_at={3: 1},
                           degrade_poison_every=1)
    outs = fleet.generate(prompts, max_new_tokens=8, chaos=chaos)
    st = fleet.stats
    assert st.degrade_poisons >= 1
    assert st.circuit_opens >= 1
    assert any(t[1] == 1 and t[3] == "quarantined"
               for t in st.health_transitions)
    assert st.requeued >= 1, "the sick replica's queue was not rescued"
    # ledger conserved; completed streams bitwise
    assert sum(st.outcomes.values()) == 10
    assert set(st.outcomes) <= set(OUTCOMES)
    done = [i for i, o in enumerate(outs) if len(o) == 8]
    assert done and all(outs[i] == base[i] for i in done)


def test_partition_heals_through_half_open_probe(gpt2):
    """A router<->replica partition opens the circuit via dispatch
    timeouts; after the partition heals, the half-open probe passes and
    the replica rejoins — all requests still finish bitwise."""
    ff, cfg = gpt2
    prompts = _prompts(8, seed=6)
    base = _baseline(ff, cfg, prompts, 8)
    fleet = _fleet(ff, cfg)
    chaos = FleetChaosPlan(partition_at={3: 0}, partition_ticks=6)
    outs = fleet.generate(prompts, max_new_tokens=8, chaos=chaos)
    st = fleet.stats
    assert outs == base
    assert st.outcomes == {"ok": 8}
    trail = [(t[3], t[4]) for t in st.health_transitions if t[1] == 0]
    assert ("quarantined", "partition_timeout") in trail
    assert ("healthy", "probe_pass") in trail


# ----------------------------------------------------------------- hedging
def test_hedge_twin_wins_no_double_count_bitwise(gpt2):
    """A partitioned primary replica stalls its streams; hedge twins on
    the healthy replica win (first new committed token), the losers are
    cancelled with NO ledger entry, and the caller-visible streams are
    bitwise-equal to an undisturbed run."""
    ff, cfg = gpt2
    config = ff.config
    prompts = _prompts(4, seed=7)
    base = _baseline(ff, cfg, prompts, 6)
    config.hedge_after_pctl = 10.0
    try:
        fleet = _fleet(ff, cfg)
        for r in fleet.replicas:
            r.engine.admission.force_token_cost_ms = 1e-6
        chaos = FleetChaosPlan(partition_at={3: 0}, partition_ticks=30)
        outs = fleet.generate(prompts, max_new_tokens=6, chaos=chaos)
        st = fleet.stats
        assert st.hedges >= 1 and st.hedge_twin_wins >= 1
        assert st.hedges_cancelled >= 1
        # no double count: exactly one outcome per submitted request,
        # twins invisible in the ledger
        assert sum(st.outcomes.values()) == 4
        assert st.outcomes == {"ok": 4}
        assert outs == base, "hedged streams diverged"
        # ISSUE 16 satellite pin: adoption mirrors the LATENCY STAMPS
        # with the tokens — every caller-held request reports a real
        # TTFT/completion time even when its winning copy was the twin
        for r in fleet._requests:
            assert r.first_token_ms > 0, "TTFT stamp lost in adoption"
            assert r.finish_ms >= r.first_token_ms > 0
    finally:
        config.hedge_after_pctl = 0.0


def test_hedge_adoption_mirrors_latency_stamps(gpt2):
    """ISSUE 16 satellite fix pin: when a hedge TWIN wins, its
    ``first_token_ms`` / ``finish_ms`` must be mirrored onto the
    caller-held primary along with the tokens — before the fix the
    primary kept stamps of 0.0, so bench TTFT went negative and the
    request trace reported a zero-latency completion."""
    from flexflow_tpu.serving.fleet import _Hedge

    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    p = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4, rng_tag=0)
    t = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4, rng_tag=0,
                generated=[1, 2, 3, 4])
    t.done = True
    t.outcome = "ok"
    t.finish_reason = "length"
    t.first_token_ms = 123.0
    t.finish_ms = 456.0
    fleet._adopted.append(_Hedge(primary=p, twin=t, fork=0,
                                 primary_replica=0, twin_replica=1))
    fleet._mirror_adopted()
    assert p.generated == [1, 2, 3, 4]
    assert p.first_token_ms == 123.0, "twin's TTFT stamp not mirrored"
    assert p.finish_ms == 456.0, "twin's finish stamp not mirrored"
    # a primary that committed tokens BEFORE the hedge fork keeps its
    # own, earlier TTFT — first token is first token wherever it landed
    p2 = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4,
                 rng_tag=1, generated=[9])
    p2.first_token_ms = 50.0
    t2 = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4,
                 rng_tag=1, generated=[9, 10])
    t2.done = True
    t2.outcome = "ok"
    t2.finish_reason = "length"
    t2.first_token_ms = 50.0
    t2.finish_ms = 99.0
    fleet._adopted.append(_Hedge(primary=p2, twin=t2, fork=1,
                                 primary_replica=0, twin_replica=1))
    fleet._mirror_adopted()
    assert p2.first_token_ms == 50.0
    assert p2.finish_ms == 99.0


def test_hedge_cap_and_idle_target_only(gpt2):
    """Hedges are bounded (hedge_cap outstanding) and only target an
    IDLE replica — with every replica busy, no hedge launches, so
    hedging cannot amplify an overload."""
    ff, cfg = gpt2
    config = ff.config
    config.hedge_after_pctl = 1.0
    try:
        fleet = _fleet(ff, cfg, n_slots=1)
        assert fleet.hedge_cap == 1
        for r in fleet.replicas:
            r.engine.admission.force_token_cost_ms = 1e-6
        # enough work that both replicas stay busy: queues non-empty ->
        # no idle target -> hedges may only fire near the drain tail
        outs = fleet.generate(_prompts(8, seed=8), max_new_tokens=6)
        st = fleet.stats
        assert sum(st.outcomes.values()) == 8
        assert st.outcomes == {"ok": 8}
        # the ledger and streams stay clean whatever hedging did
        assert all(len(o) == 6 for o in outs)
    finally:
        config.hedge_after_pctl = 0.0


def test_partition_stranded_streams_survive_to_heal(gpt2):
    """Work stranded on a partitioned replica is PENDING, not done: the
    run loop idles until the partition heals and the streams finish
    bitwise — it must not break and truncate them one tick from
    recovery."""
    ff, cfg = gpt2
    prompts = _prompts(1, seed=15)
    base = _baseline(ff, cfg, prompts, 6)
    fleet = _fleet(ff, cfg)
    # the single request lands on replica 0; partition it mid-stream
    # with replica 1 idle (nothing else to do -> worked=False ticks)
    chaos = FleetChaosPlan(partition_at={2: 0}, partition_ticks=5)
    outs = fleet.generate(prompts, max_new_tokens=6, chaos=chaos)
    assert outs == base, "stranded stream truncated or diverged"
    assert fleet.stats.outcomes == {"ok": 1}


def test_rejoin_rescues_alive_replicas_work(gpt2):
    """rejoin() of a still-alive (degraded) replica harvests the work
    the open circuit deliberately left in place — the scheduler rebuild
    must not drop streams on the floor."""
    ff, cfg = gpt2
    prompts = _prompts(8, seed=16)
    base = _baseline(ff, cfg, prompts, 10)
    fleet = _fleet(ff, cfg)
    # sustained poison opens replica 1's circuit (~tick 5) while its
    # long streams are mid-flight; rejoin fires shortly after, with the
    # replica alive and holding work
    chaos = FleetChaosPlan(degrade_replica_at={3: 1},
                           degrade_poison_every=1, rejoin_at={7: 1})
    outs = fleet.generate(prompts, max_new_tokens=10, chaos=chaos)
    st = fleet.stats
    assert st.rejoins == 1
    # ledger conserved: nothing silently lost to the rebuild
    assert sum(st.outcomes.values()) == 8, st.outcomes
    # every truncated stream carries a real failure outcome; completed
    # ones are bitwise vs the undisturbed run
    done = [i for i, o in enumerate(outs) if len(o) == 10]
    assert done and all(outs[i] == base[i] for i in done)
    assert st.outcomes.get("ok", 0) == len(done)
    # white-box: rejoin of a replica HOLDING work harvests it — slots
    # and queue both land back in the fleet queue, in-flight first
    fleet2 = _fleet(ff, cfg)
    fleet2._start(0.0, 0, 0)
    rep = fleet2.replicas[1]
    stuck = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4,
                    rng_tag=0)
    queued = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4,
                     rng_tag=1)
    rep.sched.slots[0] = stuck
    rep.sched._free.remove(0)
    rep.sched.queue.append(queued)
    fleet2.rejoin(1)
    order = list(fleet2.queue)
    assert order[0] is stuck and order[1] is queued
    assert fleet2.stats.migrations == 1
    assert fleet2.stats.requeued == 1
    assert rep.sched.active == 0 and rep.sched.queued == 0


def test_door_queue_wait_burns_the_deadline_budget(gpt2):
    """The relative deadline starts at the FLEET DOOR: a request stuck
    there (every circuit open) is dropped as deadline_exceeded instead
    of being served arbitrarily late with zero misses recorded."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    for rep in fleet.replicas:
        rep.engine.max_queue = 0  # white-box: nothing can dispatch
        rep.circuit.state = "open"
        rep.circuit.half_open_at = None
    outs = fleet.generate(_prompts(2, seed=17), max_new_tokens=4,
                          deadline_ms=1e-6)
    st = fleet.stats
    assert st.outcomes == {"deadline_exceeded": 2}, st.outcomes
    assert all(o == [] for o in outs)


def test_hedge_rescues_failed_primary(gpt2):
    """A primary evicted as deadline_exceeded/decode_fault must NOT beat
    its still-viable twin — the hedge exists precisely to rescue a
    request whose first try died: the failure is withdrawn from the
    ledger and the twin streams on as the winner."""
    from flexflow_tpu.serving.fleet import _Hedge

    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    fleet._start(0.0, 0, 0)
    p = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4, rng_tag=0)
    t = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4, rng_tag=0)
    p.done = True
    p.outcome = p.finish_reason = "deadline_exceeded"
    fleet.replicas[0].sched.finished.append(p)  # the eviction's ledger
    fleet.replicas[1].sched.submit(t)           # viable twin, queued
    fleet._hedges.append(_Hedge(primary=p, twin=t, fork=0,
                                primary_replica=0, twin_replica=1))
    fleet._hedged_ids.add(id(p))
    fleet._resolve_hedges()
    h = fleet._adopted[-1]
    assert h.winner is t
    assert not fleet.replicas[0].sched.finished, "failure not withdrawn"
    assert p.outcome is None and not p.done
    assert fleet.replicas[1].sched.queued == 1  # twin still in play


def test_passive_success_cannot_close_open_circuit(gpt2):
    """One clean decode of a leftover in-flight slot must not talk a
    quarantined replica back into rotation: an open circuit re-closes
    only through the half-open probe."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    rep = fleet.replicas[0]
    rep.circuit.state = "open"
    rep.circuit.opens = 1
    rep.circuit.half_open_at = 99
    rep.health = "quarantined"
    fleet._circuit_success(rep)
    assert rep.circuit.state == "open"
    assert rep.health == "quarantined"


def test_fleet_sigterm_hands_back_door_queue(gpt2):
    """Requests still in the fleet DOOR queue when a fleet-wide SIGTERM
    drain completes are handed back via drained_requests (outcome
    preempted) — not silently swallowed by the dead-end break."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    for rep in fleet.replicas:
        rep.engine.max_queue = 0  # white-box: nothing can dispatch
    prompts = _prompts(3, seed=14)
    chaos = FleetChaosPlan(preempt_serving_at=1)
    outs = fleet.generate(prompts, max_new_tokens=4, chaos=chaos)
    st = fleet.stats
    assert st.outcomes == {"preempted": 3}
    assert [r.rng_tag for r in fleet.drained_requests] == [0, 1, 2]
    assert all(o == [] for o in outs)
    assert st.drains == 1


def test_migration_preserves_deadline_budget(gpt2):
    """A migrated request's submit stamp survives the re-dispatch: the
    relative deadline budget must not silently restart exactly when a
    replica fails (a fresh request still gets stamped normally)."""
    ff, cfg = gpt2
    # scripted fleet clock so the fake submit stamp is inside its
    # deadline window (the door sweep judges with this same clock)
    fleet = _fleet(ff, cfg, clock=lambda: 1300.0)
    fleet._start(0.0, 0, 0)
    migrated = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4,
                       deadline_ms=100.0)
    migrated.submit_ms = 1234.5  # stamped at its FIRST dispatch
    fresh = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4)
    fleet.queue.extend([migrated, fresh])
    fleet._requests.extend([migrated, fresh])
    fleet._dispatch()
    placed = [r for rep in fleet.replicas if rep.sched is not None
              for r in rep.sched.queue]
    # identity, not ==: Request dataclasses hold ndarrays
    assert any(r is migrated for r in placed)
    assert any(r is fresh for r in placed)
    assert migrated.submit_ms == 1234.5, "deadline budget restarted"
    assert fresh.submit_ms != 0.0, "fresh request never stamped"


# --------------------------------------------------- fleet door shedding
def test_fleet_door_queue_shed_ledgered_and_hinted(gpt2):
    """The 'queue' policy graduates to the router: aggregate depth past
    the fleet high-water sheds with a typed rejection, the request is
    ledgered (outcome shed, exactly once), and the hint carries the
    fleet-derived retry_after_ms."""
    ff, cfg = gpt2
    config = ff.config
    config.shed_policy = "queue"
    try:
        fleet = _fleet(ff, cfg, max_queue=4)
        pat = []
        for i, p in enumerate(_prompts(8, seed=9)):
            r = Request(prompt=np.asarray(p, np.int32), max_new_tokens=4,
                        rng_tag=i)
            try:
                fleet.submit(r)
                pat.append("accept")
            except ServingRejection as e:
                pat.append(type(e).__name__)
                assert e.retry_after_ms >= 0.0
                assert r.outcome == "shed"
        assert pat[:2] == ["accept", "accept"]  # below high-water 4//2
        assert set(pat[2:]) == {"OverloadError"}
        st = fleet.run()
        assert st.outcomes["shed"] == 6
        assert st.outcomes["ok"] == 2
        assert sum(st.outcomes.values()) == 8
    finally:
        config.shed_policy = "off"


def test_retry_after_ms_floored_while_fleet_degraded(gpt2):
    """ISSUE 11 small fix: the fleet door's retry_after_ms must never be
    0 while any replica is draining or circuit-open — even with a cold
    EWMA the hint is floored at FLEET_MIN_RETRY_AFTER_MS, and a healthy
    fleet's hint derives from the BEST replica's drain estimate."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    # fully healthy + cold EWMA: 0 is fine (nothing degraded to protect)
    assert fleet.retry_after_ms() == 0.0
    # one circuit-open replica: floored, cold EWMA or not
    fleet.replicas[1].circuit.state = "open"
    assert fleet.retry_after_ms() >= FLEET_MIN_RETRY_AFTER_MS > 0.0
    fleet.replicas[1].circuit.state = "closed"
    # one draining replica: floored too
    fleet.replicas[0].health = "draining"
    assert fleet.retry_after_ms() >= FLEET_MIN_RETRY_AFTER_MS > 0.0
    # healthy again, warm EWMA + backlog: the hint is the BEST (minimum)
    # healthy replica's drain estimate
    fleet.replicas[0].health = "healthy"
    for rep in fleet.replicas:
        fleet._make_loop(rep)
        rep.engine.admission.force_token_cost_ms = 10.0
    busy = Request(prompt=np.zeros(4, np.int32), max_new_tokens=50)
    fleet.replicas[0].sched.slots[0] = busy  # white-box backlog
    assert fleet.retry_after_ms() == 0.0  # replica 1 is idle: best = 0
    other = Request(prompt=np.zeros(4, np.int32), max_new_tokens=10)
    fleet.replicas[1].sched.slots[0] = other
    # min(replica0: 10ms*50/2, replica1: 10ms*10/2) = 50.0
    assert fleet.retry_after_ms() == pytest.approx(50.0)


# --------------------------------------------------------- drain / rejoin
def test_rolling_drain_and_rejoin_zero_downtime(gpt2):
    """fleet.drain(replica) wraps the PR 9 graceful drain: in-flight
    requests finish, queued ones re-route to the surviving replica, and
    the drained replica rejoins through half-open probation — every
    request completes bitwise with the fleet never stopping."""
    ff, cfg = gpt2
    prompts = _prompts(10, seed=10)
    base = _baseline(ff, cfg, prompts, 8)
    fleet = _fleet(ff, cfg)
    chaos = FleetChaosPlan(drain_replica_at={2: 0}, rejoin_at={12: 0})
    outs = fleet.generate(prompts, max_new_tokens=8, chaos=chaos)
    st = fleet.stats
    assert outs == base
    assert st.outcomes == {"ok": 10}
    assert st.drains == 1 and st.rejoins == 1
    trail = [(t[3], t[4]) for t in st.health_transitions if t[1] == 0]
    assert ("draining", "drain_requested") in trail
    assert ("dead", "drained") in trail
    assert ("quarantined", "rejoin_probation") in trail
    assert ("healthy", "probe_pass") in trail


# ----------------------------------------------------------- plan lint
def test_fleet_plan_lint_names_the_bad_replica(gpt2):
    """Satellite: a heterogeneous plan set is linted per replica at
    construction (FF006 shape/divisibility) — the failure names the
    replica instead of surfacing as mid-serve garbage on 1/N of
    traffic."""
    from flexflow_tpu.analysis import StaticAnalysisError
    from flexflow_tpu.parallel.strategies import \
        hybrid_data_tensor_strategy

    ff, cfg = gpt2
    pcg = ff.executor.pcg
    bad = hybrid_data_tensor_strategy(pcg, 2, 4)
    guid = next(g for g, ns in bad.node_strategies.items()
                if ns.weight_specs)
    ns = bad.node_strategies[guid]
    wname = next(iter(ns.weight_specs))
    ns.weight_specs[wname] = (None, "bogus_axis")
    with pytest.raises(StaticAnalysisError) as ei:
        ServingFleet(ff, n_replicas=2, n_slots=2,
                     max_decode_len=cfg.seq_len, plans=[None, bad])
    msg = str(ei.value)
    assert "replica 1" in msg and "FF006" in msg
    assert "replica 0" not in msg  # the clean replica is not blamed
    # a clean plan set constructs fine
    ServingFleet(ff, n_replicas=2, n_slots=2, max_decode_len=cfg.seq_len,
                 plans=[None, hybrid_data_tensor_strategy(pcg, 2, 1)])


def test_plan_replicas_heterogeneous_generations(gpt2):
    """plan_replicas prices each replica on its OWN machine model (chip
    generation): the searched plans are valid fleet inputs and pass the
    per-replica lint."""
    from flexflow_tpu.serving import plan_replicas

    ff, cfg = gpt2
    plans = plan_replicas(ff.executor.pcg, ff.config, [4, 8],
                          generations=["v5e", "v5p"])
    assert len(plans) == 2
    assert all(p.sim_tokens_per_s > 0 for p in plans)
    fleet = ServingFleet(ff, n_replicas=2, n_slots=2,
                         max_decode_len=cfg.seq_len, plans=plans)
    assert fleet.replicas[0].plan is plans[0]


# ------------------------------------------------------- scheduler hooks
def test_scheduler_cancel_hooks_leave_no_ledger_entry():
    """cancel_slot / cancel_queued / remove_finished free capacity with
    NO terminal outcome — the hedge-loss and migration-harvest
    primitive."""
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=4, max_len=32)
    a = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4)
    b = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4)
    sched.submit(a)
    sched.submit(b)
    assert sched.next_action()[0] == "prefill"  # a into slot 0
    got = sched.cancel_slot(0)
    assert got is a and a.outcome is None and not a.done
    assert not sched.finished and sched.active == 0
    sched.cancel_queued(b)
    assert sched.queued == 0 and not sched.finished
    assert sched.cancelled == 2
    # remove_finished withdraws a same-tick completion
    c = Request(prompt=np.zeros(3, np.int32), max_new_tokens=1)
    sched.submit(c)
    _, req, slot, _b = sched.next_action()
    sched.commit_token(slot, 7)  # finishes (length 1)
    assert sched.finished and c.outcome == "ok"
    assert sched.remove_finished(c)
    assert not sched.finished
    assert not sched.remove_finished(c)  # idempotent: already gone


# ----------------------------------------------------------- end to end
def test_fleet_chaos_end_to_end_ledger_conserved(gpt2):
    """Acceptance (ISSUE 11 satellite): a 3-replica fleet under a kill,
    a sustained degrade AND fleet-door shedding finishes with every
    submitted request under exactly one outcome — migrated/hedged
    streams included — and completed streams bitwise-equal to an
    undisturbed single-replica run."""
    ff, cfg = gpt2
    config = ff.config
    prompts = _prompts(12, seed=11)
    base = _baseline(ff, cfg, prompts, 8)
    config.shed_policy = "queue"
    try:
        fleet = _fleet(ff, cfg, n_replicas=3, max_queue=20)
        chaos = FleetChaosPlan(kill_replica_at={4: 0},
                               degrade_replica_at={6: 1},
                               degrade_poison_every=1)
        outs = fleet.generate(prompts, max_new_tokens=8, chaos=chaos)
        st = fleet.stats
        # the fleet-wide ledger: 12 submissions, each exactly once
        assert sum(st.outcomes.values()) == 12, st.outcomes
        assert set(st.outcomes) <= set(OUTCOMES)
        assert st.failovers == 1 and st.migrations >= 1
        assert st.circuit_opens >= 1
        # completed streams bitwise vs the undisturbed run
        done = [i for i, o in enumerate(outs) if len(o) == 8]
        assert done, "nothing completed under chaos"
        assert all(outs[i] == base[i] for i in done)
        # the ledger survives into telemetry semantics: ok count matches
        # the completed streams that were never shed
        assert st.outcomes.get("ok", 0) == len(done)
    finally:
        config.shed_policy = "off"


def test_fleet_telemetry_block_and_trace_digest(gpt2, tmp_path, capsys):
    """The StepTelemetry ``fleet`` block lands next to the serving
    blocks and trace_summary prints its digest."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import trace_summary

    ff, cfg = gpt2
    config = ff.config
    tel_file = tmp_path / "fleet_tel.json"
    config.telemetry_file = str(tel_file)
    try:
        fleet = _fleet(ff, cfg)
        fleet.generate(_prompts(6, seed=12), max_new_tokens=4,
                       chaos=FleetChaosPlan(kill_replica_at={3: 0}))
    finally:
        config.telemetry_file = ""
    import json

    data = json.loads(tel_file.read_text())
    blk = data["fleet"]
    assert blk["replicas"] == 2
    assert blk["outcomes"] == {"ok": 6}
    assert blk["failovers"] == 1
    assert sum(blk["dispatches"]) >= 6
    trace_summary.main([str(tel_file)])
    out = capsys.readouterr().out
    assert "fleet: 2 replicas" in out
    assert "failovers: 1" in out


def test_plain_chaosplan_fleet_run_is_clean(gpt2):
    """A fleet handed a plain ChaosPlan (no fleet hooks) runs clean —
    the chaos dispatch degrades gracefully instead of crashing."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    outs = fleet.generate(_prompts(4, seed=13), max_new_tokens=4,
                          chaos=ChaosPlan())
    assert fleet.stats.outcomes == {"ok": 4}
    assert all(len(o) == 4 for o in outs)
