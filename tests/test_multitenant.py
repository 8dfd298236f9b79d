"""Multi-tenant SLO isolation + autoscaling (ISSUE 19,
flexflow_tpu/serving/tenancy.py + the fleet-door changes,
docs/multitenant.md): weighted fair queueing across tenant tiers with
the bitwise isolation law, per-tenant quotas/ledgers/retry pricing,
admission-EWMA warm carry across pool rebuilds, the backlog-forecast
autoscaler under a scripted traffic step, and the capacity-replay
planner — all deterministic on CPU."""
import json
import os
import sys

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.obs.reqtrace import disable_reqtrace, enable_reqtrace
from flexflow_tpu.resilience import FleetChaosPlan, PreflightError
from flexflow_tpu.resilience.preflight import preflight_config
from flexflow_tpu.serving import (OUTCOMES, QuotaExceededError, Request,
                                  ServingFleet, ServingRejection,
                                  TenantRegistry, WeightedFairQueue,
                                  parse_tenant_tiers)
from flexflow_tpu.serving.resilience import AdmissionController

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


@pytest.fixture(autouse=True)
def _reset_reqtrace():
    yield
    disable_reqtrace()


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


def _fleet(ff, cfg, **kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_decode_len", cfg.seq_len)
    return ServingFleet(ff, **kw)


def _req(p, i, tenant=None, max_new=6, **kw):
    return Request(prompt=np.asarray(p, np.int32), max_new_tokens=max_new,
                   rng_tag=i, tenant=tenant, **kw)


def _submit_all(fleet, reqs):
    for r in reqs:
        try:
            fleet.submit(r)
        except ServingRejection:
            pass


# ----------------------------------------------------------- tier registry
def test_parse_tenant_tiers_and_registry():
    """Spec parsing is strict (the preflight/parse-time contract) and
    the registry keeps unknown tenants on standard's parameters WITHOUT
    merging their ledger identity."""
    pols = parse_tenant_tiers("gold:8:500:1000,bronze:1")
    assert pols["gold"].weight == 8.0
    assert pols["gold"].deadline_ms == 500.0
    assert pols["gold"].quota_tokens_per_s == 1000.0
    assert pols["bronze"].weight == 1.0
    for bad in ("gold", "gold:0", "gold:-1", "gold:2:x", "a:1,a:2",
                "gold:1:2:3:4", ":1"):
        with pytest.raises(ValueError):
            parse_tenant_tiers(bad)
    assert parse_tenant_tiers("") == {}  # the flag default is valid
    reg = TenantRegistry()
    std = reg.policy(None)
    assert std.name == "standard"
    unknown = reg.policy("acme")
    assert unknown.name == "acme"  # own ledger identity
    assert unknown.weight == std.weight  # standard's parameters
    assert reg.policy("interactive").weight > std.weight > \
        reg.policy("batch").weight


def test_tier_flags_parse_and_preflight_mirror():
    """--tenant-tiers / --autoscale / --min-replicas / --max-replicas
    fail fast at parse time AND through the preflight sweep."""
    config = FFConfig()
    config.parse_args(["--tenant-tiers", "gold:8:500", "--autoscale",
                       "on", "--min-replicas", "1",
                       "--max-replicas", "4"])
    assert config.tenant_tiers == "gold:8:500"
    assert config.autoscale == "on"
    preflight_config(config)  # the valid combo sails through
    with pytest.raises(ValueError):
        FFConfig().parse_args(["--tenant-tiers", "gold:0"])
    with pytest.raises(ValueError):
        FFConfig().parse_args(["--autoscale", "sometimes"])
    with pytest.raises(ValueError):
        # replica bounds without the autoscaler are dead flags
        FFConfig().parse_args(["--min-replicas", "2"])
    with pytest.raises(ValueError):
        FFConfig().parse_args(["--autoscale", "on", "--min-replicas",
                               "4", "--max-replicas", "2"])
    bad = FFConfig()
    bad.tenant_tiers = "gold:-3"  # set programmatically: parse never ran
    with pytest.raises(PreflightError):
        preflight_config(bad)
    bad2 = FFConfig()
    bad2.min_replicas = 2  # autoscale still off
    with pytest.raises(PreflightError):
        preflight_config(bad2)


# ------------------------------------------------------------- WFQ laws
def test_wfq_fifo_degeneration_single_tenant():
    """Single-tenant (and untenanted) traffic pops in EXACT submission
    order: the pre-tenant door is a special case of the WFQ, not a
    separate mode."""
    q = WeightedFairQueue(TenantRegistry())
    reqs = [_req([1], i, max_new=3 + (i % 5)) for i in range(12)]
    for r in reqs:
        q.append(r)
    assert [q.popleft() is r for r in reqs] == [True] * 12


def test_wfq_weighted_share_no_starvation():
    """Acceptance: over a backlogged window the interactive tier (weight
    8) gets at least its weight share of pops ahead of a batch flood
    (weight 1) — and batch is never starved (it still appears within
    any window longer than the weight ratio)."""
    q = WeightedFairQueue(TenantRegistry())
    flood = [_req([1], i, tenant="batch", max_new=4) for i in range(24)]
    inter = [_req([1], 100 + i, tenant="interactive", max_new=4)
             for i in range(8)]
    for r in flood:  # the flood is ALREADY queued when interactive lands
        q.append(r)
    for r in inter:
        q.append(r)
    order = [q.popleft().tenant for _ in range(len(q))]
    # every interactive request pops within the first 12 slots despite
    # 24 batch requests ahead of it in arrival order
    assert order[:12].count("interactive") == 8, order[:12]
    # no starvation: batch drains interleaved, not after a wall
    assert "batch" in order[:12]
    assert order.count("batch") == 24


def test_wfq_deque_compat_rescue_lane_first():
    """The WFQ keeps the deque surface the fleet (and its tests) poke:
    appendleft is the rescue lane and is served before the fair queue,
    extend/iteration/__delitem__ follow service order."""
    q = WeightedFairQueue(TenantRegistry())
    a, b = _req([1], 0, tenant="batch"), _req([1], 1, tenant="batch")
    q.extend([a, b])
    rescued = _req([1], 2, tenant="interactive")
    q.appendleft(rescued)
    assert list(q)[0] is rescued  # iteration order == service order
    assert len(q) == 3
    del q[1]  # drops `a` (first fair-queue entry)
    assert q.popleft() is rescued
    assert q.popleft() is b
    assert not q


# ------------------------------------------------- bitwise isolation law
def test_bitwise_isolation_under_batch_flood(gpt2):
    """THE tier-1 isolation law (ISSUE 19 acceptance): an
    interactive stream is token for token the same with and without
    a batch-tier flood co-scheduled through the WFQ door — tenancy
    changes WHEN a stream decodes, never WHAT it decodes. The per-tenant
    exactly-one-outcome ledger closes on both sides."""
    ff, cfg = gpt2
    prompts = _prompts(5, seed=21)
    solo = _fleet(ff, cfg)
    solo_reqs = [_req(p, i, tenant="interactive") for i, p in
                 enumerate(prompts)]
    _submit_all(solo, solo_reqs)
    solo.run()
    assert solo.stats.outcomes == {"ok": 5}
    mixed = _fleet(ff, cfg)
    flood = [_req(p, 100 + i, tenant="batch", max_new=8)
             for i, p in enumerate(_prompts(8, seed=22))]
    mixed_reqs = [_req(p, i, tenant="interactive") for i, p in
                  enumerate(prompts)]
    # interleave: flood first so WFQ reordering actually does something
    _submit_all(mixed, flood + mixed_reqs)
    mixed.run()
    for a, b in zip(solo_reqs, mixed_reqs):
        assert list(a.generated) == list(b.generated), \
            "co-scheduling changed a stream's bits"
    st = mixed.stats
    assert st.tenant_requests == {"batch": 8, "interactive": 5}
    for t, n in st.tenant_requests.items():
        assert sum(st.tenant_outcomes[t].values()) == n, \
            f"{t} ledger leaked"
    assert st.tenant_outcomes["interactive"] == {"ok": 5}
    assert st.tenant_tokens["interactive"] == 5 * 6


# ---------------------------------------------------- quotas + shedding
def test_quota_exceeded_ledgered_with_refill_hint(gpt2):
    """A tenant over its token-rate bucket is rejected with the typed
    QuotaExceededError, outcome quota_exceeded (a first-class OUTCOMES
    member), and a retry hint derived from the bucket refill."""
    assert "quota_exceeded" in OUTCOMES
    ff, cfg = gpt2
    config = ff.config
    config.tenant_tiers = "metered:4:0:10"  # 10 tokens/s, burst 10
    try:
        fleet = _fleet(ff, cfg)
        ok = _req(_prompts(1, seed=23)[0], 0, tenant="metered", max_new=8)
        fleet.submit(ok)  # burst covers 8
        over = _req(_prompts(1, seed=24)[0], 1, tenant="metered",
                    max_new=8)
        with pytest.raises(QuotaExceededError) as ei:
            fleet.submit(over)
        assert ei.value.retry_after_ms > 0.0  # priced refill, not 0
        assert over.outcome == "quota_exceeded"
        fleet.run()
        st = fleet.stats
        assert st.quota_sheds == 1
        assert st.tenant_outcomes["metered"] == {"ok": 1,
                                                 "quota_exceeded": 1}
        assert sum(st.outcomes.values()) == 2
    finally:
        config.tenant_tiers = ""


def test_shed_priority_tiers_order_the_door(gpt2):
    """--shed-policy queue sheds batch before standard before
    interactive: priority 0 halves the pre-tenant high-water, priority 1
    keeps it EXACTLY (the pre-tenant contract), priority >= 2 holds to
    the hard wall."""
    ff, cfg = gpt2
    config = ff.config
    config.shed_policy = "queue"
    try:
        fleet = _fleet(ff, cfg, max_queue=8)
        base = max(fleet.max_queue // 2, 1)
        assert fleet._shed_highwater(fleet.tenants.policy(None)) == base
        assert fleet._shed_highwater(
            fleet.tenants.policy("batch")) == max(base // 2, 1)
        assert fleet._shed_highwater(
            fleet.tenants.policy("interactive")) == fleet.max_queue
    finally:
        config.shed_policy = "off"


def test_retry_after_prices_tenant_queue_position(gpt2):
    """ISSUE 19 satellite bugfix: the backoff hint prices the rejected
    TENANT'S virtual queue position — a batch client behind the flood it
    created is told a longer wait than an interactive client at the
    same instant; the tenantless hint keeps the pre-tenant value."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    for rep in fleet.replicas:
        rep.engine.admission.force_token_cost_ms = 10.0
    baseline = fleet.retry_after_ms()
    for i, p in enumerate(_prompts(10, seed=25)):
        fleet.queue.append(_req(p, i, tenant="batch", max_new=10))
    assert fleet.retry_after_ms() == baseline  # aggregate hint unchanged
    hint_batch = fleet.retry_after_ms("batch")
    hint_inter = fleet.retry_after_ms("interactive")
    assert hint_batch > hint_inter >= 0.0
    # the batch hint prices (some of) the 100 queued batch tokens at
    # 10 ms/token over 4 slots
    assert hint_batch >= 10.0


# ----------------------------------------- admission EWMA warm carry
def test_admission_warm_start_carries_cost_model():
    """ISSUE 19 satellite bugfix: a rebuilt controller adopts the warm
    aggregate + per-tenant EWMAs instead of re-learning from zero — but
    never overwrites its own history, and never copies a debug force."""
    warm = AdmissionController()
    warm.force_token_cost_ms = None
    warm.observe_step(0.010, 2, tenants=["gold"])
    warm.observe_step(0.010, 2, tenants=["gold"])
    assert warm.observed_steps == 2
    cold = AdmissionController()
    cold.warm_start(warm)
    assert cold.observed_steps == 2
    assert cold.token_cost_ms == pytest.approx(warm.token_cost_ms)
    assert cold.token_cost_ms_for("gold") == \
        pytest.approx(warm.token_cost_ms_for("gold"))
    assert cold.force_token_cost_ms is None
    # a controller with its own history refuses the transplant
    busy = AdmissionController()
    busy.observe_step(0.050, 1)
    before = busy.token_cost_ms
    busy.warm_start(warm)
    assert busy.token_cost_ms == before
    assert busy.observed_steps == 1


# -------------------------------------------------- autoscaler + chaos
def test_autoscale_up_on_traffic_step_recovery_budget(gpt2):
    """Acceptance (ISSUE 19): a scripted 4x traffic step trips the
    backlog forecast, the pool grows through half-open probation
    (autoscale_probation health trail), the surge drains within the
    pinned tick budget, scale-down never fires mid-surge below the
    floor, and the per-tenant exactly-one-outcome ledger conserves
    storm requests too."""
    ff, cfg = gpt2
    config = ff.config
    config.autoscale = "on"
    config.min_replicas = 2
    config.max_replicas = 3
    try:
        fleet = _fleet(ff, cfg, max_queue=16)
        step_tick = 3
        chaos = FleetChaosPlan(
            traffic_step_at={step_tick: (6, 2)}, storm_tenant="batch",
            fleet_storm_max_new=6, fleet_storm_prompt_tokens=3)
        reqs = [_req(p, i, tenant="interactive") for i, p in
                enumerate(_prompts(5, seed=26))]
        _submit_all(fleet, reqs)
        fleet.run(chaos=chaos)
        st = fleet.stats
        assert st.storm_requests == 12
        assert st.autoscale_ups >= 1, "the 4x step never tripped the " \
            f"forecast: events={st.autoscale_events}"
        assert len(fleet.replicas) <= config.max_replicas
        # the newcomer entered through the SAME probation as a rejoin
        trail = [(t[3], t[4]) for t in st.health_transitions if t[1] >= 2]
        assert ("quarantined", "autoscale_probation") in trail
        assert ("healthy", "probe_pass") in trail
        # pinned recovery budget: waiting depth back at pre-step level
        rec = st.surge_recovery_ticks(step_tick)
        assert rec is not None and rec <= 60, \
            f"surge never drained within budget (rec={rec})"
        # ledger conservation, storm traffic included
        total = len(reqs) + st.storm_requests
        assert sum(st.outcomes.values()) == total
        for t, n in st.tenant_requests.items():
            assert sum(st.tenant_outcomes[t].values()) == n
        assert st.tenant_outcomes["interactive"] == {"ok": 5}
        # every in-flight stream ran to completion (scale paths shed
        # nothing by themselves)
        assert all(len(r.generated) == 6 for r in reqs)
    finally:
        config.autoscale = "off"
        config.min_replicas = 0
        config.max_replicas = 0


def test_scale_down_drains_without_dropping_streams(gpt2):
    """Acceptance: scale-down leaves through migrate-and-drain — the
    victim finishes or migrates its in-flight streams and NOTHING is
    dropped; the pool never shrinks below --min-replicas."""
    ff, cfg = gpt2
    config = ff.config
    config.autoscale = "on"
    config.min_replicas = 1
    config.max_replicas = 3
    try:
        fleet = _fleet(ff, cfg, n_replicas=3)
        # slack from early on: a 2-request trickle on a 3-replica pool
        # (one replica guaranteed idle = the deterministic victim)
        fleet.autoscale_down_after = 2  # shrink patience, test-speed
        reqs = [_req(p, i, tenant="standard", max_new=8) for i, p in
                enumerate(_prompts(2, seed=27))]
        _submit_all(fleet, reqs)
        fleet.run()
        st = fleet.stats
        assert st.autoscale_downs >= 1, st.autoscale_events
        assert len(fleet._serving_replicas()) >= config.min_replicas
        assert st.outcomes == {"ok": 2}
        assert all(len(r.generated) == 8 for r in reqs)
        # the victim went through the drain path, not a kill
        assert st.drains >= 1
        trail = [(t[3], t[4]) for t in st.health_transitions]
        assert ("draining", "drain_requested") in trail
    finally:
        config.autoscale = "off"
        config.min_replicas = 0
        config.max_replicas = 0


def test_multitenant_drain_kill_ledger_conserved(gpt2):
    """ISSUE 19 satellite (extends the PR 11 drain/rejoin test): a
    drain, a rejoin AND a mid-decode kill under concurrent multi-tenant
    admission — per-tenant exactly-one-outcome conservation, and the
    surviving streams bitwise vs an undisturbed run."""
    ff, cfg = gpt2
    prompts = _prompts(9, seed=28)
    tenants = ["interactive", "batch", None] * 3
    solo = _fleet(ff, cfg, n_replicas=3)
    solo_reqs = [_req(p, i, tenant=t) for i, (p, t) in
                 enumerate(zip(prompts, tenants))]
    _submit_all(solo, solo_reqs)
    solo.run()
    fleet = _fleet(ff, cfg, n_replicas=3)
    chaos = FleetChaosPlan(drain_replica_at={2: 1}, rejoin_at={14: 1},
                           kill_replica_at={5: 0})
    reqs = [_req(p, i, tenant=t) for i, (p, t) in
            enumerate(zip(prompts, tenants))]
    _submit_all(fleet, reqs)
    fleet.run(chaos=chaos)
    st = fleet.stats
    assert sum(st.outcomes.values()) == 9
    assert set(st.outcomes) <= set(OUTCOMES)
    assert st.tenant_requests == {"interactive": 3, "batch": 3}
    for t, n in st.tenant_requests.items():
        assert sum(st.tenant_outcomes[t].values()) == n, \
            f"{t} ledger leaked under chaos"
    # untenanted rides aggregate-only: tenant ledgers must not have
    # swallowed it
    assert sum(sum(v.values()) for v in st.tenant_outcomes.values()) == 6
    done = [i for i, r in enumerate(reqs) if r.outcome == "ok"]
    assert done, "nothing completed under chaos"
    for i in done:
        assert list(reqs[i].generated) == list(solo_reqs[i].generated)


# ------------------------------------------------ observability surface
def test_tenant_storm_and_telemetry_rows(gpt2, tmp_path):
    """tenant_storm_at injects through the REAL door (same ledgers,
    fleet_tenant_storm trace event) and the per-tenant rows land in the
    telemetry fleet block."""
    ff, cfg = gpt2
    config = ff.config
    tel_file = tmp_path / "tel.json"
    config.telemetry_file = str(tel_file)
    try:
        fleet = _fleet(ff, cfg)
        chaos = FleetChaosPlan(tenant_storm_at={2: ("batch", 3)},
                               fleet_storm_max_new=4,
                               fleet_storm_prompt_tokens=3)
        fleet.generate(_prompts(4, seed=29), max_new_tokens=4,
                       chaos=chaos)
        st = fleet.stats
        assert st.storm_requests == 3
        assert st.tenant_requests.get("batch") == 3
        assert sum(st.outcomes.values()) == 7
    finally:
        config.telemetry_file = ""
    data = json.loads(tel_file.read_text())
    blk = data["fleet"]
    assert blk["tenants"]["batch"]["requests"] == 3
    assert sum(blk["tenants"]["batch"]["outcomes"].values()) == 3


def test_trace_summary_tenant_digest_and_degradation(gpt2, tmp_path,
                                                     capsys):
    """trace_summary renders the per-tenant digest from tenanted trace
    files and degrades gracefully (no crash, aggregate digest intact)
    on pre-tenant records."""
    import trace_summary

    ff, cfg = gpt2
    trace = tmp_path / "req.jsonl"
    enable_reqtrace(jsonl_file=str(trace))
    try:
        fleet = _fleet(ff, cfg)
        reqs = [_req(p, i, tenant=("interactive" if i % 2 else "batch"))
                for i, p in enumerate(_prompts(4, seed=30))]
        _submit_all(fleet, reqs)
        fleet.run()
    finally:
        disable_reqtrace()
    trace_summary.main([str(trace)])
    out = capsys.readouterr().out
    assert "interactive" in out and "batch" in out
    # pre-tenant file: the same records with the tenant key stripped
    old = tmp_path / "old.jsonl"
    with open(trace) as f, open(old, "w") as g:
        for line in f:
            rec = json.loads(line)
            rec.pop("tenant", None)
            g.write(json.dumps(rec) + "\n")
    trace_summary.main([str(old)])
    out = capsys.readouterr().out
    assert "request trace: 4 requests" in out  # aggregate digest intact


def test_capacity_plan_replay_smoke(tmp_path, capsys):
    """The offline planner replays a recorded trace through the WFQ
    simulator, reports per-tier TTFT, and answers the min-replica
    question; an empty/foreign file degrades to a one-line note."""
    import capacity_plan

    trace = tmp_path / "cap.jsonl"
    with open(trace, "w") as f:
        for i in range(16):
            f.write(json.dumps({
                "kind": "request", "arrival_ms": 1000.0 + 4.0 * i,
                "max_new_tokens": 6, "new_tokens": 6,
                "decode_ticks": 6, "decode_ms": 12.0,
                "tenant": ("interactive" if i % 2 else "batch")}) + "\n")
    rc = capacity_plan.main([str(trace), "--target-p99-ms", "200",
                             "--max-replicas", "3", "--slots", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "interactive" in out and "batch" in out
    assert "answer:" in out
    empty = tmp_path / "empty.jsonl"
    empty.write_text("{\"kind\": \"span\"}\n")
    assert capacity_plan.main([str(empty)]) == 0
    assert "nothing to replay" in capsys.readouterr().out
