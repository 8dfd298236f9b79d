"""Serving under fire (ISSUE 9, flexflow_tpu/serving/resilience.py,
docs/serving.md "Serving under failure"): deadline eviction with slot
recycling, admission load shedding (shed-vs-accept determinism under a
scripted queue storm), decode-health quarantine with bit-identical
neighbors and a retried stream, graceful SIGTERM drain returning queued
requests, and automatic elastic_replan after a chaos device drop — all
driven deterministically on CPU by the ChaosPlan serving extensions."""
import signal

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.resilience import ChaosPlan
from flexflow_tpu.serving import (ContinuousBatchScheduler, OverloadError,
                                  QueueFullError, Request, ServingEngine,
                                  ServingRejection)


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


def _engine(ff, cfg, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_decode_len", cfg.seq_len)
    return ServingEngine(ff, **kw)


class _ScriptedClock:
    """Deterministic ms clock: advances a fixed amount per call, so every
    deadline/drain decision is a pure function of the call sequence."""

    def __init__(self, step_ms=5.0):
        self.t = 0.0
        self.step_ms = step_ms

    def __call__(self):
        self.t += self.step_ms
        return self.t


# ----------------------------------------------------------------- deadlines
def test_deadline_eviction_recycles_slot_neighbors_bitwise(gpt2):
    """A request whose deadline expires mid-decode is evicted (outcome
    deadline_exceeded), its slot is recycled into the waiting queue, and
    co-batched streams are bitwise-unchanged vs an undisturbed run."""
    ff, cfg = gpt2
    prompts = _prompts(3, seed=1)
    base = _engine(ff, cfg).generate(prompts, max_new_tokens=8)

    eng = _engine(ff, cfg)
    eng.resilience_clock = _ScriptedClock(step_ms=5.0)
    # per-request deadlines: request 0 gets a tight budget that expires
    # after a few decode steps; 1 and 2 are unconstrained
    res = eng._make_resilience(None)
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8,
                                     buckets=eng.buckets,
                                     max_len=eng.max_decode_len,
                                     clock=res.clock)
    reqs = []
    for i, p in enumerate(prompts):
        r = Request(prompt=np.asarray(p, np.int32), max_new_tokens=8,
                    rng_tag=i,
                    deadline_ms=60.0 if i == 0 else None)
        res.admit(sched, r)
        reqs.append(r)
    eng.serve(sched, resilience=res)

    assert reqs[0].outcome == "deadline_exceeded"
    assert 0 < len(reqs[0].generated) < 8  # started, then evicted
    # neighbors bitwise-unchanged, and the recycled slot served request 2
    assert list(reqs[1].generated) == base[1]
    assert list(reqs[2].generated) == base[2]
    assert reqs[2].outcome == "ok" and len(reqs[2].generated) == 8
    assert eng.stats.deadline_misses == 1
    assert eng.stats.outcomes == {"ok": 2, "deadline_exceeded": 1}
    # requests_served counts clean completions only — the evicted
    # request lives in the outcome ledger, not the served count
    assert eng.stats.requests_served == 2
    assert sched.evicted == 1


def test_deadline_expired_in_queue_never_costs_a_prefill(gpt2):
    """Admission-time enforcement: a request already past its deadline
    while queued is dropped before it claims prefill compute."""
    ff, cfg = gpt2
    prompts = _prompts(4, seed=2)
    eng = _engine(ff, cfg, n_slots=1)
    # 1 ms deadline, clock advancing 5 ms/call: queued requests are
    # already expired by the first sweep — only the first request (whose
    # prefill can start before any sweep runs... it too expires) may run
    eng.resilience_clock = _ScriptedClock(step_ms=5.0)
    outs = eng.generate(prompts, max_new_tokens=4, deadline_ms=1.0)
    assert all(o == [] for o in outs)
    assert eng.stats.outcomes == {"deadline_exceeded": 4}
    assert eng.stats.prefills == 0


# ------------------------------------------------------------------ shedding
def test_shed_policy_queue_deterministic_and_rejection_base(gpt2):
    """'queue' policy sheds at the max_queue//2 high-water mark with a
    typed OverloadError; the shed-vs-accept pattern is deterministic run
    to run, and ONE except clause catches both rejection types."""
    ff, cfg = gpt2
    config = ff.config
    config.shed_policy = "queue"
    try:
        def storm_pattern():
            eng = _engine(ff, cfg, n_slots=1)
            res = eng._make_resilience(None)
            sched = ContinuousBatchScheduler(n_slots=1, max_queue=4,
                                             max_len=eng.max_decode_len,
                                             clock=res.clock)
            sched.shed_policy = res.shed_policy
            pat = []
            for i in range(8):
                r = Request(prompt=np.asarray([1, 2, 3], np.int32),
                            max_new_tokens=2, rng_tag=i)
                try:
                    res.admit(sched, r)
                    pat.append("accept")
                except ServingRejection as e:  # ONE clause, both types
                    pat.append(type(e).__name__)
                    assert e.queued >= 0 and e.active >= 0
                    assert e.retry_after_ms >= 0.0
                    assert r.outcome == "shed"
            return pat, res
        a, res_a = storm_pattern()
        b, _ = storm_pattern()
        assert a == b, "shed-vs-accept pattern not deterministic"
        assert a[:2] == ["accept", "accept"]  # below high-water (4//2=2)
        assert set(a[2:]) == {"OverloadError"}
        assert res_a.sheds == 6
    finally:
        config.shed_policy = "off"


def test_shed_policy_deadline_uses_completion_estimate(gpt2):
    """'deadline' policy sheds when the EWMA completion estimate blows
    the request deadline, with a retry_after_ms drain hint."""
    ff, cfg = gpt2
    config = ff.config
    config.shed_policy = "deadline"
    try:
        eng = _engine(ff, cfg, n_slots=1)
        eng.admission.force_token_cost_ms = 10.0  # scripted cost model
        res = eng._make_resilience(None)
        sched = ContinuousBatchScheduler(n_slots=1, max_queue=16,
                                         max_len=eng.max_decode_len,
                                         clock=res.clock)
        ok = Request(prompt=np.asarray([1, 2], np.int32),
                     max_new_tokens=4, deadline_ms=100.0)
        res.admit(sched, ok)  # est = 10 * 4 = 40 <= 100
        tight = Request(prompt=np.asarray([1, 2], np.int32),
                        max_new_tokens=4, deadline_ms=50.0)
        with pytest.raises(OverloadError) as ei:
            # est = 10 * (4 queued tokens / 1 slot + 4) = 80 > 50
            res.admit(sched, tight)
        assert ei.value.retry_after_ms == pytest.approx(40.0)
        assert "deadline" in str(ei.value)
        # no deadline -> nothing to blow -> admitted
        free = Request(prompt=np.asarray([1, 2], np.int32),
                       max_new_tokens=4)
        res.admit(sched, free)
        assert sched.queued == 2 and res.sheds == 1
    finally:
        config.shed_policy = "off"


def test_queue_full_error_names_shed_policy():
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=1, max_len=32)
    sched.shed_policy = "deadline"
    sched.submit(Request(prompt=np.zeros(4, np.int32), max_new_tokens=4))
    with pytest.raises(QueueFullError, match="shed policy 'deadline'") \
            as ei:
        sched.submit(Request(prompt=np.zeros(4, np.int32),
                             max_new_tokens=4))
    assert isinstance(ei.value, ServingRejection)
    assert ei.value.queued == 1


# ---------------------------------------------------------------- quarantine
def test_decode_poison_quarantined_retried_neighbors_bitwise(gpt2):
    """A NaN-poisoned decode slot is quarantined ALONE: co-batched
    streams continue bit-identically, and the poisoned request is retried
    on a fresh slot, resuming its stream exactly where the quarantine cut
    it (the same tokens)."""
    ff, cfg = gpt2
    prompts = _prompts(4, seed=3)
    base = _engine(ff, cfg).generate(prompts,
                                                        max_new_tokens=5)
    eng = _engine(ff, cfg)
    chaos = ChaosPlan(poison_decode_at={2: 0})
    outs = eng.generate(prompts, max_new_tokens=5, chaos=chaos)
    assert chaos.poisoned_decode_steps == [2]
    assert outs == base, "retried/neighbor streams diverged"
    st = eng.stats
    assert st.quarantines == 1 and st.decode_retries == 1
    assert st.outcomes == {"ok": 4}
    # the guarded decode step stays recompile-free too
    assert eng._last_guard is True and eng.decode_compiles == 1


def test_repeated_poison_aborts_decode_fault(gpt2):
    """Retry budget spent -> the request aborts with outcome decode_fault
    while neighbors still finish bit-identically."""
    ff, cfg = gpt2
    prompts = _prompts(2, seed=4)
    base = _engine(ff, cfg).generate(prompts,
                                                        max_new_tokens=6)
    eng = _engine(ff, cfg)
    # slot 0 poisoned at step 1; the retry re-prefills into the only free
    # slot (0 again) and is poisoned again at step 3 — budget 1 exhausted
    chaos = ChaosPlan(poison_decode_at={1: 0, 3: 0})
    outs = eng.generate(prompts, max_new_tokens=6, chaos=chaos)
    st = eng.stats
    assert st.outcomes == {"ok": 1, "decode_fault": 1}
    assert st.quarantines == 2 and st.decode_retries == 1
    faulted = [i for i, p in enumerate(prompts)
               if len(outs[i]) < 6]
    assert len(faulted) == 1
    ok_idx = 1 - faulted[0]
    assert outs[ok_idx] == base[ok_idx], "neighbor stream diverged"


def test_decode_retry_budget_zero_aborts_immediately(gpt2):
    ff, cfg = gpt2
    config = ff.config
    config.decode_retry_budget = 0
    try:
        eng = _engine(ff, cfg)
        chaos = ChaosPlan(poison_decode_at={1: 0})
        eng.generate(_prompts(1, seed=5), max_new_tokens=6, chaos=chaos)
        st = eng.stats
        assert st.outcomes == {"decode_fault": 1}
        assert st.quarantines == 1 and st.decode_retries == 0
    finally:
        config.decode_retry_budget = 1


# --------------------------------------------------------------------- drain
def test_sigterm_drain_returns_queued_and_finishes_inflight(gpt2):
    """Mid-serve SIGTERM: admission stops, the in-flight request finishes
    its full generation, queued requests come back for re-submission —
    and re-submitting them on a fresh serve completes them."""
    ff, cfg = gpt2
    prompts = _prompts(3, seed=6)
    prev = signal.getsignal(signal.SIGTERM)
    eng = _engine(ff, cfg, n_slots=1)
    chaos = ChaosPlan(preempt_serving_at=1)
    outs = eng.generate(prompts, max_new_tokens=4, chaos=chaos)
    assert signal.getsignal(signal.SIGTERM) is prev, "handler not restored"
    assert chaos.serving_preempted_at == 1
    assert len(outs[0]) == 4, "in-flight request did not finish"
    assert outs[1] == [] and outs[2] == []
    drained = eng.drained_requests
    assert [r.rng_tag for r in drained] == [1, 2]
    assert all(r.outcome == "preempted" for r in drained)
    st = eng.stats
    assert st.drains == 1 and st.drained_returned == 2
    assert st.outcomes == {"ok": 1, "preempted": 2}
    # the drained requests are clean for re-submission elsewhere
    res = eng._make_resilience(None)
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=8,
                                     max_len=eng.max_decode_len,
                                     clock=res.clock)
    for r in drained:
        r.outcome = None
        res.admit(sched, r)
    eng.serve(sched, resilience=res)
    assert all(len(r.generated) == 4 and r.outcome == "ok"
               for r in drained)


def test_drain_grace_zero_evicts_inflight_as_preempted(gpt2):
    ff, cfg = gpt2
    config = ff.config
    config.drain_grace_s = 0.0
    try:
        eng = _engine(ff, cfg, n_slots=1)
        chaos = ChaosPlan(preempt_serving_at=1)
        outs = eng.generate(_prompts(2, seed=7), max_new_tokens=6,
                            chaos=chaos)
        st = eng.stats
        assert st.outcomes == {"preempted": 2}
        assert 0 < len(outs[0]) < 6  # evicted mid-generation
        assert st.drained_returned == 1
    finally:
        config.drain_grace_s = 5.0


# ------------------------------------------------------------------ failover
def test_device_drop_auto_replans_decode_state_bitwise(gpt2):
    """ChaosPlan.drop_devices_at mid-decode triggers elastic_replan
    automatically (bounded backoff, first retry immediate); the in-flight
    DecodeState survives the hop so continuations are bit-identical to an
    undisturbed run (PR 6's replan test pattern, now self-driving)."""
    ff, cfg = gpt2
    prompts = _prompts(4, seed=8)
    base = _engine(ff, cfg).generate(prompts, max_new_tokens=5)
    eng = _engine(ff, cfg)
    chaos = ChaosPlan(drop_devices_at={2: 4})
    outs = eng.generate(prompts, max_new_tokens=5, chaos=chaos)
    assert outs == base, "DecodeState did not survive the auto-replan"
    assert chaos.devices_dropped == [2]
    assert eng.stats.replans == 1
    assert eng.plan is not None and \
        eng.plan.mesh_shape[0] * eng.plan.mesh_shape[1] <= 4
    assert eng.stats.outcomes == {"ok": 4}


def test_real_loss_with_dead_state_reprefills_bitwise(gpt2):
    """A REAL device loss raised from inside the dispatch consumes the
    donated DecodeState. The engine must not retry into 'Array has been
    deleted': it replans, rebuilds the pool, and re-prefills every live
    stream from its host-side committed tokens — continuations stay
    token-identical and every request still ends ok."""
    import jax

    ff, cfg = gpt2
    prompts = _prompts(3, seed=11)
    base = _engine(ff, cfg).generate(prompts,
                                                        max_new_tokens=5)
    eng = _engine(ff, cfg)
    real = eng._decode_fn
    fired = []

    def patched(guard=False):
        fn = real(guard=guard)

        def wrapper(params, toks, state):
            if eng.stats.decode_steps == 2 and not fired:
                fired.append(True)
                for leaf in jax.tree_util.tree_leaves(
                        (state, eng._last_tokens)):
                    leaf.delete()
                raise RuntimeError("FAILED_PRECONDITION: Device is lost")
            return fn(params, toks, state)
        return wrapper

    eng._decode_fn = patched
    outs = eng.generate(prompts, max_new_tokens=5, chaos=ChaosPlan())
    assert fired, "scripted loss never fired"
    assert outs == base, "streams diverged across the state rebuild"
    assert eng.stats.replans == 1
    assert eng.stats.outcomes == {"ok": 3}


def test_direct_scheduler_submit_deadline_enforced(gpt2):
    """A caller-set Request.deadline_ms must be enforced even when the
    request was submitted straight to the scheduler (sched.submit, the
    PR 6 pattern) and never passed engine.admit — serve() arms the
    sweeps from the deadlines already in the scheduler."""
    ff, cfg = gpt2
    eng = _engine(ff, cfg, n_slots=1)
    clock = _ScriptedClock(step_ms=5.0)
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=8,
                                     max_len=eng.max_decode_len,
                                     clock=clock)
    doomed = Request(prompt=np.asarray([1, 2, 3], np.int32),
                     max_new_tokens=8, rng_tag=0, deadline_ms=20.0)
    easy = Request(prompt=np.asarray([4, 5, 6], np.int32),
                   max_new_tokens=3, rng_tag=1)
    sched.submit(doomed)
    sched.submit(easy)
    eng.serve(sched)
    assert eng._last_guard is True, "direct-submit deadline did not arm"
    assert doomed.outcome == "deadline_exceeded"
    assert easy.outcome == "ok" and len(easy.generated) == 3


def test_completion_estimate_counts_inflight_backlog():
    """The admission estimate must see a saturated slot pool: in-flight
    remaining tokens delay a new request's first token exactly like a
    deep queue does (otherwise the 'deadline' policy under-sheds and
    retry_after_ms reads 0 in the busiest regime)."""
    from flexflow_tpu.serving import AdmissionController

    ctrl = AdmissionController()
    ctrl.force_token_cost_ms = 10.0
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=8,
                                     buckets=(8,), max_len=64)
    busy = Request(prompt=np.zeros(4, np.int32), max_new_tokens=100)
    sched.slots[0] = busy  # white-box: pool saturated, queue empty
    req = Request(prompt=np.zeros(4, np.int32), max_new_tokens=4)
    assert ctrl.estimate_completion_ms(req, sched) == \
        pytest.approx(10.0 * (100 + 4))
    assert ctrl.retry_after_ms(sched) == pytest.approx(1000.0)


def test_non_device_loss_errors_still_propagate(gpt2):
    """The failover detector is conservative: an arbitrary error from the
    decode path must NOT be eaten by a replan loop."""
    from flexflow_tpu.serving.resilience import looks_like_device_loss

    assert not looks_like_device_loss(ValueError("shape mismatch"))
    assert looks_like_device_loss(
        RuntimeError("FAILED_PRECONDITION: Device is lost"))


# ------------------------------------------------------------- end to end
def test_chaos_end_to_end_every_request_accounted(gpt2):
    """Acceptance (ISSUE 9): one serve loop with a scripted decode-NaN, a
    queue storm through the 'queue' shed policy, and a mid-serve SIGTERM
    finishes with every request under exactly one outcome (no hangs, no
    lost requests), the quarantined request's neighbors bitwise-equal to
    an undisturbed run, and the drain returning the still-queued
    requests."""
    ff, cfg = gpt2
    config = ff.config
    prompts = _prompts(4, seed=9)
    base = _engine(ff, cfg).generate(prompts,
                                                        max_new_tokens=6)
    storm = {4: [[7, 8, 9]] * 6}
    config.shed_policy = "queue"
    try:
        # max_queue 8 -> 'queue' policy high-water 4: part of the storm
        # is accepted, the rest shed; SIGTERM lands while storm work is
        # still queued so the drain has something to hand back
        eng = _engine(ff, cfg, max_queue=8)
        chaos = ChaosPlan(poison_decode_at={3: 1},
                          storm_queue=storm,
                          storm_max_new_tokens=3,
                          preempt_serving_at=5)
        outs = eng.generate(prompts, max_new_tokens=6, chaos=chaos)
        st = eng.stats
        # ledger: 4 generate requests + 6 storm requests, each under
        # exactly one outcome
        assert sum(st.outcomes.values()) == 10
        assert set(st.outcomes) <= {"ok", "deadline_exceeded", "shed",
                                    "decode_fault", "preempted"}
        assert st.quarantines >= 1, "poison never fired"
        assert st.sheds >= 1, "storm never shed"
        assert st.drains == 1, "SIGTERM never drained"
        # neighbor isolation: every generate request that ran to
        # completion matches the undisturbed run bitwise
        for i, o in enumerate(outs):
            if len(o) == 6:
                assert o == base[i], f"request {i} diverged"
        assert any(len(o) == 6 for o in outs)
        # drain handoff: queued-at-SIGTERM requests were returned
        assert st.drained_returned == len(eng.drained_requests)
        assert all(r.outcome == "preempted"
                   for r in eng.drained_requests)
    finally:
        config.shed_policy = "off"


def test_engine_admit_state_survives_into_serve(gpt2):
    """engine.admit() without an explicit resilience accumulates on a
    pending policy object the next serve() consumes: a caller-set
    deadline stamped pre-serve arms the sweeps, and nothing is lost to a
    throwaway object."""
    ff, cfg = gpt2
    eng = _engine(ff, cfg, n_slots=1)
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=8,
                                     max_len=eng.max_decode_len)
    reqs = [Request(prompt=np.asarray([1, 2, 3], np.int32),
                    max_new_tokens=4, rng_tag=i,
                    deadline_ms=1e-9 if i else None)
            for i in range(2)]
    for r in reqs:
        eng.admit(sched, r)
    assert eng._pending_resilience is not None
    assert eng._pending_resilience.deadlines_armed
    eng.serve(sched)
    assert eng._pending_resilience is None  # consumed
    assert eng._last_guard is True, "pre-serve deadline did not arm serve"
    # the nano-deadline request was enforced, its sibling completed
    assert reqs[1].outcome == "deadline_exceeded"
    assert reqs[0].outcome == "ok" and len(reqs[0].generated) == 4
    assert eng.stats.outcomes == {"ok": 1, "deadline_exceeded": 1}


def test_queue_full_policy_off_still_ledgered_as_shed(gpt2):
    """With --shed-policy off the only admission gate is the hard
    QueueFullError wall — a request rejected there must STILL leave the
    system under exactly one outcome (shed), not vanish from the
    accounting."""
    ff, cfg = gpt2
    eng = _engine(ff, cfg, n_slots=1)
    res = eng._make_resilience(None)
    assert res.shed_policy == "off"
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=2,
                                     max_len=eng.max_decode_len,
                                     clock=res.clock)
    sched.shed_policy = res.shed_policy
    reqs = [Request(prompt=np.asarray([1, 2, 3], np.int32),
                    max_new_tokens=2, rng_tag=i) for i in range(6)]
    rejected = []
    for r in reqs:
        try:
            res.admit(sched, r)
        except QueueFullError:
            rejected.append(r)
    assert rejected, "queue wall never hit"
    assert all(r.outcome == "shed" for r in rejected)
    assert res.sheds == len(rejected)
    eng.serve(sched, resilience=res)
    st = eng.stats
    assert sum(st.outcomes.values()) == len(reqs)  # all 6 accounted
    assert st.outcomes["shed"] == len(rejected)
    assert st.outcomes["ok"] == len(reqs) - len(rejected)


def test_pending_admit_sheds_merge_into_explicit_resilience(gpt2):
    """A shed ledgered on the pending policy object (engine.admit with no
    explicit resilience) survives into a serve() that IS handed an
    explicit resilience object — the pending counters merge instead of
    being dropped with the throwaway."""
    ff, cfg = gpt2
    eng = _engine(ff, cfg, n_slots=1)
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=1,
                                     max_len=eng.max_decode_len)
    ok_req = Request(prompt=np.asarray([1, 2, 3], np.int32),
                     max_new_tokens=2, rng_tag=0)
    eng.admit(sched, ok_req)
    overflow = Request(prompt=np.asarray([4, 5, 6], np.int32),
                       max_new_tokens=2, rng_tag=1)
    with pytest.raises(ServingRejection):
        eng.admit(sched, overflow)  # hard wall -> pending ledger
    assert eng._pending_resilience.sheds == 1
    res = eng._make_resilience(None)  # caller supplies a fresh object
    eng.serve(sched, resilience=res)
    assert eng._pending_resilience is None  # consumed, not leaked
    assert res.sheds == 1  # merged, not lost
    assert eng.stats.outcomes == {"ok": 1, "shed": 1}


def test_retry_resubmitted_to_narrow_scheduler_refused_at_submit():
    """A quarantine-retry request (committed tokens in tow) resubmitted
    to a scheduler whose buckets cannot cover prompt+generated must be
    refused AT SUBMIT — never after next_action() already claimed a slot
    (the slot-pool-corruption guard covers effective_len too)."""
    narrow = ContinuousBatchScheduler(n_slots=1, max_queue=8,
                                      buckets=(4,), max_len=32)
    retry = Request(prompt=np.zeros(3, np.int32), max_new_tokens=6,
                    generated=[5, 6, 7])  # effective_len 6 > bucket 4
    with pytest.raises(ValueError, match="largest prefill bucket"):
        narrow.submit(retry)
    assert narrow.queued == 0 and not narrow.active
    assert narrow.next_action() is None  # pool untouched


def test_plain_serve_stays_unguarded_and_rejection_free(gpt2):
    """Nothing armed -> the decode step is the unguarded program and no
    resilience bookkeeping appears in the stats (zero-overhead claim)."""
    ff, cfg = gpt2
    eng = _engine(ff, cfg)
    outs = eng.generate(_prompts(2, seed=10), max_new_tokens=3)
    assert all(len(o) == 3 for o in outs)
    assert eng._last_guard is False
    st = eng.stats
    assert st.outcomes == {"ok": 2}
    assert st.quarantines == 0 and st.sheds == 0 and st.drains == 0
