"""Async double-buffered serving runtime (ISSUE 17,
flexflow_tpu/serving/engine.py `_AsyncServeLoop`, docs/serving.md
"Async runtime"): `--serve-loop async` dispatches decode step k+1 while
step k's (tokens, ok_vec) transfer is in flight and commits at arrival,
one step behind dispatch. The sync loop is the reference
implementation; the async loop must match it
stream for stream, token for token — solo, co-batched, prefix-hit, chunked
prefill, speculative — including under the chaos harness (poison
quarantine, mid-decode kill + migration, SIGTERM drain, fleet hedge),
with at most one blocking host transfer per committed decode step
(white-box `host_syncs` counter) and host work overlapped with device
steps accounted in `host_overlap_s`, never in the overhead numerator.
All deterministic on CPU."""
import signal

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.resilience import ChaosPlan, FleetChaosPlan
from flexflow_tpu.serving import ServingEngine, ServingFleet


def _build(num_layers=2, hidden=64, seed=42):
    # the tiny family (hidden 64 / 4 heads) at seq 64 so prompts can
    # span KV blocks — prefix hits and chunked prefill need the room
    cfg = GPT2Config(batch_size=8, seq_len=64, hidden=hidden,
                     num_heads=4, num_layers=num_layers,
                     intermediate=2 * hidden, vocab_size=100)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    config.seed = seed
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg


@pytest.fixture(scope="module")
def gpt2():
    return _build()


def _prompts(n, seed=0, lo=3, hi=8):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 99, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _engine(ff, loop, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_decode_len", 64)
    kw.setdefault("kv_block_size", 8)
    return ServingEngine(ff, serve_loop=loop, **kw)


def _both(ff, prompts, max_new=6, gen_kw=None, **kw):
    """Run the same trace through both loops; return (sync_outs,
    async_outs, sync_stats, async_stats)."""
    outs, stats = {}, {}
    for loop in ("sync", "async"):
        eng = _engine(ff, loop, **kw)
        outs[loop] = eng.generate(prompts, max_new_tokens=max_new,
                                  **(gen_kw or {}))
        stats[loop] = eng.stats
    return outs["sync"], outs["async"], stats["sync"], stats["async"]


# ------------------------------------------------------------ clean parity
def test_async_matches_sync_solo_greedy(gpt2):
    ff, _ = gpt2
    s, a, _, _ = _both(ff, _prompts(1, seed=1), n_slots=1)
    assert s == a and all(len(x) == 6 for x in s)


def test_async_matches_sync_cobatched_sampled(gpt2):
    """Temperature + top-k sampling, 8 streams through 3 slots: rng
    streams key on (tag, tokens emitted), and at dispatch k+1 a slot
    with an uncommitted in-flight token samples at len(generated)+1 —
    a later-discarded draw can never desync a stream."""
    ff, _ = gpt2
    s, a, ss, sa = _both(ff, _prompts(8, seed=2), max_new=8,
                         gen_kw={"temperature": 0.7, "top_k": 5,
                                 "seed": 3})
    assert s == a, "sampled streams diverged between loops"
    assert ss.outcomes == sa.outcomes == {"ok": 8}


def test_async_matches_sync_prefix_hit(gpt2):
    """Shared-system-prompt trace with the radix trie live: the async
    loop's commit-at-arrival must not disturb trie insert/hit order."""
    ff, _ = gpt2
    sys_p = list(np.random.default_rng(7).integers(1, 99, size=20))
    prompts = [sys_p + [5, 6, 7], sys_p + [8, 9], sys_p + [5, 6, 1, 2]]
    s, a, ss, sa = _both(ff, prompts, n_slots=2)
    assert s == a
    assert ss.prefix_hits == sa.prefix_hits and sa.prefix_hits >= 1


def test_async_matches_sync_chunked_prefill(gpt2):
    """A long prompt prefilling in chunks co-scheduled with decode:
    chunk ticks and decode commits interleave differently in wall time
    but identically in token order."""
    ff, _ = gpt2
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 99, size=40).tolist()] + \
        _prompts(3, seed=10)
    s, a, ss, sa = _both(ff, prompts, n_slots=2,
                         prefill_chunk_tokens=16)
    assert s == a
    assert ss.outcomes == sa.outcomes


def test_speculative_matches_both_loops(gpt2):
    """The speculative decoder (device-side argmax scoring, ISSUE 17
    satellite) keeps its token-identity contract against BOTH loops'
    greedy decode."""
    from flexflow_tpu.serving import SpeculativeDecoder

    ff, _ = gpt2
    drafter_ff, _ = _build(num_layers=1, hidden=32, seed=5)
    prompts = _prompts(3, seed=11)
    s, a, _, _ = _both(ff, prompts, max_new=8)
    spec = SpeculativeDecoder(ff, drafter_ff, gamma=3, max_context=64)
    outs = spec.generate(prompts, max_new_tokens=8)
    assert s == a == outs
    assert spec.stats.spec_rounds > 0


# ------------------------------------------------------------ chaos parity
def test_chaos_poison_quarantine_parity(gpt2):
    """A NaN-poisoned slot quarantines at the SAME logical step in both
    loops (chaos keys on the dispatch counter; sync's committed step ==
    its dispatch count at injection time), with identical retry
    streams, outcomes and quarantine counts."""
    ff, _ = gpt2
    prompts = _prompts(4, seed=12)
    s, a, ss, sa = _both(
        ff, prompts, n_slots=2,
        gen_kw={"chaos": ChaosPlan(poison_decode_at={3: 0})})
    # second identical plan for the async run (ChaosPlan hooks are
    # once-per-step): rebuild instead of reusing
    eng_a = _engine(ff, "async", n_slots=2)
    a2 = eng_a.generate(prompts, max_new_tokens=6,
                        chaos=ChaosPlan(poison_decode_at={3: 0}))
    assert a2 == s
    assert eng_a.stats.quarantines == ss.quarantines == 1
    assert eng_a.stats.outcomes == ss.outcomes


def test_chaos_device_drop_rebuild_parity(gpt2):
    """drop_devices_at mid-decode: the elastic replan (and, on a real
    DecodeStateLost, the pool rebuild) runs behind a settle point, so
    continuations stay bitwise in both loops."""
    ff, _ = gpt2
    prompts = _prompts(4, seed=13)
    base = _engine(ff, "sync").generate(prompts, max_new_tokens=5)
    for loop in ("sync", "async"):
        eng = _engine(ff, loop)
        outs = eng.generate(prompts, max_new_tokens=5,
                            chaos=ChaosPlan(drop_devices_at={2: 4}))
        assert outs == base, f"{loop} diverged after device drop"


def test_chaos_sigterm_drain_parity(gpt2):
    """Mid-serve SIGTERM drains both loops identically: the in-flight
    request finishes (the async loop settles its pending step inside
    the drain-grace check before evicting stragglers), queued requests
    come back, and the outcome ledgers match."""
    ff, _ = gpt2
    prompts = _prompts(3, seed=14)
    prev = signal.getsignal(signal.SIGTERM)
    results = {}
    for loop in ("sync", "async"):
        eng = _engine(ff, loop, n_slots=1)
        outs = eng.generate(prompts, max_new_tokens=4,
                            chaos=ChaosPlan(preempt_serving_at=1))
        results[loop] = (outs, dict(eng.stats.outcomes),
                         [r.rng_tag for r in eng.drained_requests])
        assert signal.getsignal(signal.SIGTERM) is prev
    assert results["sync"] == results["async"]
    outs, outcomes, drained = results["async"]
    assert len(outs[0]) == 4 and outcomes == {"ok": 1, "preempted": 2}
    assert drained == [1, 2]


def test_fleet_kill_migration_parity(gpt2):
    """A replica killed mid-decode under the async runtime: the harvest
    settles the victim's in-flight step first (tokens already sampled
    on-device belong to the stream), so migrated continuations stay
    bitwise across loops AND against the undisturbed baseline."""
    ff, _ = gpt2
    prompts = _prompts(8, seed=15)
    base = _engine(ff, "sync", n_slots=2).generate(prompts,
                                                   max_new_tokens=6)
    for loop in ("sync", "async"):
        fleet = ServingFleet(ff, n_replicas=2, n_slots=2,
                             max_decode_len=64, serve_loop=loop)
        outs = fleet.generate(
            prompts, max_new_tokens=6,
            chaos=FleetChaosPlan(kill_replica_at={4: 0}))
        st = fleet.stats
        assert outs == base, f"{loop} migrated streams diverged"
        assert st.outcomes == {"ok": 8} and st.failovers == 1


def test_fleet_hedge_parity(gpt2):
    """Hedge twins under the async runtime: a partitioned primary's
    streams are rescued on the healthy replica with no double count,
    bitwise the undisturbed baseline."""
    ff, _ = gpt2
    config = ff.config
    prompts = _prompts(4, seed=16)
    base = _engine(ff, "sync", n_slots=2).generate(prompts,
                                                   max_new_tokens=6)
    config.hedge_after_pctl = 10.0
    try:
        for loop in ("sync", "async"):
            fleet = ServingFleet(ff, n_replicas=2, n_slots=2,
                                 max_decode_len=64, serve_loop=loop)
            for r in fleet.replicas:
                r.engine.admission.force_token_cost_ms = 1e-6
            outs = fleet.generate(
                prompts, max_new_tokens=6,
                chaos=FleetChaosPlan(partition_at={3: 0},
                                     partition_ticks=30))
            st = fleet.stats
            assert outs == base, f"{loop} hedged streams diverged"
            assert st.hedges >= 1 and st.outcomes == {"ok": 4}
            assert sum(st.outcomes.values()) == 4
    finally:
        config.hedge_after_pctl = 0.0


# --------------------------------------------------- white-box contracts
def test_async_one_blocking_sync_per_committed_step(gpt2):
    """The steady-state contract: every blocking host transfer goes
    through the loop's single `_fetch` choke point, exactly once per
    committed decode step — never more."""
    ff, _ = gpt2
    _, _, ss, sa = _both(ff, _prompts(6, seed=17), max_new=8)
    for st in (ss, sa):
        assert st.decode_steps > 0
        assert st.host_syncs == st.decode_steps, \
            (st.host_syncs, st.decode_steps)
    # the async loop runs a few extra dispatches at stream tails whose
    # in-flight results are discarded by the epoch guard — it must
    # still never fetch more than once per commit
    assert sa.host_syncs <= sa.decode_steps


def test_async_overlap_accounting(gpt2):
    """Host work performed while a dispatched step is in flight lands
    in host_overlap_s: real wall, denominator-only — the fraction's
    numerator stays (dispatch + bookkeep)."""
    ff, _ = gpt2
    _, _, ss, sa = _both(ff, _prompts(6, seed=18), max_new=8)
    assert ss.host_overlap_s == 0.0
    assert sa.host_overlap_s > 0.0, "async recorded no overlapped host work"
    num = sa.host_dispatch_s + sa.host_bookkeep_s
    den = num + sa.host_device_s + sa.host_overlap_s
    assert sa.host_overhead_fraction() == pytest.approx(num / den)
    assert "host_syncs" in sa.summary()


def test_async_finish_settles_pending(gpt2):
    """finish() is a drain point: after serve() returns there is no
    in-flight step left and every request has a terminal outcome."""
    from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                                Request)

    ff, _ = gpt2
    eng = _engine(ff, "async", n_slots=2)
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8, max_len=64,
                                     buckets=eng.buckets)
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=5,
                    rng_tag=i)
            for i, p in enumerate(_prompts(3, seed=19))]
    for r in reqs:
        eng.admit(sched, r)
    loop = eng.start_serve(sched)
    while loop.tick():
        pass
    loop.finish()
    assert loop._pending is None
    assert all(r.outcome == "ok" and len(r.generated) == 5 for r in reqs)


def test_serve_loop_validation(gpt2):
    ff, _ = gpt2
    with pytest.raises(ValueError, match="serve_loop"):
        ServingEngine(ff, n_slots=1, max_decode_len=64,
                      serve_loop="turbo")
