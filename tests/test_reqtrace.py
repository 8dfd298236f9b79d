"""Request-level distributed tracing (ISSUE 16,
flexflow_tpu/obs/reqtrace.py, docs/observability.md "Request-level
tracing"): per-request timelines threaded through submit -> queue ->
admission -> chunked prefill -> per-tick decode -> quarantine /
migration / hedge hops -> exactly one terminal outcome, exported as
Perfetto spans on the scheduler's injectable clock plus a versioned
RequestRecord JSONL stream; fleet time-series ring buffers; and the
zero-overhead contract (tracing off => bitwise-identical serve output,
no-op singleton on the hot path)."""
import itertools
import json
import os
import sys

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.obs.reqtrace import (FleetTimeSeries, NoopRequestTrace,
                                       RequestTrace, disable_reqtrace,
                                       enable_reqtrace, get_reqtrace,
                                       set_reqtrace)
from flexflow_tpu.obs.trace import Tracer
from flexflow_tpu.resilience import FleetChaosPlan
from flexflow_tpu.serving import ServingEngine, ServingFleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PHASES = ("req_queue", "req_prefill", "req_decode", "req_stall")


@pytest.fixture(autouse=True)
def _reset_reqtrace():
    """Every test leaves the process singleton back at the no-op."""
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


def _scripted(rt, rid=1):
    """One hand-scripted timeline exercising every phase transition:
    queue -> prefill (chunked, prefix hit w/ COW) -> decode ->
    quarantine -> requeue -> re-prefill -> decode -> migrate -> hedge
    launch -> decode -> ok."""
    rt.note(rid, "submit", 0.0, prompt_len=8, max_new=4, deadline_ms=None)
    rt.note(rid, "admit", 10.0, slot=0, hit=4, cow=True, replica=0)
    rt.note(rid, "chunk", 12.0, tokens=4)
    rt.note(rid, "token", 20.0, occ=2, replica=0)
    rt.note(rid, "quarantine", 25.0, replica=0)
    rt.note(rid, "submit", 26.0)
    rt.note(rid, "admit", 30.0, slot=1, hit=0, cow=False, replica=0)
    rt.note(rid, "token", 33.0, occ=1)
    rt.note(rid, "migrate", 34.0, src=0)
    rt.note(rid, "hedge", 35.0, src=0, replica=1, fork=2)
    rt.note(rid, "token", 40.0, occ=1)
    rt.finish(rid, 45.0, "ok", reason="length", new_tokens=3, replica=1)


# ------------------------------------------------------ record decomposition
def test_record_phase_decomposition_exact():
    """The scripted walk decomposes into EXACT phase buckets that tile
    [arrival, finish]: queue 10, prefill 10+3, decode 5+1+5, stall
    1+4+6 — and every v1 RequestRecord field lands."""
    rt = RequestTrace()
    _scripted(rt, rid=1)
    (rec,) = rt.records()
    assert rec["v"] == 1 and rec["kind"] == "request" and rec["rid"] == 1
    assert rec["arrival_ms"] == 0.0 and rec["finish_ms"] == 45.0
    assert rec["prompt_len"] == 8 and rec["max_new_tokens"] == 4
    assert rec["deadline_ms"] is None
    assert rec["queue_ms"] == 10.0
    assert rec["prefill_ms"] == 13.0
    assert rec["decode_ms"] == 11.0
    assert rec["stall_ms"] == 11.0
    # the four buckets account for the whole wall: no time leaks
    assert rec["queue_ms"] + rec["prefill_ms"] + rec["decode_ms"] + \
        rec["stall_ms"] == rec["finish_ms"] - rec["arrival_ms"]
    assert rec["first_token_ms"] == 20.0
    assert rec["decode_ticks"] == 3
    assert rec["occupancy_avg"] == round(4 / 3, 3)
    assert rec["new_tokens"] == 3  # finish field wins over tick count
    assert rec["prefix_hit_tokens"] == 4 and rec["cow"] is True
    assert rec["chunks"] == 1
    assert [h["kind"] for h in rec["hops"]] == \
        ["quarantine", "migrate", "hedge"]
    assert [h["t"] for h in rec["hops"]] == [25.0, 34.0, 35.0]
    assert rec["replicas"] == [0, 1]
    assert rec["outcome"] == "ok" and rec["finish_reason"] == "length"
    assert rec["hedged"] is False and rec["shed"] is None
    assert rec["dropped_notes"] == 0
    assert rt.open_timelines() == []


def test_span_export_exact_tree():
    """The same walk exported as Perfetto spans: one umbrella `request`
    span, phase spans that tile it contiguously (consecutive decode
    ticks merge into ONE `req_decode` span), `req_hop` instants for
    each hop and one `req_outcome`."""
    tr = Tracer()
    rt = RequestTrace(tracer=tr)
    _scripted(rt, rid=3)
    evs = list(tr.events)
    umbrella = [e for e in evs if e["name"] == "request"]
    assert len(umbrella) == 1
    assert umbrella[0]["ts"] == 0.0 and umbrella[0]["dur"] == 45000.0
    assert umbrella[0]["tid"] == 3
    assert umbrella[0]["args"]["outcome"] == "ok"
    spans = [(e["name"], e["ts"], e["dur"]) for e in evs
             if e["name"] in _PHASES]
    assert spans == [
        ("req_queue", 0.0, 10000.0),
        ("req_prefill", 10000.0, 10000.0),
        ("req_decode", 20000.0, 5000.0),   # tokens merge until a hop
        ("req_stall", 25000.0, 1000.0),
        ("req_stall", 26000.0, 4000.0),
        ("req_prefill", 30000.0, 3000.0),
        ("req_decode", 33000.0, 1000.0),
        ("req_stall", 34000.0, 6000.0),
        ("req_decode", 40000.0, 5000.0),
    ]
    # contiguous tiling of the umbrella span
    for (_, a_ts, a_dur), (_, b_ts, _) in zip(spans, spans[1:]):
        assert a_ts + a_dur == b_ts
    assert spans[0][1] == 0.0 and spans[-1][1] + spans[-1][2] == 45000.0
    hops = [e for e in evs if e["name"] == "req_hop"]
    assert [h["args"]["hop"] for h in hops] == \
        ["quarantine", "migrate", "hedge"]
    assert [h["ts"] for h in hops] == [25000.0, 34000.0, 35000.0]
    outcome = [e for e in evs if e["name"] == "req_outcome"]
    assert len(outcome) == 1 and outcome[0]["ts"] == 45000.0


def test_shed_record_and_instant():
    """A door-shed request (submit + terminal only) still yields one
    record: the shed decision carries the priced estimate that made it,
    and the tracer gets a `req_shed` instant."""
    tr = Tracer()
    rt = RequestTrace(tracer=tr)
    rt.note(7, "submit", 1.0, prompt_len=4, max_new=8, deadline_ms=50.0)
    rt.finish(7, 2.0, "shed", reason="deadline_unmeetable",
              policy="deadline", est_ms=500.0, queued=3)
    (rec,) = rt.records()
    assert rec["outcome"] == "shed"
    assert rec["shed"] == {"policy": "deadline", "est_ms": 500.0,
                           "queued": 3}
    assert rec["queue_ms"] == 1.0 and rec["decode_ticks"] == 0
    assert rec["first_token_ms"] is None
    names = [e["name"] for e in tr.events]
    assert "req_shed" in names and "req_outcome" in names
    assert rt.open_timelines() == []


# ------------------------------------------------- linking + idempotence
def test_link_folds_twin_and_first_terminal_wins():
    """link() gives hedge twins parent-span causality: the twin's notes
    (past and future) fold into the primary's single timeline, the twin
    never finalizes a record of its own, and the FIRST terminal note
    wins — the loser's finish is dropped."""
    rt = RequestTrace()
    rt.note(1, "submit", 0.0, prompt_len=3, max_new=4)
    rt.note(1, "admit", 1.0, replica=0)
    rt.note(1, "token", 2.0, occ=1, replica=0)
    # twin already has a note before the link (admit on replica 1)
    rt.note(99, "admit", 2.5, replica=1)
    rt.link(99, 1)
    rt.note(99, "token", 3.0, occ=1)       # folds into rid 1
    rt.finish(99, 4.0, "ok", reason="length", new_tokens=2, replica=1)
    rt.finish(1, 5.0, "preempted", reason="hedge_loser")  # dropped
    recs = rt.records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["rid"] == 1 and rec["hedged"] is True
    assert rec["outcome"] == "ok" and rec["finish_ms"] == 4.0
    assert rec["replicas"] == [0, 1]
    assert rec["decode_ticks"] == 2  # one primary + one twin tick
    assert rt.open_timelines() == []
    # post-terminal stragglers are dropped silently
    rt.note(1, "token", 6.0)
    rt.note(99, "token", 6.0)
    assert rt.open_timelines() == []


def test_unknown_note_kind_rejected_and_caps():
    rt = RequestTrace(max_records=2)
    with pytest.raises(ValueError, match="unknown request-trace"):
        rt.note(1, "telepathy", 0.0)
    for rid in (1, 2, 3):
        rt.note(rid, "submit", 0.0)
        rt.finish(rid, 1.0, "ok")
    assert len(rt.records()) == 2      # ring-bounded
    assert rt.dropped_records == 1     # ...and the drop is counted
    assert [r["rid"] for r in rt.records()] == [2, 3]


def test_jsonl_sink_roundtrip_and_digest(tmp_path, capsys):
    """finish() appends each record to the JSONL sink line-buffered;
    the file round-trips to the in-memory records and feeds the
    trace_summary per-request digest."""
    path = tmp_path / "requests.jsonl"
    rt = RequestTrace(jsonl_file=str(path))
    _scripted(rt, rid=11)
    rt.note(12, "submit", 50.0, prompt_len=2, max_new=4)
    rt.finish(12, 51.0, "shed", policy="queue", queued=9)
    rt.close()
    lines = path.read_text().splitlines()
    assert [json.loads(l) for l in lines] == rt.records()
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import trace_summary
        assert trace_summary.main([str(path)]) == 0
    finally:
        sys.path.pop(0)
    out = capsys.readouterr().out
    assert "request trace: 2 requests" in out
    assert "queue_p50" in out and "TTFT" in out
    assert "ok" in out and "shed" in out


# ----------------------------------------------------- zero-overhead contract
def test_noop_singleton_and_composition():
    """Default is the allocation-free no-op; enable installs one live
    singleton (second enable returns it unchanged); disable restores
    the no-op and hands back the live tracer for reading."""
    rt = get_reqtrace()
    assert isinstance(rt, NoopRequestTrace) and rt.enabled is False
    assert NoopRequestTrace.__slots__ == ()
    # every recording method is inert
    rt.note(1, "token", 0.0, occ=1)
    rt.link(1, 2)
    rt.finish(1, 0.0, "ok")
    assert rt.records() == []
    live = enable_reqtrace()
    assert live.enabled and get_reqtrace() is live
    assert enable_reqtrace() is live
    prev = disable_reqtrace()
    assert prev is live
    assert isinstance(get_reqtrace(), NoopRequestTrace)


def test_tracing_off_is_bitwise_identical(gpt2):
    """Acceptance (ISSUE 16): the same serve with tracing enabled and
    disabled produces bitwise-identical streams — the request path only
    ever branches on `rt.enabled`."""
    ff, cfg = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    prompts = _prompts(4, seed=5)
    base = eng.generate(prompts, max_new_tokens=5)
    live = enable_reqtrace()
    on = eng.generate(prompts, max_new_tokens=5)
    disable_reqtrace()
    off = eng.generate(prompts, max_new_tokens=5)
    assert on == base and off == base
    recs = live.records()
    assert len(recs) == 4
    assert all(r["outcome"] == "ok" for r in recs)
    assert all(r["new_tokens"] == 5 for r in recs)
    assert live.open_timelines() == []


# ------------------------------------------------------------ fleet e2e
def test_fleet_e2e_timeline_chunked_prefix_migration(gpt2):
    """Acceptance (ISSUE 16): a deterministic chaos fleet run under a
    FAKE COUNTING CLOCK — chunked long prompt, a prefix-cache hit on a
    warm replica, and one mid-decode replica kill — exports exactly one
    connected timeline per admitted request (phase spans contiguously
    tile [arrival, finish]), exactly one terminal outcome each, a
    migrate hop crossing replicas, and live fleet time-series."""
    ff, cfg = gpt2
    config = ff.config
    old_chunk = getattr(config, "prefill_chunk_tokens", 0)
    old_block = getattr(config, "kv_block_size", 16)
    config.prefill_chunk_tokens = 4
    config.kv_block_size = 4
    tr = Tracer()
    rt = RequestTrace(tracer=tr)
    set_reqtrace(rt)
    ticks = itertools.count()
    try:
        fleet = _fleet(ff, cfg, clock=lambda: float(next(ticks)))
        long_p = list(range(1, 10))  # 9 tokens: 3 chunks of <= 4
        fleet.generate([long_p, [40, 41, 42]], max_new_tokens=4)
        # tick_no persists across runs: aim the kill 4 ticks into the
        # second run, mid-decode
        chaos = FleetChaosPlan(kill_replica_at={fleet.tick_no + 4: 0})
        fleet.generate([long_p, [50, 51, 52], [60, 61, 62, 63]],
                       max_new_tokens=4, chaos=chaos)
    finally:
        set_reqtrace(NoopRequestTrace())
        config.prefill_chunk_tokens = old_chunk
        config.kv_block_size = old_block

    recs = rt.records()
    assert len(recs) == 5                      # one record per request
    assert len({r["rid"] for r in recs}) == 5  # ...each its own
    assert rt.open_timelines() == []           # every timeline closed
    assert all(r["outcome"] == "ok" for r in recs)
    assert all(r["new_tokens"] == 4 for r in recs)
    # chunked prefill visible on the long prompts
    assert any(r["chunks"] >= 2 for r in recs)
    # the second long prompt re-prefilled against a warm trie
    assert any(r["prefix_hit_tokens"] >= 4 for r in recs)
    # the kill migrated at least one in-flight stream across replicas
    migrated = [r for r in recs
                if any(h["kind"] == "migrate" for h in r["hops"])]
    assert migrated, "kill_replica_at produced no migrate hop"
    assert any(len(r["replicas"]) >= 2 for r in migrated)

    # span tree: per rid, phase spans tile [arrival, finish] EXACTLY
    # (the fake clock makes every edge an integer ms)
    by_rid = {}
    for e in tr.events:
        if e["name"] in _PHASES:
            by_rid.setdefault(e["tid"], []).append(e)
    umbrella = {e["tid"]: e for e in tr.events if e["name"] == "request"}
    for rec in recs:
        ph = sorted(by_rid[rec["rid"]], key=lambda e: e["ts"])
        assert ph[0]["ts"] == rec["arrival_ms"] * 1e3
        for a, b in zip(ph, ph[1:]):
            assert a["ts"] + a["dur"] == b["ts"], \
                f"phase gap in rid {rec['rid']}"
        assert ph[-1]["ts"] + ph[-1]["dur"] == rec["finish_ms"] * 1e3
        u = umbrella[rec["rid"]]
        assert u["ts"] == rec["arrival_ms"] * 1e3
        assert u["dur"] == (rec["finish_ms"] - rec["arrival_ms"]) * 1e3
        # bucket sums agree with the span tree
        assert rec["queue_ms"] + rec["prefill_ms"] + rec["decode_ms"] \
            + rec["stall_ms"] == pytest.approx(
                rec["finish_ms"] - rec["arrival_ms"])
    assert sum(1 for e in tr.events if e["name"] == "req_outcome") == 5

    # fleet time-series sampled once per tick while tracing was live
    ts = fleet.timeseries
    assert ts is not None and len(ts) > 0
    s = ts.summary()
    for key in ("ticks", "queue_depth_last", "queue_depth_max",
                "tokens_total", "backlog_ewma_ms_last",
                "occupancy_mean", "unhealthy_ticks"):
        assert key in s
    assert s["tokens_total"] > 0
    assert s["unhealthy_ticks"] >= 1  # the dead replica shows up


def test_fleet_hedge_timeline_linked(gpt2):
    """A hedged request keeps ONE timeline: the twin's rid never
    finalizes a record, the hedge hop lands on the primary with
    parent-span causality, and the record says hedged=True."""
    ff, cfg = gpt2
    config = ff.config
    prompts = _prompts(4, seed=7)
    config.hedge_after_pctl = 10.0
    rt = RequestTrace()
    set_reqtrace(rt)
    try:
        fleet = _fleet(ff, cfg)
        for r in fleet.replicas:
            r.engine.admission.force_token_cost_ms = 1e-6
        chaos = FleetChaosPlan(partition_at={3: 0}, partition_ticks=30)
        fleet.generate(prompts, max_new_tokens=6, chaos=chaos)
        assert fleet.stats.hedges >= 1
    finally:
        set_reqtrace(NoopRequestTrace())
        config.hedge_after_pctl = 0.0
    recs = rt.records()
    assert len(recs) == 4, "a hedge twin leaked its own record"
    assert rt.open_timelines() == []
    assert all(r["outcome"] == "ok" for r in recs)
    hedged = [r for r in recs if r["hedged"]]
    assert hedged, "no record marked hedged"
    assert any(any(h["kind"] == "hedge" for h in r["hops"])
               for r in hedged)


def test_fleet_host_overhead_fraction(gpt2):
    """Host-overhead accounting is always on (ROADMAP item 5 baseline):
    after a run both the per-engine and fleet stats report a fraction
    in (0, 1), split across dispatch / device-wait / bookkeeping."""
    ff, cfg = gpt2
    fleet = _fleet(ff, cfg)
    fleet.generate(_prompts(4, seed=9), max_new_tokens=4)
    st = fleet.stats
    frac = st.host_overhead_fraction()
    assert frac is not None and 0.0 < frac < 1.0
    assert st.host_device_s > 0.0
    assert st.host_dispatch_s > 0.0  # router + replica dispatch wall
    for rep in fleet.replicas:
        f = rep.loop.stats.host_overhead_fraction()
        assert f is not None and 0.0 < f < 1.0


# ------------------------------------------------------------- time-series
def test_fleet_timeseries_unit():
    ts = FleetTimeSeries(maxlen=4)
    for i in range(10):
        ts.sample(i, queue_depth=i, tokens=2, backlog_ms=10.0,
                  occupancy=(0.5, 1.0), health=("healthy", "degraded"))
    assert len(ts) == 4                      # ring-bounded
    assert list(ts.ticks) == [6, 7, 8, 9]
    s = ts.summary()
    assert s["ticks"] == 4
    assert s["queue_depth_last"] == 9 and s["queue_depth_max"] == 9
    assert s["tokens_total"] == 8            # retained ticks only
    assert s["backlog_ewma_ms_last"] == 10.0  # constant input -> EWMA
    assert s["occupancy_mean"] == 0.75
    assert s["unhealthy_ticks"] == 4
    # EWMA actually smooths: a step input converges, not jumps
    ts2 = FleetTimeSeries()
    ts2.sample(0, 0, 0, 10.0, (), ())
    ts2.sample(1, 0, 0, 20.0, (), ())
    assert ts2.backlog_ewma_ms[-1] == pytest.approx(12.0)
    assert FleetTimeSeries().summary() == {"ticks": 0}
