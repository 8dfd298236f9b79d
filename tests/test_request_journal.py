"""Crash-durable serving (ISSUE 20, flexflow_tpu/serving/journal.py,
docs/durability.md): the fleet-door write-ahead request journal —
segmented crc32-framed records with torn-tail truncation (property-style
churn over random corruption), group commit, compaction, the NOOP_JOURNAL
off-contract, rid-keyed client-retry dedupe, and the end-to-end loop:
crash mid-serve (FleetChaosPlan.crash_at) -> ServingFleet.recover() ->
every journaled rid under exactly one outcome, progress-journaled streams
resuming with unchanged tokens — all deterministic on CPU."""
import json
import os

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.resilience import FleetChaosPlan
from flexflow_tpu.serving import (NOOP_JOURNAL, FleetCrashed,
                                  JournalCorruptError, NoopJournal,
                                  Request, RequestJournal, ServingEngine,
                                  ServingFleet, ServingRejection,
                                  journal_from_config)
from flexflow_tpu.serving.scheduler import reserve_rids


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


def _req(prompt, rid=None, **kw):
    kw.setdefault("max_new_tokens", 4)
    r = Request(prompt=np.asarray(prompt, dtype=np.int32), **kw)
    if rid is not None:
        r.rid = rid
    return r


def _journal_config(config, jdir, sync_ms=0.0, commit_every=0):
    """Set the journal knobs on the shared FFConfig; caller resets in
    a finally (the module fixture shares one config)."""
    config.request_journal = str(jdir)
    config.journal_sync_ms = sync_ms
    config.journal_commit_every = commit_every


def _reset_journal_config(config):
    config.request_journal = ""
    config.journal_sync_ms = 0.0
    config.journal_commit_every = 0


# ------------------------------------------------------------ journal unit
def test_journal_roundtrip_dedupe_and_reopen(tmp_path):
    """Submit/progress/outcome round-trip the segment format: a reopen
    rebuilds exactly the unfinished backlog, a duplicate submit dedupes,
    a repeated outcome is first-wins, and the outcome vocabulary is
    closed over OUTCOMES."""
    jr = RequestJournal(str(tmp_path / "j"), sync_ms=0.0,
                        commit_every=2)
    a = _req([1, 2, 3], rid=501, rng_tag=7, tenant="interactive",
             deadline_ms=250.0)
    b = _req([4, 5], rid=502)
    assert jr.log_submit(a) and jr.log_submit(b)
    assert not jr.log_submit(a)  # client retry: rid-keyed dedupe
    assert jr.dedupe_hits == 1
    a.generated.extend([11, 12])
    jr.log_progress(a)           # commit_every=2 reached -> recorded
    a.generated.extend([13])
    jr.log_progress(a)           # below the threshold -> no record
    b.outcome, b.done = "ok", True
    assert jr.log_outcome(b)
    assert not jr.log_outcome(b)  # first terminal wins
    with pytest.raises(ValueError, match="unknown outcome"):
        jr.log_outcome(a, outcome="vanished")
    jr.close()

    jr2 = RequestJournal(str(tmp_path / "j"))
    assert jr2.pending_rids() == [501]
    assert jr2.max_rid() == 502
    (rec,) = jr2.pending_requests()
    assert rec.rid == 501 and list(rec.prompt) == [1, 2, 3]
    assert rec.generated == [11, 12]  # the journaled prefix only
    assert rec.rng_tag == 7 and rec.tenant == "interactive"
    assert rec.deadline_ms == 250.0
    assert jr2.truncated_records == 0


def test_torn_tail_truncation_property(tmp_path):
    """Property-style churn (the PR 13 allocator-churn idiom): random
    byte-level tears of the LIVE segment — truncation mid-record or a
    flipped byte anywhere — always recover the longest valid record
    prefix: the reopened journal's state equals a fold of exactly the
    records wholly before the tear, the file is truncated to that
    prefix, and the journal stays appendable."""
    rng = np.random.default_rng(0)
    for it in range(25):
        root = tmp_path / f"t{it}"
        jr = RequestJournal(str(root), sync_ms=0.0, commit_every=1)
        n = int(rng.integers(2, 9))
        reqs = [_req([int(x) for x in rng.integers(0, 50, size=3)],
                     rid=1000 + i) for i in range(n)]
        for r in reqs:
            jr.log_submit(r)
        for r in reqs[:int(rng.integers(0, n))]:
            r.outcome, r.done = "ok", True
            jr.log_outcome(r)
        jr.crash()  # abandon the handle; the bytes are already synced
        (seg,) = [root / f for f in os.listdir(root)]
        data = seg.read_bytes()
        cut = int(rng.integers(1, len(data)))
        truncated = bool(rng.integers(2))
        if truncated:
            seg.write_bytes(data[:cut])        # torn mid-append
        else:
            torn = bytearray(data)
            torn[cut] ^= 0xFF                  # bit rot in the tail
            seg.write_bytes(bytes(torn))
        # the law: every record wholly before the tear survives
        keep = data.rfind(b"\n", 0, cut) + 1
        want_pending, want_outcomes = {}, set()
        for line in data[:keep].splitlines():
            p = json.loads(line.split(b" ", 1)[1])
            if p["k"] == "submit" and p["rid"] not in want_outcomes:
                want_pending.setdefault(p["rid"], [])
            elif p["k"] == "progress":
                if p["rid"] in want_pending:
                    want_pending[p["rid"]].extend(p["toks"])
            elif p["k"] == "outcome":
                want_pending.pop(p["rid"], None)
                want_outcomes.add(p["rid"])
        jr2 = RequestJournal(str(root))
        got = {r.rid: r.generated for r in jr2.pending_requests()}
        assert got == want_pending, f"iteration {it}: tear at {cut}"
        assert seg.read_bytes() == data[:keep]  # tail truncated, fsynced
        # the scanner counts a tear only when it SAW torn bytes: a cut
        # landing exactly on a record boundary leaves a clean file
        file_len = cut if truncated else len(data)
        assert (jr2.truncated_records >= 1) == (keep < file_len)
        # still appendable after surgery: a fresh record lands durably
        jr2.log_submit(_req([9], rid=4000 + it))
        jr2.close()
        assert 4000 + it in RequestJournal(str(root)).pending_rids()


def test_sealed_segment_corruption_raises(tmp_path):
    """Corruption in a SEALED (non-last) segment is not a torn tail —
    later records may depend on that history, so the scan refuses with
    JournalCorruptError naming the segment."""
    root = tmp_path / "sealed"
    jr = RequestJournal(str(root), sync_ms=0.0, segment_bytes=1 << 10)
    for i in range(40):
        jr.log_submit(_req([1, 2, 3], rid=100 + i))
    jr.close()
    segs = sorted(f for f in os.listdir(root))
    assert len(segs) >= 2, "segment rotation never fired"
    first = root / segs[0]
    blob = bytearray(first.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    first.write_bytes(bytes(blob))
    with pytest.raises(JournalCorruptError, match=segs[0]):
        RequestJournal(str(root))


def test_compaction_drops_settled_segments_only(tmp_path):
    """A sealed segment is dropped once every rid it references has an
    outcome; compaction stops at the first segment still holding a
    pending rid's history (prefix order keeps the submit/progress chain
    of every unfinished request intact)."""
    root = tmp_path / "c"
    jr = RequestJournal(str(root), sync_ms=0.0, segment_bytes=1 << 10)
    reqs = [_req([1, 2, 3], rid=200 + i) for i in range(40)]
    for r in reqs:
        jr.log_submit(r)
    for r in reqs:
        r.outcome, r.done = "ok", True
        jr.log_outcome(r)
    n_before = len(os.listdir(root))
    dropped = jr.compact()
    assert dropped >= 1
    assert jr.compacted_segments == dropped
    assert len(os.listdir(root)) == n_before - dropped
    assert RequestJournal(str(root)).pending_rids() == []
    # a pending rid in the OLDEST segment pins everything behind it
    root2 = tmp_path / "c2"
    jr2 = RequestJournal(str(root2), sync_ms=0.0, segment_bytes=1 << 10)
    jr2.log_submit(_req([7], rid=9000))  # never gets an outcome
    more = [_req([1, 2, 3], rid=300 + i) for i in range(40)]
    for r in more:
        jr2.log_submit(r)
        r.outcome, r.done = "ok", True
        jr2.log_outcome(r)
    assert len(os.listdir(root2)) >= 2
    assert jr2.compact() == 0


def test_reserve_rids_monotone():
    """reserve_rids skips the process-wide counter past every journaled
    rid (fresh submits never collide with a replayed one) and never
    moves it backwards."""
    r1 = _req([1])
    reserve_rids(r1.rid + 100)
    r2 = _req([1])
    assert r2.rid == r1.rid + 101
    reserve_rids(0)  # stale reservation must not rewind the counter
    assert _req([1]).rid > r2.rid


# ----------------------------------------------------------- off-contract
def test_journal_off_is_noop_singleton_bitwise(gpt2):
    """Journal off (the default) is the PR 16 noop contract: the fleet
    holds the one shared slotted NOOP_JOURNAL and serves bitwise
    identically to the baseline — zero durability, zero tax."""
    assert NoopJournal.__slots__ == ()
    assert journal_from_config(FFConfig()) is NOOP_JOURNAL
    ff, cfg = gpt2
    prompts = _prompts(6, seed=3)
    base = _baseline(ff, cfg, prompts, 5)
    fleet = _fleet(ff, cfg)
    assert fleet.journal is NOOP_JOURNAL
    assert fleet.journal.log_submit(None) is True  # door never blocked
    outs = fleet.generate(prompts, max_new_tokens=5)
    assert outs == base
    assert fleet.stats.outcomes == {"ok": 6}


# -------------------------------------------------- crash -> recover loop
def test_crash_recover_exactly_one_outcome_bitwise(gpt2, tmp_path):
    """Acceptance (ISSUE 20): FleetChaosPlan.crash_at fires mid-serve
    (in-process hard mode — the journal drops its un-synced buffer and
    FleetCrashed skips every flush path), ServingFleet.recover() replays
    the unfinished backlog through the real door, and after the recovery
    run every journaled rid has exactly one outcome on disk — with
    progress-journaled streams resumed BITWISE vs an undisturbed
    single-engine run."""
    ff, cfg = gpt2
    config = ff.config
    prompts = _prompts(8, seed=4)
    base = _baseline(ff, cfg, prompts, 6)
    jdir = tmp_path / "wal"
    _journal_config(config, jdir, sync_ms=0.0, commit_every=1)
    try:
        fleet = _fleet(ff, cfg)
        for i, p in enumerate(prompts):
            fleet.submit(_req(p, max_new_tokens=6, rng_tag=i))
        chaos = FleetChaosPlan(crash_at={6: "hard"})
        with pytest.raises(FleetCrashed, match="tick 6"):
            fleet.run(chaos=chaos)
        assert chaos.crashes_fired == ["hard"]

        # what the dead process left on disk: every submit durable
        # (sync_ms=0), and the crash landed mid-stream — at least one
        # backlog entry carries a journaled committed-token prefix
        scan = RequestJournal(str(jdir), commit_every=1)
        backlog = scan.pending_requests()
        assert len(backlog) + len(scan._outcomes) == 8
        assert backlog, "crash after everything finished proves nothing"
        assert any(r.generated for r in backlog), \
            "crash tick never reached mid-stream decode"

        fleet2 = ServingFleet.recover(ff, n_replicas=2, n_slots=2,
                                      max_decode_len=cfg.seq_len)
        jr = fleet2.journal
        assert jr.replayed == len(backlog)
        assert jr.recovery_wall_s > 0
        st = fleet2.stats
        fleet2.run()
        assert st.outcomes == {"ok": len(backlog)}
        # bitwise resume: every recovered stream equals the undisturbed
        # baseline stream for its rng_tag (re-prefill + (tag, n) rng)
        rec = {r.rng_tag: list(r.generated) for r in fleet2._requests}
        assert rec == {i: base[i] for i in rec}
        jr.close()
        # the on-disk census: no journaled rid is left without exactly
        # one outcome, and settled history compacted away
        assert RequestJournal(str(jdir)).pending_rids() == []
    finally:
        _reset_journal_config(config)


def test_recover_dedupes_client_retries(gpt2, tmp_path):
    """Client retries are idempotent at the door across the whole
    lifecycle: a same-rid resubmit while pending and a same-rid resubmit
    after the outcome both dedupe instead of double-admitting."""
    ff, cfg = gpt2
    config = ff.config
    _journal_config(config, tmp_path / "d")
    try:
        fleet = _fleet(ff, cfg)
        first = _req(_prompts(1, seed=5)[0], max_new_tokens=4, rng_tag=0)
        fleet.submit(first)
        retry = _req(list(first.prompt), rid=first.rid,
                     max_new_tokens=4, rng_tag=0)
        fleet.submit(retry)  # pending retry: swallowed, not re-queued
        assert fleet.journal.dedupe_hits == 1
        fleet.run()
        assert fleet.stats.outcomes == {"ok": 1}
        late = _req(list(first.prompt), rid=first.rid,
                    max_new_tokens=4, rng_tag=0)
        fleet.submit(late)   # post-outcome retry: also swallowed
        assert fleet.journal.dedupe_hits == 2
        assert len(fleet._requests) == 1
        fleet.journal.close()
    finally:
        _reset_journal_config(config)


def test_drain_crash_recover_exactly_once(gpt2, tmp_path):
    """Satellite pin (ISSUE 20): a fleet-wide SIGTERM drain journals the
    handed-back door queue as preempted and group-commits BEFORE the
    process goes away — a recovery on the same directory replays
    nothing, and each drained request's timeline closed exactly once."""
    ff, cfg = gpt2
    config = ff.config
    _journal_config(config, tmp_path / "drain")
    try:
        fleet = _fleet(ff, cfg)
        for rep in fleet.replicas:
            rep.engine.max_queue = 0  # white-box: nothing can dispatch
        outs = fleet.generate(_prompts(3, seed=6), max_new_tokens=4,
                              chaos=FleetChaosPlan(preempt_serving_at=1))
        assert fleet.stats.outcomes == {"preempted": 3}
        assert all(o == [] for o in outs)
        assert len(fleet.drained_requests) == 3
        # the drain's outcome records are already durable: recovery on
        # the same directory finds zero unfinished rids
        fleet2 = ServingFleet.recover(ff, n_replicas=2, n_slots=2,
                                      max_decode_len=cfg.seq_len)
        assert fleet2.journal.replayed == 0
        assert fleet2.journal.pending_rids() == []
        fleet2.journal.close()
    finally:
        _reset_journal_config(config)


@pytest.mark.slow
def test_crash_sigkill_child_process_recovers(gpt2, tmp_path):
    """The real-signal mode: a child process serving with the journal on
    dies by actual SIGKILL mid-serve (crash_at sigkill), and the parent
    recovers its backlog to terminal — the tier-1 hard-mode loop without
    the in-process stand-in."""
    import subprocess
    import sys

    jdir = tmp_path / "kill"
    script = tmp_path / "serve_and_die.py"
    script.write_text(f"""
import numpy as np
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.resilience import FleetChaosPlan
from flexflow_tpu.serving import Request, ServingFleet

cfg = GPT2Config.tiny(batch_size=8)
config = FFConfig()
config.batch_size = cfg.batch_size
config.request_journal = {str(jdir)!r}
config.journal_commit_every = 1
ff = FFModel(config)
build_gpt2(ff, cfg)
ff.compile(optimizer=SGDOptimizer(ff),
           loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
rng = np.random.default_rng(6)
fleet = ServingFleet(ff, n_replicas=2, n_slots=2,
                     max_decode_len=cfg.seq_len)
for i in range(6):
    p = rng.integers(0, 100, size=int(rng.integers(3, 6)))
    fleet.submit(Request(prompt=p.astype(np.int32), max_new_tokens=6,
                         rng_tag=i))
fleet.run(chaos=FleetChaosPlan(crash_at={{6: "sigkill"}}))
raise SystemExit("still alive after SIGKILL tick")
""")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (repo, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    ff, cfg = gpt2
    config = ff.config
    _journal_config(config, jdir, commit_every=1)
    try:
        fleet = ServingFleet.recover(ff, n_replicas=2, n_slots=2,
                                     max_decode_len=cfg.seq_len)
        assert fleet.journal.replayed >= 1
        fleet.run()
        assert set(fleet.stats.outcomes) == {"ok"}
        fleet.journal.close()
        assert RequestJournal(str(jdir)).pending_rids() == []
    finally:
        _reset_journal_config(config)


# -------------------------------------------------- flags + observability
def test_journal_flags_parse_and_preflight(tmp_path):
    """--request-journal / --journal-sync-ms / --journal-commit-every:
    parse-time validation (values >= 0, tuning flags require the
    directory flag) and preflight_config's programmatic-assignment
    checks (including the parent-directory existence gate)."""
    from flexflow_tpu.resilience.preflight import (PreflightError,
                                                   preflight_config)

    cfg = FFConfig()
    assert cfg.request_journal == ""
    assert cfg.journal_sync_ms == 0.0 and cfg.journal_commit_every == 0
    cfg.parse_args(["--request-journal", str(tmp_path / "j"),
                    "--journal-sync-ms", "5", "--journal-commit-every",
                    "8"])
    assert cfg.request_journal == str(tmp_path / "j")
    assert cfg.journal_sync_ms == 5.0 and cfg.journal_commit_every == 8
    preflight_config(cfg)
    with pytest.raises(ValueError, match=">= 0"):
        FFConfig().parse_args(["--request-journal", "x",
                               "--journal-sync-ms", "-1"])
    with pytest.raises(ValueError, match=">= 0"):
        FFConfig().parse_args(["--request-journal", "x",
                               "--journal-commit-every", "-2"])
    with pytest.raises(ValueError, match="request-journal"):
        FFConfig().parse_args(["--journal-sync-ms", "5"])
    with pytest.raises(ValueError, match="request-journal"):
        FFConfig().parse_args(["--journal-commit-every", "4"])
    with pytest.raises(ValueError, match="directory"):
        FFConfig().parse_args(["--request-journal", ""])
    bad = FFConfig()
    bad.request_journal = "x"
    bad.journal_sync_ms = -3.0
    with pytest.raises(PreflightError, match=">= 0"):
        preflight_config(bad)
    tuner = FFConfig()
    tuner.journal_commit_every = 4
    with pytest.raises(PreflightError, match="request-journal"):
        preflight_config(tuner)
    orphan = FFConfig()
    orphan.request_journal = str(tmp_path / "no" / "such" / "parent")
    with pytest.raises(PreflightError, match="parent"):
        preflight_config(orphan)


def test_journal_telemetry_block_and_trace_digest(gpt2, tmp_path,
                                                  capsys):
    """The StepTelemetry ``serving_journal`` block lands next to the
    fleet block on a journaled run (and only then: the PR 16 presence
    contract) and trace_summary prints its digest."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import trace_summary

    ff, cfg = gpt2
    config = ff.config
    tel_file = tmp_path / "tel.json"
    config.telemetry_file = str(tel_file)
    _journal_config(config, tmp_path / "wal", commit_every=1)
    try:
        fleet = _fleet(ff, cfg)
        fleet.generate(_prompts(4, seed=7), max_new_tokens=4)
        fleet.journal.close()
    finally:
        config.telemetry_file = ""
        _reset_journal_config(config)
    data = json.loads(tel_file.read_text())
    blk = data["serving_journal"]
    assert blk["appended"] > 0 and blk["syncs"] >= 1
    assert blk["replayed"] == 0 and blk["truncated_records"] == 0
    trace_summary.main([str(tel_file)])
    out = capsys.readouterr().out
    assert "request journal:" in out
    # journal off -> no block (zero-overhead absence)
    tel2 = tmp_path / "tel2.json"
    config.telemetry_file = str(tel2)
    try:
        _fleet(ff, cfg).generate(_prompts(2, seed=8), max_new_tokens=3)
    finally:
        config.telemetry_file = ""
    assert "serving_journal" not in json.loads(tel2.read_text())
