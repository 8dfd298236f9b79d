"""Closed-loop calibration (ISSUE 8, docs/calibration.md): per-op measured
profiling joined on the op-cost cache key, the sim-vs-measured drift
sentinel, trace-driven recalibration with EXACT delta-cost invalidation,
persistent calibration tables, the top-K re-rank, and the fit-level
acceptance episode: a deliberately perturbed cost is detected, repaired
from the trace without hand-retuning, and only the moved keys' cache
entries die (selfcheck-asserted)."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from flexflow_tpu import (ActiMode, AdamOptimizer, FFConfig, FFModel,
                          LossType, MetricsType)
from flexflow_tpu.obs import disable
from flexflow_tpu.obs.drift import DriftSentinel
from flexflow_tpu.obs.profile import OpProfile, OpRecord, profile_model
from flexflow_tpu.search.machine_model import TPUMachineModel
from flexflow_tpu.search.simulator import OpSharding, Simulator

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))


@pytest.fixture(autouse=True)
def _reset_tracer():
    disable()
    yield
    disable()


def _mlp(batch=16, epochs=1, **cfg_overrides):
    """Four dense layers; the two middle ones are IDENTICAL op shapes, so
    the profile/key machinery's dedup contract is observable."""
    config = FFConfig()
    config.batch_size = batch
    config.epochs = epochs
    for k, v in cfg_overrides.items():
        setattr(config, k, v)
    ff = FFModel(config)
    x_t = ff.create_tensor((batch, 8))
    t = ff.dense(x_t, 16, ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 16, ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 16, ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    return ff


def _data(n=64, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    return x, y


def _graph_keys(sim, pcg):
    """repr(op key) -> (node, in_shapes) for every compute node."""
    out = {}
    for node in pcg.compute_nodes():
        in_shapes = [pcg.nodes[g].out_shapes[i] for g, i in node.inputs]
        out.setdefault(repr(sim._op_key(node, in_shapes)),
                       (node, in_shapes))
    return out


def _synthetic_records(sim, pcg, scale=None):
    """OpRecords whose measured time IS the simulator's prediction (scaled
    per key when asked) — deterministic drift, no wall clocks involved."""
    records = []
    for krepr, (node, in_shapes) in _graph_keys(sim, pcg).items():
        sh = OpSharding()
        predicted = sim.op_cost(node, in_shapes, sh).forward_time
        s = (scale or {}).get(krepr, 1.0)
        records.append(OpRecord(
            name=node.name, op_type=node.op.op_type.name, key=krepr,
            in_shapes=[list(s_) for s_ in in_shapes],
            sharding=dataclasses.asdict(sh), dcn=(1, 1),
            measured_fwd_s=predicted * s))
    return records


# --------------------------------------------------------------- profiling
def test_profile_records_join_on_op_cost_key():
    """ProfiledStep records carry the SAME key the op-cost cache uses, and
    identical op shapes (BERT's 24 layers; here two twin dense layers)
    collapse into one timed record with count=2."""
    import jax

    ff = _mlp()
    x, _y = _data()
    sim = Simulator(TPUMachineModel.detect(1))
    bx = [jax.device_put(x[:16], ff.executor.batch_sharding(x.ndim))]
    records = profile_model(ff, bx, iters=2, sim=sim)
    keys = _graph_keys(sim, ff.pcg)
    assert records, "no ops profiled"
    for r in records:
        assert r.key in keys, f"profile key {r.key!r} not an op-cost key"
        assert r.measured_fwd_s > 0
        assert r.predicted_fwd_s is not None and r.predicted_fwd_s > 0
    # dedup: 5 compute nodes (4 dense + softmax), the twin 16->16 dense
    # layers share one record
    by_count = {r.name: r.count for r in records}
    assert len(records) == len(keys) == 4
    assert 2 in by_count.values(), f"twin layers not deduped: {by_count}"
    # every compute node is accounted for exactly once across counts
    assert sum(r.count for r in records) == \
        len(list(ff.pcg.compute_nodes()))


def test_opprofile_jsonl_roundtrip(tmp_path):
    """The --profile-ops artifact round-trips; foreign/garbage lines are
    skipped; later passes supersede earlier ones per key; unknown future
    fields don't break the reader."""
    p = str(tmp_path / "prof.jsonl")
    r1 = OpRecord(name="a", op_type="OP_LINEAR", key="K1",
                  in_shapes=[[16, 8]], sharding={"dp": 1}, dcn=(1, 1),
                  measured_fwd_s=1e-5, step=0)
    r2 = OpRecord(name="a", op_type="OP_LINEAR", key="K1",
                  in_shapes=[[16, 8]], sharding={"dp": 1}, dcn=(2, 1),
                  measured_fwd_s=2e-5, step=1)
    OpProfile([r1]).write_jsonl(p)
    with open(p, "a") as f:
        f.write("not json at all\n")
        f.write(json.dumps({"event": "unity_iter", "cost_ms": 1}) + "\n")
        d = r2.to_json()
        d["future_field"] = {"schema": "grows"}  # unknown field tolerated
        f.write(json.dumps(d) + "\n")
    prof = OpProfile.read_jsonl(p)
    assert len(prof) == 2
    latest = prof.latest_by_key()
    assert set(latest) == {"K1"}
    assert latest["K1"].measured_fwd_s == pytest.approx(2e-5)
    assert latest["K1"].dcn == (2, 1)  # tuple restored from JSON list
    # a valid-JSON line that LOOKS like a record but lacks required fields
    # (hand-edited / foreign writer) is skipped, not a TypeError
    with open(p, "a") as f:
        f.write(json.dumps({"key": "K9", "measured_fwd_s": 1e-5}) + "\n")
    assert len(OpProfile.read_jsonl(p)) == 2


def test_profile_skips_training_gated_ops():
    """Dropout's inference-mode forward is identity: timing it would
    measure dispatch overhead and the closed loop would slam its
    calibration to the floor — the profiled pass executes it for its
    consumers but never emits a record."""
    import jax

    config = FFConfig()
    config.batch_size = 16
    ff = FFModel(config)
    x_t = ff.create_tensor((16, 8))
    t = ff.dense(x_t, 16, ActiMode.AC_MODE_RELU)
    t = ff.dropout(t, rate=0.5)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    x, _y = _data(n=16)
    bx = [jax.device_put(x, ff.executor.batch_sharding(x.ndim))]
    records = profile_model(ff, bx, iters=1)
    assert records, "no ops profiled"
    assert "OP_DROPOUT" not in {r.op_type for r in records}
    # downstream consumers of the dropout output were still profiled
    assert {r.op_type for r in records} >= {"OP_LINEAR", "OP_SOFTMAX"}


# ---------------------------------------------------------- drift sentinel
def test_drift_sentinel_flags_only_the_perturbed_key():
    """Deterministic drift: measured == predicted for every key except one
    whose calibration we bend 5x. The sentinel flags exactly that key,
    names it worst, and emits calibration_drift tracer events."""
    from flexflow_tpu.obs import enable, get_tracer

    ff = _mlp()
    sim = Simulator(TPUMachineModel.detect(1))
    # these ops are tiny: with the default per-op dispatch overhead the
    # roofline term (the part per-key calibration scales) is ~1% of the
    # predicted cost and NO calibration bend could leave the band. Zero it
    # so predicted == roofline * calibration and the 5x bend is a 5x lie.
    sim.op_overhead = 0.0
    records = _synthetic_records(sim, ff.pcg)
    sentinel = DriftSentinel(sim, ff.pcg, tolerance=0.25)
    clean = sentinel.observe(records, step=0)
    assert clean["out_of_band"] == []
    assert clean["aggregate_ratio"] == pytest.approx(1.0, rel=1e-6)

    victim = records[0].key
    key = next(k for k in _graph_keys(sim, ff.pcg) if k == victim)
    node, in_shapes = _graph_keys(sim, ff.pcg)[key]
    op_key = sim._op_key(node, in_shapes)
    sim._key_calibration[op_key] = \
        sim._key_calibration.get(op_key, sim.calibration) * 5
    sim.invalidate_op_keys([op_key])  # the ruler changed under the cache
    enable()
    fresh = DriftSentinel(sim, ff.pcg, tolerance=0.25)
    drift = fresh.observe(records, step=1)
    assert drift["out_of_band"] == [victim]
    assert drift["worst_key"] == records[0].name
    # measured/predicted with predicted 5x inflated -> ~0.2
    assert drift["worst_ratio"] == pytest.approx(0.2, rel=0.05)
    evs = [e for e in get_tracer().events
           if e.get("name") == "calibration_drift"]
    assert len(evs) == 1 and evs[0]["args"]["op"] == records[0].name
    # band semantics: symmetric in ratio space around 1.0
    assert fresh.in_band(1.0) and fresh.in_band(1.24) and \
        fresh.in_band(1 / 1.24)
    assert not fresh.in_band(1.26) and not fresh.in_band(1 / 1.26)


# ----------------------------------------- selective, EXACT invalidation
def test_calibrate_from_profile_invalidates_exactly_the_moved_keys():
    """The tentpole's cache contract, deterministically: after a clean
    calibration, one key's measurement moves 5x. calibrate_from_profile
    updates ONLY that key, and the delta-cost caches lose EXACTLY the
    entries built over it — every cost entry at any sharding/dcn, every
    DP option table — while all other entries survive (no full flush)."""
    from flexflow_tpu.search.unity import SearchSpace, dp_assign

    ff = _mlp()
    sim = Simulator(TPUMachineModel.detect(1))
    # overhead-free sim: predicted == roofline * per-key calibration, so
    # the settle pass is an exact no-op and the 5x scale maps to exactly
    # one moved key (with the default overhead these tiny ops sit under
    # calibrate_from_profile's 0.1*t measurement floor and every key
    # would legitimately move on the first pass)
    sim.op_overhead = 0.0
    # settle calibration so only the deliberate perturbation moves
    base = _synthetic_records(sim, ff.pcg)
    sim.calibrate_from_profile(OpProfile(base), ff.pcg)
    base = _synthetic_records(sim, ff.pcg)  # re-predict under settled cal

    # prime BOTH cache sides: raw cost entries + the DP's option tables
    dp_assign(ff.pcg, sim, 1, 1, 16, space=SearchSpace.full())
    for krepr, (node, in_shapes) in _graph_keys(sim, ff.pcg).items():
        sim.op_cost(node, in_shapes, OpSharding())
        sim.op_cost(node, in_shapes, OpSharding(remat="full"))
    assert sim._cost_cache and sim._table_cache

    victim = base[0].key
    node, in_shapes = _graph_keys(sim, ff.pcg)[victim]
    victim_op_key = sim._op_key(node, in_shapes)
    old_fwd = sim.op_cost(node, in_shapes, OpSharding()).forward_time
    cost_before = set(sim._cost_cache)
    table_before = set(sim._table_cache)

    rep = sim.calibrate_from_profile(
        OpProfile(_synthetic_records(sim, ff.pcg, scale={victim: 5.0})),
        ff.pcg)
    assert rep["matched"] == len(base)
    assert rep["updated"] == 1
    assert [u[0] for u in rep["updates"]] == [victim]

    cost_dead = cost_before - set(sim._cost_cache)
    table_dead = table_before - set(sim._table_cache)
    # exactly the victim's entries died...
    assert cost_dead and all((k[0], k[1]) == victim_op_key
                             for k in cost_dead)
    assert table_dead and all((k[1], k[2]) == victim_op_key
                              for k in table_dead)
    # ...and the counts the caller gets match the real removals
    assert rep["invalidated"] == {"cost_entries": len(cost_dead),
                                  "table_entries": len(table_dead)}
    # everything else survived warm (no full flush)
    assert set(sim._cost_cache) == cost_before - cost_dead
    assert set(sim._table_cache) == table_before - table_dead
    # the repaired cost prices the measurement: ~5x the settled cost
    new_fwd = sim.op_cost(node, in_shapes, OpSharding()).forward_time
    assert new_fwd == pytest.approx(5 * old_fwd, rel=0.15)


# ---------------------------------------------------- persistent tables
def test_persistent_table_lazy_adoption(tmp_path):
    """A table stored by one Simulator prices a fresh one identically:
    entries are adopted lazily on the uncached op-cost path."""
    from flexflow_tpu.search.calibration import store_persistent_calibration

    ff = _mlp()
    cal_dir = str(tmp_path / "cal")
    sim_a = Simulator(TPUMachineModel.detect(1), calibration_dir=cal_dir,
                      dtype_label="f32")
    sim_a.calibrate_from_profile(
        OpProfile(_synthetic_records(sim_a, ff.pcg, scale={
            k: 3.0 for k in _graph_keys(sim_a, ff.pcg)})), ff.pcg)
    assert sim_a._key_calibration
    path = store_persistent_calibration(sim_a)
    assert path and os.path.isfile(path)

    sim_b = Simulator(TPUMachineModel.detect(1), calibration_dir=cal_dir,
                      dtype_label="f32")
    assert not sim_b._key_calibration  # nothing adopted yet: lazy
    for krepr, (node, in_shapes) in _graph_keys(sim_a, ff.pcg).items():
        a = sim_a.op_cost(node, in_shapes, OpSharding()).forward_time
        b = sim_b.op_cost(node, in_shapes, OpSharding()).forward_time
        assert a == b, f"adopted calibration disagrees for {krepr}"
    assert sim_b._key_calibration  # adoption happened on the priced path


# ------------------------------------------------------- trace-driven cal
def test_calibrate_from_trace_into_search(tmp_path):
    """--calibrate-from-trace replays a --profile-ops JSONL into the
    search simulator BEFORE ranking; the warm winner simulator rides out
    on SearchResult.sim. A missing profile fails fast both ways."""
    from flexflow_tpu.search.calibration import calibrate_sim_from_trace
    from flexflow_tpu.search.unity import unity_search

    ff = _mlp()
    sim0 = Simulator(TPUMachineModel.detect(1))
    p = str(tmp_path / "prof.jsonl")
    OpProfile(_synthetic_records(sim0, ff.pcg, scale={
        k: 2.0 for k in _graph_keys(sim0, ff.pcg)})).write_jsonl(p)

    sim = Simulator(TPUMachineModel.detect(1))
    rep = calibrate_sim_from_trace(sim, ff.pcg, p)
    assert rep["matched"] == 4 and rep["updated"] == 4

    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.calibrate_from_trace = p
    res = unity_search(ff.pcg, cfg, 1, return_result=True)
    assert res.sim is not None
    assert res.sim._key_calibration, \
        "search did not replay the trace into its simulator"

    with pytest.raises(FileNotFoundError, match="no such profile"):
        calibrate_sim_from_trace(sim, ff.pcg, str(tmp_path / "nope.jsonl"))


def test_rerank_candidates_reprices_fallback_chain():
    """After a repair, the PR 5 top-K chain is re-priced: runners-up
    re-sort feasible-first by the repaired time, rank 0 (the LIVE plan)
    keeps its place, and a calibration_rerank event reports the verdict."""
    from flexflow_tpu.obs import enable, get_tracer
    from flexflow_tpu.search.calibration import rerank_candidates
    from flexflow_tpu.search.unity import RankedCandidate

    ff = _mlp()
    sim = Simulator(TPUMachineModel.detect(1))
    # chain: live winner + two runners-up with deliberately WRONG stale
    # costs (the stale order says full-remat is faster, which re-pricing
    # under the repaired ruler must overturn: recompute costs time)
    ff._strategy_candidates = [
        RankedCandidate(mesh_shape=(1, 1), sim_time=1e-3),
        RankedCandidate(mesh_shape=(1, 1), remat="full", sim_time=1e-9),
        RankedCandidate(mesh_shape=(1, 1), remat="selective",
                        sim_time=2e-9),
    ]
    enable()
    assert rerank_candidates(ff, sim) is True
    cands = ff._strategy_candidates
    assert cands[0].mesh_shape == (1, 1) and cands[0].remat == "none"
    tail = cands[1:]
    assert all(t.sim_time > 1e-8 for t in tail), "stale costs survived"
    assert tail[0].sim_time <= tail[1].sim_time
    assert {t.remat for t in tail} == {"full", "selective"}
    evs = [e for e in get_tracer().events
           if e.get("name") == "calibration_rerank"]
    assert len(evs) == 1 and evs[0]["args"]["changed"] is True
    # a chain of one is a no-op (nothing to re-rank against)
    ff._strategy_candidates = cands[:1]
    assert rerank_candidates(ff, sim) is False


# ------------------------------------------------ the acceptance episode
def test_closed_loop_fit_detects_and_repairs_perturbed_cost(
        tmp_path, monkeypatch, capsys):
    """ROADMAP item 2's chaos acceptance, end to end under the selfcheck
    env: a profiled fit settles calibration; one op's cost is then
    deliberately perturbed 8x; the next profiled fit's sentinel flags the
    drift, --auto-recalibrate repairs sim-vs-measured back inside the
    tolerance band from the trace alone, the delta-cost caches lose only
    moved keys (any stale survivor would trip the selfcheck gate on its
    next hit), and the episode is visible in the drift events, the
    telemetry "calibration" block, and the trace_summary digest."""
    import trace_summary

    from flexflow_tpu.obs import enable

    monkeypatch.setenv("FLEXFLOW_TPU_SEARCH_SELFCHECK", "1")
    prof = str(tmp_path / "prof.jsonl")
    tel_path = str(tmp_path / "telemetry.json")
    jsonl = str(tmp_path / "events.jsonl")
    enable(jsonl_file=jsonl)  # the alertable sink drift events land in
    ff = _mlp(profile_ops=prof, auto_recalibrate=True,
              telemetry_file=tel_path)
    ff.config.drift_tolerance = 0.25
    x, y = _data()

    # the profiled pass runs the live graph, node by node, as always; its
    # best-of-N wall times — CPU timings under however many test workers
    # share the host — are replaced by known per-op times (20, 40, 60,
    # 80 us in graph order), so that the perturbation, the drift event
    # and the repair below are asserted on known numbers
    from flexflow_tpu.execution.executor import Executor

    real_profile_ops = Executor.profile_ops

    def known_times(self, params, xs, iters=3):
        raw = real_profile_ops(self, params, xs, iters=1)
        for i, rec in enumerate(sorted(raw, key=lambda r: r["guid"])):
            rec["measured_fwd_s"] = 20e-6 * (i + 1)
        return raw

    monkeypatch.setattr(Executor, "profile_ops", known_times)

    # fit 1: the profiled pass measures the live graph and the closed
    # loop settles the (measured vs TPU-sim) ruler to ~1.0
    ff.fit(x, y)
    tel = json.loads(open(tel_path).read())
    cal = tel["calibration"]
    assert cal["profiled_keys"] == 4
    assert cal["recalibrations"] >= 1
    assert cal["ratio_after"] == pytest.approx(1.0, abs=1e-3)
    lines = [json.loads(ln) for ln in open(prof) if ln.strip()]
    assert len(lines) == 4 and all(
        ln["event"] == "op_profile" for ln in lines)

    # chaos: bend ONE op's calibration 8x (the sim's ruler now lies about
    # that op only) and drop its stale cache entries, as any real cost
    # perturbation would
    sim = ff._calibration_sim
    node = next(iter(ff.pcg.compute_nodes()))
    in_shapes = [ff.pcg.nodes[g].out_shapes[i] for g, i in node.inputs]
    op_key = sim._op_key(node, in_shapes)
    cost_survivors = {k for k in sim._cost_cache
                      if (k[0], k[1]) != op_key}
    sim._key_calibration[op_key] *= 8
    sim.invalidate_op_keys([op_key])
    assert cost_survivors <= set(sim._cost_cache), \
        "perturbation invalidation was not selective"

    # fit 2: detect + repair, no hand-retuning
    ff.fit(x, y)
    tel = json.loads(open(tel_path).read())
    cal = tel["calibration"]
    # the other three keys measure what the repaired ruler predicts, so
    # exactly one is out of band, and it reads the perturbation back:
    # 20 us measured against 8 x 20 predicted (156.5: the op's fixed
    # part is not scaled)
    assert cal["out_of_band"] == 1
    assert cal["worst_key"] == node.name, \
        f"sentinel blamed {cal['worst_key']}, perturbed {node.name}"
    assert cal["worst_ratio"] == pytest.approx(1 / 8, rel=0.05)
    assert cal["recalibrations"] >= 1 and cal["invalidated_entries"] >= 1
    assert cal["ratio_after"] == pytest.approx(1.0, abs=1e-3), \
        f"repair left ratio {cal['ratio_after']} outside the band"

    # selfcheck backstop: re-price every key on the repaired sim — a
    # stale cache entry for a moved key would assert inside op_cost
    sent = ff._drift_sentinel
    post = sent.ratios(OpProfile.read_jsonl(prof).latest_by_key().values())
    assert post["aggregate_ratio"] is not None

    # the episode is alertable: drift + repair events in the JSONL sink
    evs = [json.loads(ln) for ln in open(jsonl) if ln.strip()]
    names = [e.get("name") for e in evs]
    assert "calibration_drift" in names
    assert "calibration_repair" in names
    drifts = [e["args"] for e in evs
              if e.get("name") == "calibration_drift"]
    # fit 1 flags all four keys (an uncalibrated ruler), fit 2 the
    # perturbed one alone
    assert [d["op"] for d in drifts[4:]] == [node.name]
    assert drifts[-1]["measured_us"] == pytest.approx(20.0)

    # ...and in both trace_summary digests
    assert trace_summary.main([tel_path]) == 0
    out = capsys.readouterr().out
    assert "calibration:" in out and "recalibrations applied" in out
    assert trace_summary.main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "calibration drift" in out and "recalibration applied" in out


def test_profile_ops_plain_fit_untouched(tmp_path):
    """Without --profile-ops the loop is disarmed: no profile file, no
    calibration telemetry block, no sentinel state on the model."""
    tel_path = str(tmp_path / "telemetry.json")
    ff = _mlp(telemetry_file=tel_path)
    x, y = _data()
    ff.fit(x, y)
    assert "calibration" not in json.loads(open(tel_path).read())
    assert getattr(ff, "_drift_sentinel", None) is None
    assert not os.listdir(str(tmp_path)) == []  # telemetry only
