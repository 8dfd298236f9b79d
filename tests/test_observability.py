"""Observability subsystem (flexflow_tpu/obs): tracer span nesting +
Chrome-trace export, the disabled tracer's zero-footprint contract, fit()
step telemetry (compile-vs-steady split), search iteration logs, and the
OpContext profiling threading bugfix."""
import json
import os

import numpy as np
import pytest

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          MetricsType, ActiMode)
from flexflow_tpu.obs import (NoopTracer, SearchLog, StepTelemetry, Tracer,
                              disable, enable, get_tracer, set_tracer)


@pytest.fixture(autouse=True)
def _reset_tracer():
    """Each test starts and ends with the disabled singleton."""
    disable()
    yield
    disable()


def _mlp(batch=16, epochs=2, **cfg_overrides):
    config = FFConfig()
    config.batch_size = batch
    config.epochs = epochs
    for k, v in cfg_overrides.items():
        setattr(config, k, v)
    ff = FFModel(config)
    x_t = ff.create_tensor((batch, 8))
    t = ff.dense(x_t, 16, ActiMode.AC_MODE_RELU)
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


# ------------------------------------------------------------------- tracer
def test_span_nesting_and_chrome_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("outer", phase="a"):
        assert tr.depth == 1
        with tr.span("inner"):
            assert tr.depth == 2
        tr.event("marker", k=1)
        tr.counter("gauge", 42)
    assert tr.depth == 0

    path = str(tmp_path / "trace.json")
    tr.write(path)
    data = json.loads(open(path).read())  # must round-trip via json.loads
    evs = data["traceEvents"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner"}
    for e in spans.values():
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert "tid" in e and "pid" in e
    # nesting: inner is contained in outer's [ts, ts+dur] window
    o, i = spans["outer"], spans["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert i["args"]["depth"] == 1 and o["args"]["depth"] == 0
    # instant + counter events well-formed
    phs = {e["ph"] for e in evs}
    assert {"X", "i", "C"} <= phs


def test_complete_event_retroactive():
    tr = Tracer()
    tr.complete("late_span", 0.5, step=3)
    (e,) = tr.events
    assert e["ph"] == "X"
    assert abs(e["dur"] - 0.5e6) < 1.0  # 0.5 s in us
    assert e["args"]["step"] == 3


def test_disabled_tracer_is_inert_and_allocation_free():
    tr = get_tracer()
    assert isinstance(tr, NoopTracer) and not tr.enabled
    # span() returns ONE shared null context manager: the hot loop's
    # per-step cost when tracing is off is a method call, no allocation
    s1 = tr.span("a")
    s2 = tr.span("b")
    assert s1 is s2
    with s1:
        pass
    tr.event("x", y=1)
    tr.complete("x", 1.0)
    tr.counter("c", 2)
    assert len(tr.events) == 0
    tr.write()  # no-op, no file I/O (would raise on a path-less Tracer)


def test_enable_disable_singleton():
    t = enable()
    assert t.enabled and get_tracer() is t
    # second enable returns the same instance
    assert enable() is t
    prev = disable()
    assert prev is t
    assert not get_tracer().enabled


def test_jsonl_event_sink(tmp_path):
    p = str(tmp_path / "events.jsonl")
    tr = Tracer(jsonl_file=p)
    with tr.span("phase"):
        tr.event("tick", n=1)
    tr.close()
    lines = [json.loads(l) for l in open(p) if l.strip()]
    assert len(lines) == 2  # event + completed span
    assert {l["name"] for l in lines} == {"phase", "tick"}


# ------------------------------------------------------- fit tracing + tele
def test_fit_writes_chrome_trace_with_phases(tmp_path):
    trace_path = str(tmp_path / "trace.json")
    ff = _mlp(trace_file=trace_path)
    x, y = _data()
    ff.fit(x, y)
    data = json.loads(open(trace_path).read())
    names = {e["name"] for e in data["traceEvents"] if e["ph"] == "X"}
    assert {"compile", "train_step", "epoch"} <= names
    # eval flushes the trace itself — eval-only workloads get a file too
    ff.eval(x, y)
    data = json.loads(open(trace_path).read())
    names = {e["name"] for e in data["traceEvents"] if e["ph"] == "X"}
    assert "eval" in names


def test_fit_disabled_no_files_no_telemetry(tmp_path, monkeypatch):
    """Observability off: no trace/telemetry file I/O, no StepTelemetry, and
    the hot loop's tracer is the inert singleton."""
    cwd_before = set(os.listdir(tmp_path))
    monkeypatch.chdir(tmp_path)
    ff = _mlp()
    x, y = _data()
    ff.fit(x, y)
    assert ff.get_telemetry() is None
    assert set(os.listdir(tmp_path)) == cwd_before  # no files appeared
    assert len(get_tracer().events) == 0


def test_fit_telemetry_records(tmp_path):
    tel_path = str(tmp_path / "telemetry.json")
    ff = _mlp(epochs=2, telemetry_file=tel_path)
    x, y = _data()
    ff.fit(x, y)
    tel = ff.get_telemetry()
    assert tel is not None
    steps_per_epoch = 64 // 16
    assert tel.steps == steps_per_epoch * 2
    assert len(tel.loss_history) == tel.steps
    assert all(np.isfinite(v) for v in tel.loss_history)
    # compile-vs-steady split: first step carries the jit compile
    assert tel.first_step_s() > tel.steady_step_s()
    data = json.loads(open(tel_path).read())
    assert data["steps"] == tel.steps
    assert data["first_step_s"] >= data["steady_step_s"]
    assert data["compile_overhead_s"] >= 0
    assert data["samples_per_sec"] > 0
    assert len(data["epoch_loss"]) == 2
    # XLA compiled-memory capture is best-effort (CPU exposes a subset of
    # the CompiledMemoryStats fields)
    if data.get("device_memory"):
        assert all(isinstance(v, int) for v in
                   data["device_memory"].values())


def test_step_telemetry_summary_math():
    import jax.monitoring as monitoring

    from flexflow_tpu.obs.builds import BACKEND, LOWER, TRACE

    def stage(event, name, start, seconds):
        """One stage of a build, as JAX reports it (obs/builds.py listens)."""
        monitoring.record_scalar(event, start, fun_name=name)
        monitoring.record_event_time_span(event, start, start + seconds,
                                          fun_name=name)

    tel = StepTelemetry(batch_size=10)
    # the first step holds a build of the step program: 0.2 s tracing,
    # 0.1 s lowering, 0.6 s in the backend
    stage(TRACE, "step", 100.0, 0.2)
    stage(LOWER, "jit(step)", 100.2, 0.1)
    stage(BACKEND, "jit(step)", 100.3, 0.6)
    tel.record_step(1.0, 2.0)
    tel.record_step(0.1, 1.0)
    tel.record_step(0.2, 0.5)
    tel.record_step(0.1, 0.4)
    tel.finalize()
    stage(BACKEND, "jit(later)", 200.0, 5.0)  # after the run: not its own
    assert tel.first_step_s() == 1.0
    assert tel.steady_step_s() == 0.1
    assert tel.samples_per_sec() == pytest.approx(100.0)
    s = tel.summary()
    # the registry's seconds for the builds inside the run, and no longer
    # first step less the median of the rest (1.0 - 0.1)
    assert s["compile_overhead_s"] == pytest.approx(0.9)
    assert s["build_s"] == pytest.approx(0.9)
    assert s["programs_built"] == 1 and s["by_name"] == {"jit_step": 1}
    assert s["loss_history"] == [2.0, 1.0, 0.5, 0.4]


# ------------------------------------------------------------------- search
def test_search_emits_iteration_events_and_log(tmp_path):
    from flexflow_tpu.search.unity import unity_search

    log_path = str(tmp_path / "search.jsonl")
    tracer = enable()
    config = FFConfig()
    config.batch_size = 32
    config.search_log_file = log_path
    ff = FFModel(config)
    x_t = ff.create_tensor((32, 64))
    t = ff.dense(x_t, 64)
    t = ff.dense(t, 16)
    t = ff.softmax(t)
    pcg = ff.create_pcg()
    unity_search(pcg, config, 4)
    # tracer saw >=1 iteration event + the search span
    names = [e["name"] for e in tracer.events]
    assert "unity_iter" in names
    assert any(e["name"] == "search" and e["ph"] == "X"
               for e in tracer.events)
    # JSONL log is consumable: candidate records carry the required fields
    recs = [json.loads(l) for l in open(log_path) if l.strip()]
    cands = [r for r in recs if r.get("event") == "candidate"]
    assert len(cands) >= 1
    for r in cands:
        assert {"cost_ms", "accepted", "best_ms", "dp", "tp"} <= set(r)
    assert any(r.get("event") == "result" for r in recs)
    # trace_summary.py parses it
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "trace_summary", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "trace_summary.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    kind, payload = mod.load(log_path)
    assert kind == "jsonl" and len(payload) == len(recs)
    assert mod.main([log_path]) == 0


def test_mcmc_emits_iteration_log(tmp_path):
    from flexflow_tpu.search.unity import mcmc_optimize

    log_path = str(tmp_path / "mcmc.jsonl")
    config = FFConfig()
    config.batch_size = 16
    config.search_log_file = log_path
    ff = FFModel(config)
    x_t = ff.create_tensor((16, 32))
    t = ff.dense(x_t, 32)
    t = ff.softmax(t)
    pcg = ff.create_pcg()
    mcmc_optimize(pcg, config, 2, iterations=10)
    recs = [json.loads(l) for l in open(log_path) if l.strip()]
    iters = [r for r in recs if r.get("event") == "mcmc"]
    assert len(iters) == 10
    for r in iters:
        assert {"cost_ms", "accepted", "temperature", "best_ms"} <= set(r)


def test_search_log_counts_without_sinks():
    slog = SearchLog()
    slog.log(event="candidate", cost_ms=1.0)
    slog.log(event="candidate", cost_ms=2.0)
    slog.close()
    assert slog.iterations == 2


# ------------------------------------------------- OpContext profiling fix
def test_opcontext_profiling_threaded(monkeypatch):
    """executor.make_* must pass config.profiling into OpContext (it was
    silently dropped before the obs PR)."""
    ff = _mlp(epochs=1)
    ff.config.profiling = True
    ff.executor._forward_jit = None  # force a rebuild that re-captures
    seen = []
    node = next(n for n in ff.pcg.compute_nodes())
    orig = node.op.forward

    def spy(params, inputs, ctx):
        seen.append(ctx.profiling)
        return orig(params, inputs, ctx)

    monkeypatch.setattr(node.op, "forward", spy)
    x, _ = _data(n=16)
    fwd = ff.executor.make_forward()
    fwd(ff.params, [x])
    assert seen and all(seen), "profiling flag not threaded into OpContext"


def test_named_scope_in_hlo():
    """Per-op jax.named_scope makes node names visible to XLA metadata."""
    import jax

    ff = _mlp(epochs=1)
    x, _ = _data(n=16)

    def f(params, xs):
        from flexflow_tpu.ops.base import OpContext

        vals = ff.executor.forward_outputs(
            params, ff.executor._bind_inputs(xs),
            OpContext(training=False, rng=None, mesh=ff.mesh))
        return vals[ff.final_guid][0]

    hlo = jax.jit(f).lower(ff.params, [x]).as_text()
    # dense layer names appear in op metadata / scopes
    assert "dense" in hlo.lower()
