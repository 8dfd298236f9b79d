"""The build registry (ISSUE 39, ``flexflow_tpu/obs/builds.py``): one record
per build of a jitted program, by name and by phase, hit or miss in the
persistent compile cache; the set-up spans beside it (``obs.setup_span`` /
``obs.setup_walls``); what ``fit`` and a serve run say they built; and that
none of it changes a result. On the CPU, against a temporary cache directory.
The registry is the process's: every test reads it from a ``build_mark()``
of its own, and starts with no entry point open (``_no_entry_point_open``:
a serve loop another file's test abandoned without ``finish()`` — the fleet's
replicas, the request journal's crashes, an op that raises under
``generate`` — leaves its ``serve`` entered in this worker's process, and
the phase tests here then read ``serve`` where they built outside every
entry point: seen under xdist at PR 46, whenever this file follows one of
those on a worker)."""
import os
import re
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu import (ActiMode, AdamOptimizer, FFConfig, FFModel,
                          LossType, MetricsType, SGDOptimizer)
from flexflow_tpu import obs
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.obs.builds import BACKEND, _register, _unregister
from flexflow_tpu.obs.trace import SETUP_SPANS
from flexflow_tpu.parallel.strategies import hybrid_data_tensor_strategy
from flexflow_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_OPTIMIZERS = {
    "sgd": lambda ff: SGDOptimizer(ff, lr=0.01),
    "sgd_momentum": lambda ff: SGDOptimizer(ff, lr=0.01, momentum=0.9),
    "adam": lambda ff: AdamOptimizer(ff, alpha=0.01),
    "adam_bf16_moments": lambda ff: AdamOptimizer(
        ff, alpha=0.01, moment_dtype=jnp.bfloat16),
}


@pytest.fixture(autouse=True)
def _no_entry_point_open():
    import sys

    entries = sys.modules["flexflow_tpu.obs.builds"]._ENTRIES
    kept = list(entries)
    del entries[:]
    yield
    entries[:] = kept


@pytest.fixture(autouse=True)
def _time_limit():
    """Every test here has its own limit, under a minute."""
    def on_alarm(signum, frame):
        raise TimeoutError("test_program_builds: a test passed its 55 s "
                           "limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, 55.0)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """A persistent compile cache of the test's own that keeps every
    program (tests/conftest.py turns the cache off for the others)."""
    from jax.experimental.compilation_cache import compilation_cache

    path = str(tmp_path / "jax_cache")
    # FFModel.compile() places no cache of its own where this is set
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": path,
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield path
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _mlp(batch=32, width=64, optimizer=None, mesh="default"):
    """``mesh``: ``"default"`` (what compile() picks over the eight virtual
    devices), ``"one_device"``, or ``"dp2_tp2"`` — four devices with the
    dense kernels split over the model axis, so the moments are sharded."""
    config = FFConfig()
    config.batch_size = batch
    config.epochs = 1
    strategy_fn = None
    if mesh == "one_device":
        config.mesh_shape = (1,)
        config.only_data_parallel = True
    elif mesh == "dp2_tp2":
        def strategy_fn(pcg):
            return hybrid_data_tensor_strategy(pcg, dp=2, tp=2)
    ff = FFModel(config)
    t = ff.create_tensor((batch, width))
    t = ff.dense(t, 32, ActiMode.AC_MODE_RELU)
    t = ff.softmax(ff.dense(t, 4))
    ff.compile(optimizer=(optimizer or _OPTIMIZERS["adam"])(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY],
               strategy_fn=strategy_fn)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4 * batch, width)).astype(np.float32)
    y = rng.integers(0, 4, size=(4 * batch,)).astype(np.int32)
    return ff, x, y


@pytest.fixture(scope="module")
def gpt2():
    cfg = GPT2Config(batch_size=8, seq_len=64, hidden=64, num_heads=4,
                     num_layers=2, intermediate=128, vocab_size=100)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    config.seed = 42
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _ones(n):
    """An argument that builds no program of its own (``jnp.ones`` does)."""
    return np.ones(n, np.float32)


def _prompts(n, length, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 99, size=length).tolist() for _ in range(n)]


class _BackendStages:
    """An independent count of what JAX compiled or loaded: the names of the
    backend stages that ended, by a listener of the test's own."""

    def __init__(self):
        self.names = []

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, seconds, **kw):
        if event == BACKEND:
            self.names.append(kw["fun_name"])


# ---------------------------------------------- one record a build, by name
def test_compile_and_fit_give_one_record_a_build(cache_dir):
    mark = obs.build_mark()
    with _BackendStages() as seen:
        ff, x, y = _mlp()
        ff.fit(x, y)
    recs = obs.builds()[mark:]
    assert len(recs) == len(seen.names) == obs.build_totals(mark)["builds"]
    # JAX says jit(step); the module, and the device trace, say jit_step
    assert [r.name for r in recs] == [
        re.sub(r"[^\w.-]", "_", n).rstrip("_") for n in seen.names]
    names = [r.name for r in recs]
    assert "jit_init_fn" in names
    # The train step is built ONCE a process: the optimizer's state leaves
    # compile() with the signature the step hands it back with, so fit's
    # first call and every later one are one program (ROADMAP.md S12 (1)).
    assert names.count("jit_step") == 1
    totals = obs.build_totals(mark)
    assert totals["by_name"]["jit_step"] == 1
    for r in recs:
        assert r.end >= r.start and r.backend_s > 0 and r.lower_s > 0
        assert r.cache == "miss" and r.load_s == 0.0  # an empty directory
    step = [r for r in recs if r.name == "jit_step"]
    assert all(r.trace_s > 0 for r in step)  # joined to its trace stage
    assert totals["misses"] == len(recs) and totals["hits"] == 0
    assert totals["compile_s"] == pytest.approx(
        sum(r.backend_s for r in recs))
    assert totals["load_s"] == 0.0


def test_a_second_build_over_the_same_directory_hits(cache_dir):
    jax.clear_caches()  # nothing this directory lacks is held in memory
    ff, x, y = _mlp()
    ff.fit(x, y)
    jax.clear_caches()  # what a second process starts with
    mark = obs.build_mark()
    ff, x, y = _mlp()
    ff.fit(x, y)
    recs = obs.builds()[mark:]
    totals = obs.build_totals(mark)
    assert totals["by_name"]["jit_step"] == 1
    assert all(r.cache == "hit" and r.load_s > 0 for r in recs), recs
    assert totals["misses"] == 0 and totals["hits"] == len(recs)
    assert totals["compile_s"] == 0 and totals["load_s"] > 0
    # tracing and lowering are paid warm or cold
    assert totals["trace_s"] > 0 and totals["lower_s"] > 0


# ----------------------------------- one signature for the life of a process
def _signatures(state):
    """What ``jax.jit`` keys a program on, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda a: (a.dtype, a.aval.weak_type, a.sharding, a.committed),
        state)


@pytest.mark.parametrize("mesh", ["one_device", "dp2_tp2"])
@pytest.mark.parametrize("optimizer", list(_OPTIMIZERS))
def test_the_step_is_built_once_a_process(optimizer, mesh, tmp_path):
    """compile(), fit, fit again and the memory analysis at fit's end (and
    one asked for by hand) build ``jit_step`` once, because every leaf of
    the optimizer's state is born as the step hands it back: dtype,
    ``weak_type``, sharding and committedness (ROADMAP.md S12 (1))."""
    from flexflow_tpu.obs.telemetry import capture_memory_analysis

    mark = obs.build_mark()
    ff, x, y = _mlp(optimizer=_OPTIMIZERS[optimizer], mesh=mesh)
    assert ff.mesh.size == {"one_device": 1, "dp2_tp2": 4}[mesh]
    born = _signatures(ff.opt_state)
    assert born["step"] == (jnp.int32, False,
                            NamedSharding(ff.mesh, PartitionSpec()), True)
    moments = jax.tree_util.tree_leaves(
        {k: v for k, v in ff.opt_state.items() if k != "step"})
    assert bool(moments) == (optimizer != "sgd")
    if moments and mesh == "dp2_tp2":  # and some of them really are split
        assert any("model" in m.sharding.spec for m in moments)
    ff.config.telemetry_file = str(tmp_path / "telemetry.json")
    ff.fit(x[:32], y[:32])  # ONE step, then the analysis: the benchmark's
    assert _signatures(ff.opt_state) == born
    assert ff.get_telemetry().device_memory is not None
    ff.fit(x, y)
    assert _signatures(ff.opt_state) == born
    assert int(ff.opt_state["step"]) == 5
    xs = [jax.device_put(x[:32], ff.executor.batch_sharding(2))]
    labels = jax.device_put(y[:32].reshape(32, 1),
                            ff.executor.batch_sharding(2))
    assert capture_memory_analysis(ff.executor, ff.params, ff.opt_state,
                                   xs, labels) is not None
    assert obs.build_totals(mark)["by_name"]["jit_step"] == 1


def test_the_cache_off_reads_off():
    """tests/conftest.py's state: no persistent cache."""
    mark = obs.build_mark()
    jax.jit(lambda v: v * 3 + 1)(_ones(5))
    (rec,) = obs.builds()[mark:]
    assert rec.cache == "off" and rec.load_s == 0.0
    totals = obs.build_totals(mark)
    assert totals["hits"] == totals["misses"] == 0
    assert totals["compile_s"] == rec.backend_s


# ------------------------------------------------------------------- phases
def test_phases_of_compile_fit_and_the_callers_own():
    mark = obs.build_mark()
    ff, x, y = _mlp()
    ff.fit(x, y)
    ff.eval(x, y)

    def mine(v):
        return jnp.tanh(v) * 2

    jax.jit(mine)(_ones(7))  # outside every entry point: the caller's
    by = {}
    for r in obs.builds()[mark:]:
        by.setdefault(r.name, set()).add(r.phase)
    assert by["jit_init_fn"] == {"param_init"}
    assert by["jit_step"] == {"fit"}  # built at the first train_step
    assert by["jit_estep"] == {"eval"}
    assert by["jit_mine"] == {None}
    assert set().union(*by.values()) <= {"param_init", "fit", "eval", None}


def test_the_memory_analysis_at_fits_end_finds_fits_own_build(tmp_path):
    """With ``--telemetry-file`` fit() ends by lowering and compiling the
    step once more for XLA's memory analysis. After a fit of ONE step — the
    benchmark's first fit is this shape — that finds the program the one
    call built (the counter it is handed is the array the call was handed),
    and the analysis is there. fit's phase ends where fit() does."""
    ff, x, y = _mlp()
    ff.config.telemetry_file = str(tmp_path / "telemetry.json")
    mark = obs.build_mark()
    ff.fit(x[:32], y[:32])
    step = [r for r in obs.builds()[mark:] if r.name == "jit_step"]
    assert [r.phase for r in step] == ["fit"]
    assert ff.get_telemetry().summary()["by_name"]["jit_step"] == 1
    assert ff.get_telemetry().device_memory["argument_size_in_bytes"] > 0
    jax.jit(lambda v: v * 11)(_ones(3))
    assert obs.builds()[-1].phase is None  # and fit's phase ended with it


def test_setup_walls_of_compile_cover_its_children():
    before = obs.setup_walls()
    top_before = obs.setup_walls(outermost=True)
    _mlp()
    walls = {k: v - before[k] for k, v in obs.setup_walls().items()}
    top = {k: v - top_before[k]
           for k, v in obs.setup_walls(outermost=True).items()}
    assert set(walls) == set(SETUP_SPANS)
    children = ("compile_graph", "search", "compile_executor", "param_init")
    assert all(walls[k] > 0 for k in ("compile", "compile_graph",
                                      "compile_executor", "param_init"))
    assert walls["compile"] >= sum(walls[k] for k in children)
    # nothing of the children is outermost, so nothing is counted twice
    assert top["compile"] == walls["compile"]
    assert all(top[k] == 0.0 for k in children)


def test_setup_span_registry():
    with pytest.raises(KeyError):
        obs.setup_span("train_step")  # a hot-loop span, no phase of set-up
    with pytest.raises(KeyError):
        obs.setup_span("not_a_registered_span")
    assert set(SETUP_SPANS) <= set(obs.SPANS)
    # a Chrome tracer handed in records the same span
    tracer = obs.Tracer()
    mark = obs.build_mark()
    before = obs.setup_walls()["search"]
    with obs.setup_span("search", tracer=tracer, n_dev=4):
        jax.jit(lambda v: v - 1)(_ones(2))
    (ev,) = tracer.events
    assert ev["name"] == "search" and ev["args"]["n_dev"] == 4
    assert obs.builds()[mark].phase == "search"
    wall = obs.setup_walls()["search"] - before
    assert 0 < wall <= ev["dur"] * 1e-6  # read inside the span's own edges


# ------------------------------------------- what does not make a record
def test_a_trace_or_a_lowering_alone_makes_no_record():
    @jax.jit
    def inner(v):
        return jnp.sin(v) + 1

    @jax.jit
    def outer(v):
        return inner(v) * inner(v + 1)

    mark = obs.build_mark()
    jax.eval_shape(outer, _ones(3))
    outer.lower(_ones(4))
    assert obs.build_mark() == mark
    # a jitted function inside another's trace: one record, the caller's
    outer(_ones(5))
    (rec,) = obs.builds()[mark:]
    assert rec.name == "jit_outer" and rec.trace_s > 0


def test_builds_on_two_threads_are_kept_apart():
    mark = obs.build_mark()

    def build(tag):
        def fn(v):
            return v * tag + tag

        fn.__name__ = f"thread_program_{tag}"
        jax.jit(fn)(_ones(8))

    threads = [threading.Thread(target=build, args=(t,)) for t in (1, 2, 3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    names = sorted(r.name for r in obs.builds()[mark:]
                   if "thread_program" in r.name)
    assert names == [f"jit_thread_program_{t}" for t in (1, 2, 3)]
    assert all(r.trace_s > 0 and r.lower_s > 0
               for r in obs.builds()[mark:] if "thread_program" in r.name)


# ----------------------------------------- what fit and a serve run report
def test_fit_telemetry_carries_what_it_built():
    ff, x, y = _mlp()
    ff._telemetry_requested = True
    mark = obs.build_mark()
    ff.fit(x, y)
    tel = ff.get_telemetry()
    s = tel.summary()
    totals = obs.build_totals(mark)
    assert s["programs_built"] == totals["builds"] >= 1
    assert s["by_name"] == totals["by_name"]
    seconds = sum(totals[k] for k in ("trace_s", "lower_s", "load_s",
                                      "compile_s"))
    assert s["build_s"] == pytest.approx(seconds, abs=1e-5)
    # the registry's seconds, no longer first step less the median step
    assert s["compile_overhead_s"] == s["build_s"] > 0
    # a second fit of the same model builds nothing, and says so
    ff._telemetry_requested = True
    ff.fit(x, y)
    s = ff.get_telemetry().summary()
    assert s["programs_built"] == 0 and "by_name" not in s
    assert s["compile_overhead_s"] == 0


def test_generate_twice_builds_nothing_new_and_a_new_bucket_one(gpt2):
    eng = ServingEngine(gpt2, n_slots=3, max_decode_len=64, kv_block_size=8)
    assert eng.buckets[0] == 16 and eng.buckets[1] == 32
    mark = obs.build_mark()
    eng.generate(_prompts(3, 5), max_new_tokens=4)
    first = obs.build_totals(mark)
    assert first["by_name"]["jit_prefill"] == 1
    assert first["by_name"]["jit_decode"] == 1
    assert eng.stats.summary()["programs_built"] > 0
    recs = obs.builds()[mark:]
    assert {r.phase for r in recs if r.name in ("jit_prefill", "jit_decode")
            } == {"serve"}  # built at a tick's first call
    assert {r.phase for r in recs} == {"serve", "kv_pool_alloc"}
    assert obs.setup_walls()["kv_pool_alloc"] > 0
    assert obs.setup_walls()["engine_build"] > 0
    # the same shapes again: nothing is built, and the run says so
    mark = obs.build_mark()
    eng.generate(_prompts(3, 5, seed=1), max_new_tokens=4)
    assert obs.build_totals(since=mark)["builds"] == 0
    assert eng.stats.programs_built == 0
    assert eng.stats.summary()["programs_built"] == 0
    # a prompt of the next bucket: the recompile under traffic, by name
    mark = obs.build_mark()
    eng.generate(_prompts(1, 20, seed=2), max_new_tokens=4)
    assert obs.build_totals(since=mark)["by_name"] == {"jit_prefill": 1}
    summary = eng.stats.summary()
    assert summary["programs_built"] == 1
    assert summary["by_name"] == {"jit_prefill": 1}
    assert summary["build_s"] > 0


# --------------------------------------------------- names, docs, no result
def test_every_setup_span_is_registered_and_documented():
    import importlib.util

    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        doc = f.read()
    for name in SETUP_SPANS:
        assert name in obs.SPANS, name
        assert re.search(rf"(?<![\w-]){name}(?![\w-])", doc), name
    for word in ("obs.builds()", "obs.build_mark()", "obs.build_totals(",
                 "obs.setup_walls(", "programs_built", "build_s",
                 "JAX_EXPLAIN_CACHE_MISSES"):
        assert word in doc, word
    spec = importlib.util.spec_from_file_location(
        "check_trace_events",
        os.path.join(REPO, "scripts", "check_trace_events.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0
    # no Chrome-only call site of the two set-up spans is left
    for rel in ("model.py", os.path.join("search", "unity.py")):
        with open(os.path.join(REPO, "flexflow_tpu", rel)) as f:
            src = f.read()
        assert 'tracer.span("compile"' not in src
        assert 'tracer.span("search"' not in src


def test_the_listeners_change_no_result(gpt2):
    """The same four steps and the same streams with the registry's
    listeners registered and unregistered: bitwise."""
    def four_steps():
        ff, x, y = _mlp()
        ff.fit(x, y)
        return float(jax.device_get(ff.get_perf_metrics().mean(
            "sparse_cce_loss"))), jax.device_get(ff.params)

    def streams():
        eng = ServingEngine(gpt2, n_slots=3, max_decode_len=64,
                            kv_block_size=8)
        return eng.generate(_prompts(4, 6, seed=3), max_new_tokens=5,
                            temperature=0.7, top_k=5, seed=1)

    mark = obs.build_mark()
    on = four_steps(), streams()
    assert obs.build_mark() > mark
    _unregister()
    try:
        mark = obs.build_mark()
        off = four_steps(), streams()
        assert obs.build_mark() == mark  # nothing listened
    finally:
        _register()
    assert on[0][0] == off[0][0]
    for a, b in zip(jax.tree_util.tree_leaves(on[0][1]),
                    jax.tree_util.tree_leaves(off[0][1])):
        assert np.array_equal(a, b)
    assert on[1] == off[1]
    jax.jit(lambda v: v + 2)(_ones(6))
    assert obs.build_mark() == mark + 1  # and they are back
