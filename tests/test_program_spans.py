"""The program's own spans on the profiler's clock (ISSUE 24,
``flexflow_tpu/obs/trace.py`` ``span`` / ``step_span`` / ``SPANS``): ``fit``
with its input pipeline and the serve tick, read back from a profiler trace
taken on the CPU; the always-on counters beside them; that they change no
result; and the names of the jitted programs a device trace is read by."""
import glob
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (ActiMode, AdamOptimizer, FFConfig, FFModel,
                          LossType, MetricsType, SGDOptimizer)
from flexflow_tpu import obs
from flexflow_tpu.execution.executor import PROGRAM_NAMES
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.serving import ServingEngine

FIT_MAIN = {"epoch", "fit_epoch_setup", "dataloader_wait", "train_step",
            "epoch_fold", "fit_sync"}
FIT_PRODUCER = {"batch_gather", "batch_put", "prefetch_backpressure"}
WINDOW = "test_window"


@pytest.fixture(autouse=True)
def _time_limit():
    """Every test here has its own limit, under a minute."""
    def on_alarm(signum, frame):
        raise TimeoutError("test_program_spans: a test passed its 55 s limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, 55.0)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


# ------------------------------------------------------------------ helpers
def _mlp(batch=32, width=256):
    config = FFConfig()
    config.batch_size = batch
    config.epochs = 2
    ff = FFModel(config)
    t = ff.create_tensor((batch, width))
    t = ff.dense(t, 64, ActiMode.AC_MODE_RELU)
    t = ff.softmax(ff.dense(t, 4))
    ff.compile(optimizer=AdamOptimizer(ff, alpha=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4 * batch, width)).astype(np.float32)
    y = rng.integers(0, 4, size=(4 * batch,)).astype(np.int32)
    return ff, x, y


def _gpt2():
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


@pytest.fixture(scope="module")
def gpt2():
    return _gpt2()


def _engine(ff, loop):
    return ServingEngine(ff, serve_loop=loop, n_slots=3, max_decode_len=64,
                         kv_block_size=8)


def _prompts(n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 99, size=int(rng.integers(3, 8))).tolist()
            for _ in range(n)]


def _traced(tmp_path, fn):
    """Run ``fn`` inside a profiler session and a window annotation; the
    program's spans by thread line: [(line key, [(name, start, end, stats)])]
    and the window's (start, end)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(pb)
    lines, window = [], None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = []
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in obs.SPANS:
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
            if evs:
                lines.append(((plane.name, i), sorted(evs,
                                                      key=lambda t: t[1])))
    return out, lines, window


def _main_line(lines, name):
    """The events of the one line that holds spans called ``name``."""
    holders = [evs for _, evs in lines if any(e[0] == name for e in evs)]
    assert len(holders) == 1, f"{name} on {len(holders)} thread lines"
    return holders[0]


def _sum(evs, name):
    return sum(e - s for n, s, e, _ in evs if n == name) * 1e-9


# ---------------------------------------------- (a) fit + the input pipeline
def test_fit_spans_on_the_profilers_clock(tmp_path):
    ff, x, y = _mlp()
    ff.fit(x, y)  # compiles
    _, lines, window = _traced(tmp_path, lambda: ff.fit(x, y))
    assert window is not None
    main = _main_line(lines, "train_step")
    names_main = {e[0] for e in main}
    assert FIT_MAIN <= names_main, FIT_MAIN - names_main
    others = [e for _, evs in lines if evs is not main for e in evs]
    names_other = {e[0] for e in others}
    # the producer's spans sit on another line, and only there
    assert FIT_PRODUCER <= names_other, FIT_PRODUCER - names_other
    assert not (FIT_PRODUCER & names_main)
    assert not (FIT_MAIN & names_other)
    # one clock: every span lies inside the window annotation
    for name, s, e, _ in main + others:
        assert window[0] <= s <= e <= window[1], name
    # the steps and the waits alternate on the main thread: none overlaps
    flat = [ev for ev in main if ev[0] in ("dataloader_wait", "train_step")]
    for a, b in zip(flat, flat[1:]):
        assert a[2] <= b[1], (a, b)
    steps = [ev for ev in main if ev[0] == "train_step"]
    assert len(steps) == 8  # 2 epochs x 4 batches
    assert [ev[3]["step_num"] for ev in steps] == list(range(8))
    assert all(ev[3].get("_r") == 1 for ev in steps)  # a StepTraceAnnotation
    waits = [ev for ev in main if ev[0] == "dataloader_wait"]
    assert len(waits) == 8 + 2  # every batch, and the end of each epoch
    puts = [ev for ev in others if ev[0] == "batch_put"]
    assert len(puts) == 8 and all(
        ev[3]["bytes"] == x[:32].nbytes + 32 * 4 for ev in puts)


# ------------------------- (d) input_stats() sums are the spans' sums
def test_input_stats_match_the_span_sums(tmp_path, monkeypatch):
    """Each counter is read inside its span's own two edges
    (``obs.trace.timed_span``), so however the threads are scheduled —
    the suite runs under six workers — a sum never exceeds its spans'; and
    it falls short of them by the annotation's own enter and exit alone.
    The regions are made long — every batch's gather sleeps 20 ms (the
    consumer waits for it as long) and every put 10 ms; on the CPU a put
    alone is under a millisecond — so that a few milliseconds lost between
    a clock read and an edge stay inside the 5%."""
    from flexflow_tpu.data import dataloader

    batches, put = dataloader.batch_iterator, dataloader.device_put_batch

    def slow_batches(*args, **kwargs):
        for batch in batches(*args, **kwargs):
            time.sleep(0.02)
            yield batch

    def slow_put(*args, **kwargs):
        time.sleep(0.01)
        return put(*args, **kwargs)

    ff, x, y = _mlp(batch=512, width=4096)
    ff.fit(x, y)
    assert ff.input_stats()["batches"] == 8
    monkeypatch.setattr(dataloader, "batch_iterator", slow_batches)
    monkeypatch.setattr(dataloader, "device_put_batch", slow_put)
    _, lines, _ = _traced(tmp_path, lambda: ff.fit(x, y, shuffle=True))
    stats = ff.input_stats()
    assert stats["batches"] == 8
    everything = [e for _, evs in lines for e in evs]
    for key, span_name in (("wait_s", "dataloader_wait"),
                           ("gather_s", "batch_gather"),
                           ("put_s", "batch_put")):
        total = _sum(everything, span_name)
        assert total > 0.02, (key, total)
        # never more than the spans (the allowance is the two clocks' rates:
        # perf_counter here, the profiler's there) ...
        assert stats[key] <= total * (1 + 2e-3), (key, stats[key], total)
        # ... and all of them but their edges: a region whose add is lost
        # reads a tenth or more short
        assert stats[key] >= total * 0.95 - 2e-3, (key, stats[key], total)
    assert stats["gather_s"] >= 8 * 0.02 and stats["put_s"] >= 8 * 0.01
    # reset per fit, and a copy
    stats["wait_s"] = -1.0
    assert ff.input_stats()["wait_s"] >= 0.0


# ------------------------------------------------- (b) the serve tick
@pytest.mark.parametrize("loop", ["sync", "async"])
def test_serve_tick_spans_and_buckets(tmp_path, gpt2, loop):
    eng = _engine(gpt2, loop)
    eng.generate(_prompts(2, seed=5), max_new_tokens=3)  # compiles
    eng = _engine(gpt2, loop)
    outs, lines, window = _traced(
        tmp_path, lambda: eng.generate(_prompts(), max_new_tokens=6))
    assert all(len(o) == 6 for o in outs)
    stats = eng.stats
    main = _main_line(lines, "serve_tick")
    ticks = [ev for ev in main if ev[0] == "serve_tick"]
    kinds = [ev[3].get("kind") for ev in ticks]
    assert all(k in ("prefill", "prefill_chunk", "decode", "idle")
               for k in kinds), kinds
    assert kinds.count("prefill") == stats.prefills == 6
    # the counters beside the spans: one tick, one count, the same wall
    for kind in ("prefill", "decode", "idle"):
        assert stats.ticks_by_kind[kind] == kinds.count(kind)
    assert sum(stats.ticks_by_kind.values()) == len(ticks)
    for kind in ("prefill", "decode"):
        wall = sum(e - s for n, s, e, st in ticks
                   if st.get("kind") == kind) * 1e-9
        assert stats.tick_wall_s_by_kind[kind] == pytest.approx(
            wall, rel=0.05)
    for name, s, e, _ in main:
        assert window[0] <= s <= e <= window[1], name
    wanted = {"tick_dispatch", "prefill", "slot_write", "decode_dispatch",
              "fetch_tokens", "tick_bookkeep"}
    if loop == "async":
        wanted.add("tick_overlap")
    names = {ev[0] for ev in main}
    assert wanted <= names, wanted - names

    def inside(name, keep):
        """Seconds of the ``name`` spans inside the ticks ``keep`` picks."""
        picked = [(s, e) for _, s, e, st in ticks if keep(st)]
        return sum(e - s for n, s, e, _ in main if n == name
                   and any(ts <= s and e <= te for ts, te in picked)) * 1e-9

    # The accounted regions are the spans, within 5% — or, on this toy model
    # whose dispatch is 20 us a tick, within the 4 us an annotation's own
    # enter and exit take inside a session. A tick that issued a device call
    # unpipelined counts its dispatch in host_dispatch_s ...
    own = 4e-6 * len(ticks)
    accounted = inside("tick_dispatch", lambda st: st["kind"] != "idle"
                       and not st.get("pipelined"))
    assert stats.host_dispatch_s == pytest.approx(accounted, rel=0.05,
                                                  abs=own)
    assert stats.host_bookkeep_s == pytest.approx(
        _sum(main, "tick_bookkeep"), rel=0.05, abs=own)
    if loop == "async":
        # ... and behind a step in flight in host_overlap_s, with the rest
        # of the tick's hidden host work
        hidden = inside("tick_dispatch", lambda st: st.get("pipelined")) \
            + _sum(main, "tick_overlap")
        assert stats.host_overlap_s == pytest.approx(hidden, rel=0.05,
                                                     abs=own)
        assert any(st.get("pipelined") for _, _, _, st in ticks)
    else:
        assert stats.host_overlap_s == 0.0
        assert stats.host_device_s == pytest.approx(
            _sum(main, "prefill") + _sum(main, "decode_dispatch")
            + _sum(main, "fetch_tokens"), rel=0.05, abs=own)


# ------------------------------- (c) spans change no result; the registry
def test_spans_change_no_result(tmp_path, gpt2):
    """The same four steps and the same streams with and without a profiler
    session (and with the Chrome tracer on): bitwise."""
    def four_steps():
        ff, x, y = _mlp()
        ff.fit(x, y, epochs=1)
        return float(jax.device_get(ff.get_perf_metrics().mean(
            "sparse_cce_loss"))), jax.device_get(ff.params)

    def streams(loop):
        return _engine(gpt2, loop).generate(
            _prompts(5, seed=3), max_new_tokens=5, temperature=0.7, top_k=5,
            seed=1)

    plain = four_steps(), streams("sync"), streams("async")
    (traced, lines, _) = _traced(
        tmp_path, lambda: (four_steps(), streams("sync"), streams("async")))
    assert {e[0] for _, evs in lines for e in evs} >= {"train_step",
                                                      "serve_tick"}
    tracer = obs.enable()
    try:
        chrome = four_steps(), streams("sync"), streams("async")
        recorded = {e["name"] for e in tracer.events if e["ph"] == "X"}
    finally:
        obs.disable()
    assert {"epoch", "train_step", "dataloader_wait", "batch_put",
            "serve_tick", "prefill", "tick_dispatch"} <= recorded
    for other in (traced, chrome):
        assert other[0][0] == plain[0][0]
        for a, b in zip(jax.tree_util.tree_leaves(other[0][1]),
                        jax.tree_util.tree_leaves(plain[0][1])):
            assert np.array_equal(a, b)
        assert other[1] == plain[1] and other[2] == plain[2]
    assert plain[1] == plain[2]


def test_span_registry():
    with pytest.raises(KeyError):
        obs.span("not_a_registered_span")
    with pytest.raises(KeyError):
        obs.step_span("also_not_registered", 0)
    assert FIT_MAIN | FIT_PRODUCER <= set(obs.SPANS)
    with pytest.raises(TypeError):
        obs.SPANS["x"] = "y"  # frozen
    # no session, no tracer: the bare profiler annotation, nothing of ours
    sp = obs.span("train_step", step=1)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as entered:
        entered.set_metadata(loss=0.5)
    assert isinstance(obs.step_span("serve_tick", 3),
                      jax.profiler.StepTraceAnnotation)
    # a Chrome tracer handed in records the same span, late args included
    tracer = obs.Tracer()
    with obs.span("prefill", tracer=tracer, rid=7) as sp:
        sp.set_metadata(slot=2)
    (ev,) = tracer.events
    assert ev["name"] == "prefill" and ev["ph"] == "X"
    assert ev["args"]["rid"] == 7 and ev["args"]["slot"] == 2


# -------------------------------------------------- the programs' names
@pytest.fixture(scope="module")
def lowered(gpt2):
    """(program name -> its lowering, the engine that built them)."""
    out = {}
    ff, x, y = _mlp()
    step = ff.executor.make_train_step()
    sh = ff.executor.batch_sharding
    out["step"] = step.lower(
        ff.params, ff.opt_state, [jax.device_put(x[:32], sh(2))],
        jax.device_put(y[:32, None], sh(2)), jax.random.PRNGKey(0))
    eng = ServingEngine(gpt2, n_slots=2, max_decode_len=64,
                        kv_block_size=8, prefill_chunk_tokens=16)
    eng.generate(_prompts(2, seed=11), max_new_tokens=2)
    ids = [jnp.zeros((1, eng.buckets[0]), jnp.int32)]
    out["prefill"] = eng._prefill_fn(eng.buckets[0]).lower(
        gpt2.params, ids, jnp.asarray([3], jnp.int32))
    out["decode"] = eng._decode_fn(guard=False).lower(
        gpt2.params, [jnp.zeros((eng.n_slots, 1), jnp.int32)], eng.state)
    row = jnp.zeros((eng.state.block_tables.shape[1],), jnp.int32)
    out["prefill_chunk"] = eng._chunk_fn(16).lower(
        gpt2.params, [jnp.zeros((1, 16), jnp.int32)], eng.state, row,
        jnp.int32(0), jnp.int32(4))
    assert eng._write_slot_fn is not None
    return out, eng


@pytest.mark.parametrize("program", PROGRAM_NAMES)
def test_program_names_are_pinned(lowered, program):
    """The device trace names a launch ``jit_<name>``; the benchmark finds
    the train step and the serving programs by these."""
    lowered, eng = lowered
    if program == "write":
        # jitted with donated, engine-shaped arguments: read the name the
        # jit itself carries instead of lowering it a second time
        assert eng._write_slot_fn.__name__ == "write"
        return
    text = lowered[program].as_text()
    assert text.lstrip().startswith(f"module @jit_{program} "), text[:80]


def test_named_scopes_in_the_step_programs(lowered):
    """``loss`` / ``optimizer_update`` / ``metrics`` in the train step,
    ``kv_update`` in the decode step: the op_name paths a compiled text is
    joined to a trace by."""
    lowered, _ = lowered
    import re

    # bare, or wrapped by autodiff: jit(step)/jvp(loss)/reduce_sum
    step = lowered["step"].as_text(debug_info=True)
    for scope in ("loss", "optimizer_update", "metrics"):
        assert re.search(rf'jit\(step\)[^"]*[/(]{scope}[/)]', step), scope
    decode = lowered["decode"].as_text(debug_info=True)
    assert re.search(r'jit\(decode\)[^"]*/kv_update/', decode)
