"""The readers of the program's own spans (``reduce/program_spans.py`` and the
six ``layer_metrics`` built on it): the attribution on a synthetic set of
spans and gaps, the collection on two small host traces recorded on the CPU
(``record_spans_fixture.py``), the device's gaps on the trace recorded on the
chip, and that a program without the spans gives nothing and raises nothing."""
import importlib.util
import os

import pytest

from benchmark.reduce import program_spans as P
from benchmark.reduce import xplane as X

HERE = os.path.dirname(os.path.abspath(__file__))
FIT = os.path.join(HERE, "fixtures", "tiny_fit_cpu.xplane.pb")
SERVE = os.path.join(HERE, "fixtures", "tiny_serve_cpu.xplane.pb")
CHIP = os.path.join(HERE, "fixtures", "tiny_bert_v5e.xplane.pb")
NEW = ("input_wait_ms_per_step", "input_gather_ms_per_step",
       "input_put_ms_per_step", "prefill_tick_share", "decode_tick_ms_p50",
       "idle_attributed_share")


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def fresh_cache():
    P._CACHE.clear()
    yield
    P._CACHE.clear()


# --------------------------------------------------------- synthetic spans
S = 1e9  # the trace's unit is ns
WINDOW = (0.0, 10 * S)
MAIN = [
    ("epoch", 0.0, 10 * S, {}),
    ("fit_epoch_setup", 0.0, 0.1 * S, {}),
    ("dataloader_wait", 0.1 * S, 1.1 * S, {"batch": 0}),
    ("train_step", 1.1 * S, 1.2 * S, {"step_num": 0}),
    ("dataloader_wait", 1.2 * S, 1.25 * S, {"batch": 1}),
    ("train_step", 1.25 * S, 1.3 * S, {"step_num": 1}),
    ("epoch_fold", 9.0 * S, 9.5 * S, {}),
]
# the producer works all through the first gap: it must not get the gap
PRODUCER = [("batch_gather", 0.0, 0.6 * S, {}), ("batch_put", 0.6 * S,
                                                1.1 * S, {"bytes": 8})]
GAPS = [(0.0, 1.15 * S),      # head of the pass: mostly the first wait
        (4.0 * S, 5.0 * S),   # under no leaf: only the epoch covers it
        (9.1 * S, 9.4 * S),   # inside the fold
        (1.22 * S, 1.27 * S)]  # 30 of 50 ms the wait, 20 the dispatch


def test_gaps_go_to_the_main_threads_leaf_span():
    got = P.attribute(GAPS, MAIN)
    assert [(n, named) for _, n, named in got] == [
        ("dataloader_wait", True), ("epoch", False), ("epoch_fold", True),
        ("dataloader_wait", True)]
    assert got[0][0] == pytest.approx(1.15)
    # the same gap with the producer's spans offered as if they were the
    # main thread's would go to the gather: the reduction never offers them
    r = P.reduce_spans(WINDOW, MAIN, PRODUCER, GAPS)
    by = dict(r["idle_by_span"])
    assert "batch_gather" not in by and "batch_put" not in by
    assert by["dataloader_wait"] == pytest.approx(1.15 + 0.05)
    assert by["epoch"] == pytest.approx(1.0)
    assert r["idle_s"] == pytest.approx(2.5)
    assert r["idle_named_s"] == pytest.approx(1.5)
    assert r["other_s"] == {"batch_gather": pytest.approx(0.6),
                            "batch_put": pytest.approx(0.5)}
    assert r["main_s"]["dataloader_wait"] == pytest.approx(1.05)


def test_a_gap_no_span_touches_is_untraced():
    (got,) = P.attribute([(20 * S, 21 * S)], MAIN)
    assert got[1:] == (P.UNTRACED, False)


def test_spans_are_clipped_to_the_window():
    r = P.reduce_spans((0.5 * S, 1.2 * S), MAIN, PRODUCER, [])
    assert r["main_s"]["dataloader_wait"] == pytest.approx(0.6)
    assert r["main_s"]["train_step"] == pytest.approx(0.1)
    assert "epoch_fold" not in r["main_s"]
    assert r["other_s"]["batch_gather"] == pytest.approx(0.1)
    assert r["idle_s"] == 0.0


TICKS = [("serve_tick", 0.0, 0.2 * S, {"kind": "decode"}),
         ("serve_tick", 0.2 * S, 0.3 * S, {"kind": "prefill"}),
         ("serve_tick", 0.3 * S, 0.6 * S, {"kind": "decode"}),
         ("serve_tick", 0.6 * S, 0.8 * S, {"kind": "decode"}),
         ("serve_tick", 0.8 * S, 0.9 * S, {"kind": "prefill_chunk"}),
         ("fetch_tokens", 0.05 * S, 0.2 * S, {})]


def _as_run(monkeypatch, kind, reduced, **facts):
    """A run's facts whose trace reduces to ``reduced``."""
    monkeypatch.setattr(P, "read", lambda run: reduced)
    return dict(kind=kind, trace_file="x", **facts)


def test_readers_on_the_synthetic_set(monkeypatch):
    r = P.reduce_spans(WINDOW, MAIN, PRODUCER, GAPS)
    run = _as_run(monkeypatch, "train", r, steps=2)
    assert reader("input_wait_ms_per_step").read(run) == pytest.approx(525.0)
    assert reader("input_gather_ms_per_step").read(run) == pytest.approx(300.0)
    assert reader("input_put_ms_per_step").read(run) == pytest.approx(250.0)
    assert reader("idle_attributed_share").read(run) == pytest.approx(0.6)
    assert reader("prefill_tick_share").read(run) is None  # not a serve run
    assert reader("decode_tick_ms_p50").read(run) is None
    r = P.reduce_spans((0.0, S), TICKS, [], [(0.0, 0.1 * S)])
    run = _as_run(monkeypatch, "serve", r, steps=3)
    assert reader("prefill_tick_share").read(run) == pytest.approx(0.2 / 0.9)
    assert reader("decode_tick_ms_p50").read(run) == pytest.approx(200.0)
    # the gap lies in the tick and, for 50 of its 100 ms, in the fetch: not
    # more than half, so it falls to the enclosing tick and is not named
    assert dict(r["idle_by_span"]) == {"serve_tick": pytest.approx(0.1)}
    assert reader("idle_attributed_share").read(run) == 0.0
    assert reader("input_wait_ms_per_step").read(run) is None


# ------------------------------------------------------- recorded traces
def test_fit_trace_recorded_on_the_cpu(capsys):
    run = {"kind": "train", "trace_file": FIT, "steps": 8}
    r = P.read(run)
    assert "[bench] idle by program span:" in capsys.readouterr().out
    assert r is P.read(run)  # loaded once
    assert r["chip"] is None and r["idle_s"] == 0.0  # no device in it
    assert {"epoch", "fit_epoch_setup", "dataloader_wait", "train_step",
            "epoch_fold", "fit_sync"} <= set(r["main_s"])
    assert set(r["other_s"]) == {"batch_gather", "batch_put",
                                 "prefetch_backpressure"}
    assert not set(r["other_s"]) & set(r["main_s"])
    # steps and waits tile the epochs: together under the window's wall
    assert r["main_s"]["dataloader_wait"] + r["main_s"]["train_step"] \
        <= r["main_s"]["epoch"] <= r["window_s"]
    for name, key in (("input_wait_ms_per_step", "dataloader_wait"),
                      ("input_gather_ms_per_step", "batch_gather"),
                      ("input_put_ms_per_step", "batch_put")):
        where = "main_s" if key == "dataloader_wait" else "other_s"
        assert reader(name).read(run) == pytest.approx(
            1e3 * r[where][key] / 8)
    assert reader("idle_attributed_share").read(run) is None  # nothing idle
    assert reader("prefill_tick_share").read(run) is None


def test_serve_trace_recorded_on_the_cpu():
    run = {"kind": "serve", "trace_file": SERVE, "steps": 1}
    r = P.read(run)
    walls = r["tick_walls_s"]
    assert len(walls["prefill"]) == 6  # six prompts, every tick with a kind
    assert set(walls) <= {"prefill", "decode", "idle"} and walls["decode"]
    share = reader("prefill_tick_share").read(run)
    assert share == pytest.approx(sum(walls["prefill"]) / sum(
        sum(v) for v in walls.values()))
    assert 0.0 < share < 1.0
    import statistics

    assert reader("decode_tick_ms_p50").read(run) == pytest.approx(
        1e3 * statistics.median(walls["decode"]))
    # the tick's regions tile it
    parts = sum(r["main_s"][k] for k in ("tick_dispatch", "prefill",
                                         "decode_dispatch", "fetch_tokens",
                                         "tick_bookkeep"))
    assert parts == pytest.approx(r["main_s"]["serve_tick"], rel=0.05)
    assert r["main_s"]["slot_write"] < r["main_s"]["tick_bookkeep"]


def test_device_gaps_as_the_trace_reduction_judges_them():
    """On the trace recorded on the chip (it predates the program's spans):
    the gaps recomputed here are the idle time ``reduce_trace`` reports."""
    data = X.load(CHIP)
    window, main, _ = P.collect(data, ["fit_epoch"])
    assert [n for n, *_ in main] == ["fit_epoch", "fit_epoch"]
    chip, gaps = P.device_idle(data, window)
    ref = X.reduce_trace(CHIP, window_span="bench_window")
    assert chip == ref["worst_device"] == 0
    assert X.total(gaps) * 1e-9 == pytest.approx(
        ref["window_s"] - ref["busy_s"], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reports_nothing(monkeypatch, name):
    """The parent commit has no ``obs.SPANS`` and no such span in its trace:
    every new reader gives None and raises nothing, on both kinds of run."""
    for kind in ("train", "serve"):
        run = {"kind": kind, "trace_file": CHIP, "steps": 4}
        assert reader(name).read(run) is None
        P._CACHE.clear()
    monkeypatch.setattr(P, "registry", lambda: None)
    for kind in ("train", "serve"):
        assert reader(name).read(
            {"kind": kind, "trace_file": FIT, "steps": 8}) is None
    assert reader(name).read({"kind": "train", "steps": 8}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_trace_that_does_not_load_costs_no_result(tmp_path, capsys, name):
    """run.py lets a reader raise three kinds of error and no other: a trace
    file that is cut short or not a trace reads as "no spans" (logged once),
    the run keeps its result line."""
    bad = tmp_path / "cut.xplane.pb"
    bad.write_bytes(open(FIT, "rb").read()[:1000] + b"\xff" * 64)
    for kind in ("train", "serve"):
        for path in (str(bad), str(tmp_path / "missing.xplane.pb")):
            assert reader(name).read(
                {"kind": kind, "trace_file": path, "steps": 8}) is None
    assert capsys.readouterr().out.count("program spans: nothing read") <= 2


def test_a_registry_that_does_not_import_is_no_registry(monkeypatch):
    import sys

    monkeypatch.setitem(sys.modules, "flexflow_tpu.obs", None)
    assert P.registry() is None
    assert P.read({"kind": "train", "trace_file": FIT, "steps": 8}) is None
