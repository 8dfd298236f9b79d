"""The reduction of PR 33 (one load, one list of events, one sweep) against
the one it replaced, kept in ``reduce_oracle.py``: equal in every field and
digit on the three recorded traces and on seeded random gaps and spans that
hold the hard cases; and its cost, counted in (gap, span) pairs looked at,
never read from a clock."""
import os
import random

import pytest

from benchmark.reduce import program_spans as P
from benchmark.reduce import xplane as X
from benchmark.tests import reduce_oracle as O

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_SPANS = ["fit_epoch", "generator_sleep", "tick", "admit", "compile"]


def fixture(name):
    return os.path.join(HERE, "fixtures", name + ".xplane.pb")


@pytest.fixture(autouse=True)
def fresh_caches():
    P._CACHE.clear()
    X._TRACES.clear()
    yield
    P._CACHE.clear()
    X._TRACES.clear()


# ------------------------------------------------------- recorded traces
@pytest.mark.parametrize("name", ["tiny_bert_v5e", "tiny_fit_cpu",
                                  "tiny_serve_cpu"])
def test_recorded_trace_reduces_to_the_same(name):
    path = fixture(name)
    for window_span in ("bench_window", None):
        new = X.reduce_trace(path, span_names=BENCH_SPANS,
                             window_span=window_span)
        assert new == O.reduce_trace(path, span_names=BENCH_SPANS,
                                     window_span=window_span)
    if name == "tiny_bert_v5e":  # the only one with a device in it
        assert new["n_devices"] == 1 and len(new["idle_gaps"]) >= 2


@pytest.mark.parametrize("name,names", [
    ("tiny_bert_v5e", BENCH_SPANS),  # it predates the program's spans
    ("tiny_fit_cpu", None), ("tiny_serve_cpu", None)])
def test_recorded_trace_gives_the_same_program_spans(name, names, capsys):
    names = names or P.registry()
    new = P._reduce_file(fixture(name), names)
    assert new is not None and new == O.reduce_file(fixture(name), names)
    if name == "tiny_bert_v5e":
        assert new["idle_s"] > 0 and new["idle_by_span"]


def test_one_load_serves_both_reductions(monkeypatch):
    import jax

    loads = []
    real = jax.profiler.ProfileData.from_file
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda p: loads.append(p) or real(p))
    path = fixture("tiny_bert_v5e")
    X.reduce_trace(path, span_names=BENCH_SPANS, window_span="bench_window")
    P.read({"kind": "train", "trace_file": path, "steps": 4})
    P._reduce_file(path, BENCH_SPANS)
    assert loads == [path]


# ------------------------------------------------- seeded gaps and spans
def nested_spans(rng, n_ticks, t0=0.0):
    """Ticks back to back, each an enclosing ``serve_tick`` over leaves that
    tile most of it, one leaf holding a child of its own."""
    spans, t = [], t0
    for k in range(n_ticks):
        wall = rng.choice([50.0, 200.0, 210.0])
        spans.append(("serve_tick", t, t + wall, {"kind": "decode"}))
        cuts = sorted(rng.uniform(0, wall) for _ in range(3))
        edges = [0.0] + cuts + [wall * rng.choice([1.0, 0.9])]
        for name, a, b in zip(("tick_dispatch", "decode_dispatch",
                               "fetch_tokens", "tick_bookkeep"),
                              edges, edges[1:]):
            spans.append((name, t + a, t + b, {}))
        a, b = edges[2], edges[3]
        spans.append(("slot_write", t + a + (b - a) / 4, t + b - (b - a) / 4,
                      {}))
        t += wall + rng.choice([0.0, 0.0, 3.0])  # some ticks touch, some not
    return spans


def hard_cases(rng):
    main = nested_spans(rng, 40)
    end = max(e for _, _, e, _ in main)
    # equal in cover and in length: the first in the list's order wins
    main += [("twin_a", 1000.0, 1010.0, {}), ("twin_b", 1000.0, 1010.0, {}),
             ("zero_length", 500.0, 500.0, {})]
    rng.shuffle(main)  # list order is not start order
    gaps = [(end + 100.0, end + 110.0),   # touched by no span
            (1002.0, 1004.0),             # the twins
            (500.0, 500.0), (777.0, 777.0),  # zero-length gaps
            (-50.0, 25.0)]                # begins before every span
    for name, s, e, _ in main:
        if name == "fetch_tokens" and e - s > 1.0:
            # a gap half inside the leaf and half after its tick's leaves:
            # on both sides of the half that makes it named
            gaps.append((e - 1.0, e + 0.999))
            gaps.append((e - 1.0, e + 1.001))
            gaps.append((e - 1.0, e + 1.0))
    t = 0.0
    while t < end:  # the device's gaps: short, disjoint, in order
        t += rng.uniform(0.01, 4.0)
        w = rng.choice([0.001, 0.05, 0.5, 30.0])
        gaps.append((t, t + w))
        t += w
    return sorted(gaps[:5 + 3 * 40]) + gaps[5 + 3 * 40:], main


@pytest.mark.parametrize("seed", range(8))
def test_sweep_names_every_gap_as_the_loop_does(seed):
    rng = random.Random(seed)
    gaps, main = hard_cases(rng)
    spans = [(n, s, e) for n, s, e, _ in main]
    assert X.attribute_gaps(gaps, spans) == [O.attribute_gap(g, spans)
                                             for g in gaps]
    got, want = P.attribute(gaps, main), O.attribute(gaps, main)
    assert got == want
    assert {named for _, _, named in got} == {True, False}
    window = (0.0, max(e for _, _, e, _ in main))
    assert P.reduce_spans(window, main, [], gaps) == O.reduce_spans(
        window, main, [], gaps)


@pytest.mark.parametrize("seed", range(4))
def test_sweep_holds_without_nesting_or_order(seed):
    """Spans of several threads need not nest, and ``attribute`` is handed
    gaps in any order, overlapping ones too."""
    rng = random.Random(100 + seed)
    spans = []
    for k in range(300):
        s = rng.uniform(0, 1000)
        spans.append((f"s{k % 7}", s, s + rng.choice([0.0, 1.0, 5.0, 400.0])))
    gaps = []
    for _ in range(500):
        s = rng.uniform(-10, 1010)
        gaps.append((s, s + rng.choice([0.0, 0.5, 5.0, 100.0])))
    assert X.attribute_gaps(gaps, spans) == [O.attribute_gap(g, spans)
                                             for g in gaps]
    main = [(n if k % 5 else "serve_tick", s, e, {})
            for k, (n, s, e) in enumerate(spans)]
    assert P.attribute(gaps, main) == O.attribute(gaps, main)


def test_the_twins_go_to_the_first_in_list_order():
    spans = [("outer", 0.0, 100.0), ("twin_b", 10.0, 20.0),
             ("twin_a", 10.0, 20.0)]
    assert X.attribute_gaps([(12.0, 14.0)], spans) == ["twin_b"]
    assert X.attribute_gaps([(12.0, 14.0)], spans[::-1]) == ["twin_a"]


# ---------------------------------------------------------------- the cost
def test_cost_follows_gaps_plus_spans_not_their_product():
    """200,000 gaps against 1,500 spans (300 ticks of one enclosing span and
    four leaves): the loop looked at 1,500 spans a gap, three times over (the
    device's attribution, the leaf cover's clip, the leaves'); the sweep
    stays under 20 a gap in each."""
    rng = random.Random(7)
    main = [sp for sp in nested_spans(rng, 300) if sp[0] != "slot_write"]
    assert len(main) == 1500
    end = max(e for _, _, e, _ in main)
    n = 200_000
    step = end / n
    gaps = [(k * step, k * step + step / 2) for k in range(n)]
    names, compared = X._sweep(gaps, [(n_, s, e) for n_, s, e, _ in main])
    assert len(names) == n and compared < 20 * n
    out, compared = P._attribute(gaps, main)
    assert len(out) == n and compared < 20 * n
