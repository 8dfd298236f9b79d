"""The serving cell's arithmetic on a fake clock: TTFT counts from the due
time, a failed request is +inf in the percentile, buckets are the ones the
mix can hit."""
import importlib.util
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def serve():
    spec = importlib.util.spec_from_file_location(
        "bench_driver_serve", os.path.join(BENCH, "drivers", "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_driver_serve"] = mod
    spec.loader.exec_module(mod)
    return mod


def req(first=0.0, n=0, outcome="ok"):
    return types.SimpleNamespace(first_token_ms=first, generated=[0] * n,
                                 outcome=outcome)


LIMITS = {"ttft_ms": 2000.0, "token_gap_ms": 2000.0}


def test_ttft_counts_from_due_not_from_submit(serve):
    # due at 1000 ms; the loop submitted it at 1040 (a tick was running);
    # the first token came at 1100: TTFT is 100, not 60
    ttft, tpot, failed = serve.latencies(
        [(req(1100.0, 5), 1000.0, False, 1500.0, 150.0)], LIMITS, 9000.0)
    assert ttft == [100.0] and tpot == [100.0] and failed == 0


def test_failed_request_is_infinite(serve):
    rows = [(req(1100.0, 5), 1000.0, False, 1500.0, 120.0),
            (req(0.0, 0, outcome=None), 1000.0, False, None, None),  # no first token
            (req(1200.0, 3, outcome=None), 1000.0, False, 1600.0, 210.0),  # still decoding: fine
            (req(1300.0, 2, outcome="deadline_exceeded"), 1000.0, False, 1400.0, 100.0),
            (req(1250.0, 4), 1000.0, True, None, None)]  # refused at the door
    ttft, tpot, failed = serve.latencies(rows, LIMITS, 1700.0)
    assert failed == 3 and tpot == [100.0, 200.0]
    assert ttft[1] == float("inf") and ttft[4] == float("inf")
    assert serve.percentile(ttft, 50) == 300.0
    assert serve.percentile(ttft, 90) == float("inf")
    assert serve.finite_or_cap(serve.percentile(ttft, 90)) == 1e9


@pytest.mark.parametrize("row, judged_until, fails", [
    # a first token 2.5 s after the due time: over the limit
    ((req(3500.0, 5), 1000.0, False, 4000.0, 125.0), 9000.0, True),
    # the same, but it came after the judging stopped 1.5 s after the due
    # time (a traced run's close): what it waited then is the profiler's
    ((req(3500.0, 5), 1000.0, False, 4000.0, 125.0), 2500.0, False),
    # ... unless it had already waited over the limit by then
    ((req(4500.0, 5), 1000.0, False, 5000.0, 125.0), 3100.0, True),
    # one gap between two tokens over the limit: a stream that stalled
    ((req(1100.0, 5), 1000.0, False, 4000.0, 2400.0), 9000.0, True),
    # unfinished, newest token 3 s old when the run ends: stalled for good
    ((req(1100.0, 5, outcome=None), 1000.0, False, 1500.0, 100.0), 4500.0, True),
    # unfinished, newest token fresh: still decoding, fine
    ((req(1100.0, 5, outcome=None), 1000.0, False, 4400.0, 100.0), 4500.0, False),
])
def test_limits_on_first_token_and_on_gaps(serve, row, judged_until, fails):
    _, _, failed = serve.latencies([row], LIMITS, judged_until)
    assert failed == int(fails)


def test_percentile_is_the_nearest_rank(serve):
    v = list(range(1, 201))                    # 200 requests
    assert serve.percentile(v, 90) == 180      # 20 samples lie beyond it
    assert serve.percentile(v, 50) == 100
    assert serve.percentile([], 90) == float("inf")


def test_buckets_the_mix_can_hit(serve):
    assert serve.buckets_hit((64, 128, 256, 512, 768), 16, 768) == \
        [64, 128, 256, 512, 768]
    assert serve.buckets_hit((16, 32, 64, 128, 256, 512, 1024), 16, 768) == \
        [16, 32, 64, 128, 256, 512, 1024]
    assert serve.buckets_hit((64, 128, 256, 512, 768), 200, 300) == [256, 512]
