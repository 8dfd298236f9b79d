"""Round-7 satellite regressions (ISSUE 3):

* ``prefetch_iterator`` propagates producer errors and joins its thread on
  early consumer exit (previously the daemon thread could outlive the
  generator, pinning in-flight device batches).
* ``scripts/trace_summary.py`` prints the searched plan (mesh / pipeline /
  remat level) from a SearchLog.
"""
import json
import threading
import time

import numpy as np
import pytest

from flexflow_tpu.data.dataloader import prefetch_iterator


def _wait_threads_back_to(baseline: int, timeout: float = 5.0) -> bool:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if threading.active_count() <= baseline:
            return True
        time.sleep(0.05)
    return False


# ------------------------------------------------------- prefetch_iterator
def test_prefetch_propagates_producer_error_and_joins():
    class Boom(RuntimeError):
        pass

    def source():
        yield [np.zeros((2, 2))]
        raise Boom("dataset broke mid-epoch")

    baseline = threading.active_count()
    it = prefetch_iterator(source(), [None])
    got = next(it)
    assert got[0].shape == (2, 2)
    with pytest.raises(Boom, match="dataset broke"):
        next(it)
    # the producer thread must not linger after the error surfaced
    assert _wait_threads_back_to(baseline), "producer thread leaked"


def test_prefetch_early_consumer_exit_joins_producer():
    produced = []

    def source():
        for i in range(1000):
            produced.append(i)
            yield [np.full((2, 2), i)]

    baseline = threading.active_count()
    it = prefetch_iterator(source(), [None], depth=2)
    first = next(it)
    assert first[0][0, 0] == 0
    it.close()  # abandon mid-stream (fit breaking out on a recompile)
    assert _wait_threads_back_to(baseline), \
        "producer thread not joined on generator close"
    # bounded lookahead: the producer stopped near the consumed position
    # instead of draining the whole source
    assert len(produced) < 50, len(produced)


def test_prefetch_normal_exhaustion_still_works():
    def source():
        for i in range(5):
            yield [np.full((1,), i)]

    baseline = threading.active_count()
    out = [b[0][0] for b in prefetch_iterator(source(), [None])]
    assert out == [0, 1, 2, 3, 4]
    assert _wait_threads_back_to(baseline)


# --------------------------------------------------------- trace_summary
def test_trace_summary_prints_searched_remat_plan(tmp_path, capsys):
    import sys

    sys.path.insert(0, "/root/repo/scripts")
    import trace_summary

    log = tmp_path / "search.jsonl"
    records = [
        {"event": "candidate", "cost_ms": 5.0, "accepted": True,
         "best_ms": 5.0, "remat": "none"},
        {"event": "candidate", "cost_ms": 4.2, "accepted": True,
         "best_ms": 4.2, "remat": "selective"},
        {"event": "result", "cost_ms": 4.2, "mesh": [8, 1],
         "remat": "selective", "pipeline": None, "search_wall_s": 1.0,
         "candidates": 2, "candidates_per_s": 2.0,
         "cost_cache_hit_rate": 0.9},
    ]
    log.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    trace_summary.main([str(log)])
    out = capsys.readouterr().out
    assert "searched plan:" in out
    assert "remat=selective" in out
    assert "mesh=(8, 1)" in out
