"""Housekeeping pins for ISSUE 14 (prefix cache + chunked prefill +
prefix-aware routing): flag/docs wiring, exports, scheduler clock
stamps, config defaults, and zero-overhead absence of the new telemetry
block — the small contracts the main suite (test_prefix_cache.py) does
not re-pin."""
import os

import numpy as np

_REPO = os.path.join(os.path.dirname(__file__), "..")


def _read(relpath):
    with open(os.path.join(_REPO, relpath)) as f:
        return f.read()


def test_docs_wiring():
    """The serving.md section exists and decode_perf.md / fleet.md /
    static_analysis.md cross-link/describe the new machinery."""
    serving = _read("docs/serving.md")
    assert "Prefix cache & chunked prefill" in serving
    assert "copy-on-write" in serving and "radix" in serving.lower()
    assert "--prefill-chunk-tokens" in serving
    assert "prefix" in _read("docs/decode_perf.md").lower()
    fleet = _read("docs/fleet.md")
    assert "affinity" in fleet and "prefix" in fleet.lower()
    assert "--prefill-chunk-tokens" in _read("docs/static_analysis.md")
    api = _read("docs/python_api.md")
    for flag in ("--prefix-cache", "--prefill-chunk-tokens",
                 "--prefix-cache-blocks"):
        assert flag in api, f"{flag} undocumented"


def test_serving_exports():
    from flexflow_tpu.serving import (BlockAccountingError,  # noqa: F401
                                      PrefixCache, PrefixNode)
    from flexflow_tpu.serving.prefix import _lcp

    assert _lcp((1, 2, 3), (1, 2, 9)) == 2
    assert issubclass(BlockAccountingError, RuntimeError)


def test_config_defaults_and_parse():
    from flexflow_tpu import FFConfig

    cfg = FFConfig()
    assert cfg.prefix_cache == "on"
    assert cfg.prefill_chunk_tokens == 0
    assert cfg.prefix_cache_blocks == 0
    cfg.parse_args(["--prefill-chunk-tokens", "0"])  # explicit off OK
    assert cfg.prefill_chunk_tokens == 0


def test_finish_ms_stamped_on_every_terminal_path():
    """Request-completion latency (finish_ms - submit_ms) is read off the
    request after any outcome — every terminal path must stamp it."""
    from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                                Request)

    t = [0.0]
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8, max_len=32,
                                     clock=lambda: t[0])
    a = Request(prompt=np.zeros(3, np.int32), max_new_tokens=1)
    b = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4)
    c = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4)
    for r in (a, b, c):
        sched.submit(r)
    sched.next_action()  # a -> slot 0
    t[0] = 5.0
    sched.commit_token(0, 7)  # finishes (length 1)
    assert a.finish_ms == 5.0
    sched.next_action()  # b -> a slot
    t[0] = 9.0
    slot_b = sched.slots.index(b)
    sched.evict(slot_b, "deadline_exceeded")
    assert b.finish_ms == 9.0
    t[0] = 11.0
    sched.drop_queued(c, "deadline_exceeded")
    assert c.finish_ms == 11.0


def test_prefix_block_absent_without_activity():
    """Zero-overhead absence: a telemetry record with no prefix/chunk
    activity has NO serving_prefix block."""
    from flexflow_tpu.obs.telemetry import StepTelemetry

    tel = StepTelemetry(batch_size=1, phase="serving")
    tel.finalize()
    assert "serving_prefix" not in tel.summary()
    tel.serving_prefix_tokens_reused = 10
    tel.serving_prefill_tokens_computed = 30
    tel.finalize()
    blk = tel.summary()["serving_prefix"]
    assert blk["reuse_rate"] == 0.25
