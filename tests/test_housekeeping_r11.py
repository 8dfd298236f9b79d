"""Round-11 housekeeping (ISSUE 9 satellites): the bounded ServingStats
reservoir, the ServingRejection hierarchy, the new serving-resilience
flags' parse-time validation, the telemetry serving_resilience block +
trace_summary digest, and the docs wiring."""
import os
import subprocess
import sys

import pytest

from flexflow_tpu import FFConfig
from flexflow_tpu.obs.telemetry import StepTelemetry
from flexflow_tpu.serving import (OverloadError, QueueFullError,
                                  ServingRejection, ServingStats)
from flexflow_tpu.serving.engine import TOKEN_WALL_WINDOW

_REPO = os.path.join(os.path.dirname(__file__), "..")


# ----------------------------------------------------------- stats reservoir
def test_serving_stats_token_walls_bounded():
    """The old list grew one float per token forever; the reservoir is a
    ring of TOKEN_WALL_WINDOW walls with identical summary fields."""
    st = ServingStats()
    for i in range(TOKEN_WALL_WINDOW + 500):
        st.record_token(1e-3 * (i % 7 + 1))
        st.tokens_generated += 1
    assert len(st.token_walls_s) == TOKEN_WALL_WINDOW
    assert st.token_walls_s.maxlen == TOKEN_WALL_WINDOW
    st.wall_s = 1.0
    out = st.summary()
    # same keys the unbounded version produced
    for k in ("requests_served", "tokens_generated", "prefills",
              "decode_steps", "queue_depth_hwm", "wall_s", "tokens_per_s",
              "p50_token_ms", "p99_token_ms"):
        assert k in out, f"summary lost field {k}"
    assert out["p99_token_ms"] >= out["p50_token_ms"] > 0


def test_serving_stats_resilience_fields_appear_only_when_nonzero():
    st = ServingStats()
    st.wall_s = 1.0
    assert "outcomes" not in st.summary()
    assert "sheds" not in st.summary()
    st.count_outcome("ok", 2)
    st.count_outcome("shed", 0)  # zero-count never creates a key
    st.sheds = 3
    out = st.summary()
    assert out["outcomes"] == {"ok": 2}
    assert out["sheds"] == 3


# --------------------------------------------------------- rejection family
def test_rejection_hierarchy_and_fields():
    assert issubclass(QueueFullError, ServingRejection)
    assert issubclass(OverloadError, ServingRejection)
    e = OverloadError("x", queued=3, active=2, retry_after_ms=12.5)
    assert (e.queued, e.active, e.retry_after_ms) == (3, 2, 12.5)
    # defaults: constructible with a bare message (error paths must never
    # themselves raise on a missing field)
    q = QueueFullError("full")
    assert q.queued == 0 and q.retry_after_ms == 0.0


# ----------------------------------------------------------------- flags
def test_serving_resilience_flags_parse_and_validate():
    c = FFConfig()
    c.parse_args(["--request-timeout-ms", "250", "--shed-policy",
                  "deadline", "--drain-grace-s", "2.5",
                  "--decode-retry-budget", "2"])
    assert c.request_timeout_ms == 250.0
    assert c.shed_policy == "deadline"
    assert c.drain_grace_s == 2.5
    assert c.decode_retry_budget == 2
    with pytest.raises(ValueError, match="shed-policy"):
        FFConfig().parse_args(["--shed-policy", "sometimes"])
    with pytest.raises(ValueError, match="request-timeout-ms"):
        FFConfig().parse_args(["--request-timeout-ms", "-5"])
    with pytest.raises(ValueError, match="drain-grace-s"):
        FFConfig().parse_args(["--drain-grace-s", "-1"])
    with pytest.raises(ValueError, match="decode-retry-budget"):
        FFConfig().parse_args(["--decode-retry-budget", "-1"])
    # 0 is a meaningful value for all three numerics
    c2 = FFConfig()
    c2.parse_args(["--request-timeout-ms", "0", "--drain-grace-s", "0",
                   "--decode-retry-budget", "0"])
    assert c2.request_timeout_ms == 0.0 and c2.decode_retry_budget == 0


def test_new_flags_documented():
    with open(os.path.join(_REPO, "docs", "python_api.md")) as f:
        doc = f.read()
    for flag in ("--request-timeout-ms", "--shed-policy",
                 "--drain-grace-s", "--decode-retry-budget"):
        assert flag in doc, f"{flag} undocumented in python_api.md"


# -------------------------------------------------------------- telemetry
def test_telemetry_serving_resilience_block_and_digest(tmp_path, capsys):
    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    import trace_summary

    tel = StepTelemetry(batch_size=4, phase="serving")
    tel.requests_served = 9
    tel.tokens_generated = 40
    tel.serving_outcomes = {"ok": 6, "shed": 2, "deadline_exceeded": 1}
    tel.serving_sheds = 2
    tel.serving_deadline_misses = 1
    tel.serving_quarantines = 3
    tel.serving_drains = 1
    tel.serving_replans = 1
    tel.finalize()
    blk = tel.summary()["serving_resilience"]
    assert blk["outcomes"] == {"ok": 6, "shed": 2, "deadline_exceeded": 1}
    assert blk["shed_rate"] == pytest.approx(2 / 9, abs=1e-4)
    assert blk["deadline_miss_rate"] == pytest.approx(1 / 9, abs=1e-4)
    assert blk["quarantines"] == 3 and blk["drains"] == 1
    f = tmp_path / "tel.json"
    tel.write(str(f))
    trace_summary.main([str(f)])
    out = capsys.readouterr().out
    assert "serving resilience: ok=6 deadline_exceeded=1 shed=2" in out
    assert "quarantines: 3" in out and "drains: 1" in out
    assert "replans: 1" in out


def test_telemetry_block_absent_for_clean_runs():
    tel = StepTelemetry(phase="serving")
    tel.requests_served = 2
    tel.tokens_generated = 8
    tel.finalize()
    assert "serving_resilience" not in tel.summary()
    assert "serving" in tel.summary()


# -------------------------------------------------------------------- docs
def test_docs_wiring():
    with open(os.path.join(_REPO, "docs", "serving.md")) as f:
        serving_md = f.read()
    assert "Serving under failure" in serving_md
    for outcome in ("deadline_exceeded", "decode_fault", "preempted"):
        assert outcome in serving_md
    with open(os.path.join(_REPO, "docs", "fault_tolerance.md")) as f:
        ft_md = f.read()
    assert "poison_decode_at" in ft_md and "serving.md" in ft_md


def test_check_docs_flags_green():
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts",
                                      "check_docs_flags.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
