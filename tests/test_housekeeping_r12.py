"""Round-12 housekeeping (ISSUE 11 satellites): the fleet flags'
parse-time validation and documentation, the telemetry ``fleet`` block's
presence/absence semantics, the circuit-breaker unit laws, and the
docs wiring."""
import os
import sys

import pytest

from flexflow_tpu import FFConfig
from flexflow_tpu.obs.telemetry import StepTelemetry

_REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _REPO)


# ----------------------------------------------------------------- flags
def test_fleet_flags_parse_and_validate():
    c = FFConfig()
    c.parse_args(["--fleet-replicas", "3", "--hedge-after-pctl", "95",
                  "--health-probe-every", "8",
                  "--circuit-open-after", "2"])
    assert c.fleet_replicas == 3
    assert c.hedge_after_pctl == 95.0
    assert c.health_probe_every == 8
    assert c.circuit_open_after == 2
    with pytest.raises(ValueError, match="fleet-replicas"):
        FFConfig().parse_args(["--fleet-replicas", "-1"])
    with pytest.raises(ValueError, match="hedge-after-pctl"):
        FFConfig().parse_args(["--hedge-after-pctl", "-5"])
    with pytest.raises(ValueError, match="health-probe-every"):
        FFConfig().parse_args(["--health-probe-every", "-1"])
    with pytest.raises(ValueError, match="circuit-open-after"):
        FFConfig().parse_args(["--circuit-open-after", "0"])
    # 0 is meaningful where documented
    c2 = FFConfig()
    c2.parse_args(["--fleet-replicas", "0", "--hedge-after-pctl", "0",
                   "--health-probe-every", "0"])
    assert c2.fleet_replicas == 0 and c2.health_probe_every == 0


# ------------------------------------------------------------- telemetry
def test_telemetry_fleet_block_present_and_absent():
    tel = StepTelemetry(batch_size=4, phase="fleet")
    tel.fleet_replicas = 2
    tel.fleet_requests = 9
    tel.fleet_outcomes = {"ok": 8, "shed": 1}
    tel.fleet_sheds = 1
    tel.fleet_dispatches = [5, 4]
    tel.fleet_migrations = 2
    tel.fleet_failovers = 1
    tel.finalize()
    blk = tel.summary()["fleet"]
    assert blk["outcomes"] == {"ok": 8, "shed": 1}
    assert blk["shed_rate"] == pytest.approx(1 / 9, abs=1e-4)
    assert blk["dispatches"] == [5, 4]
    # no fleet activity -> no block (zero-noise for plain serving runs)
    clean = StepTelemetry(phase="serving")
    clean.requests_served = 2
    clean.tokens_generated = 4
    clean.finalize()
    assert "fleet" not in clean.summary()


# ------------------------------------------------------- circuit breaker
def test_circuit_breaker_laws():
    """closed -> open at the threshold, bounded-linear backoff growth,
    half-open failure reopens LONGER, success resets fully, and opens
    with no scheduled probe (kill/drain) never self-probe."""
    from flexflow_tpu.serving import CircuitBreaker

    cb = CircuitBreaker(open_after=3, backoff_ticks=4,
                        max_backoff_ticks=10)
    cb.record_failure(0)
    cb.record_failure(1)
    assert cb.state == "closed"
    cb.record_failure(2)
    assert cb.state == "open" and cb.half_open_at == 2 + 4
    assert not cb.ready_to_probe(5) and cb.ready_to_probe(6)
    # failures while open are ignored (no probe-point pushback)
    cb.record_failure(3)
    assert cb.half_open_at == 6
    cb.half_open()
    cb.record_failure(7)  # half-open failure -> reopen, longer backoff
    assert cb.state == "open" and cb.opens == 2
    assert cb.half_open_at == 7 + 8
    cb.half_open()
    cb.record_success()
    assert cb.state == "closed" and cb.failures == 0
    # backoff is CAPPED
    for t in range(20, 26):
        cb.record_failure(t)
    assert cb.state == "open"
    assert cb.half_open_at - 22 <= 10
    # a held-open circuit (kill/drain) never schedules its own probe
    cb.force_open(half_open_at=None)
    assert not cb.ready_to_probe(10 ** 9)


# ------------------------------------------------------------------ docs
def test_docs_wiring():
    with open(os.path.join(_REPO, "docs", "fleet.md")) as f:
        fleet_md = f.read()
    for needle in ("health state machine", "circuit breaker",
                   "hedged retries", "request migration",
                   "kill_replica_at", "rejoin_at",
                   "FLEET_MIN_RETRY_AFTER_MS"):
        assert needle.lower() in fleet_md.lower(), f"fleet.md lost {needle}"
    with open(os.path.join(_REPO, "docs", "index.md")) as f:
        assert "fleet.md" in f.read()
    with open(os.path.join(_REPO, "docs", "serving.md")) as f:
        assert "fleet.md" in f.read()
    with open(os.path.join(_REPO, "README.md")) as f:
        assert "docs/fleet.md" in f.read()


def test_mypy_typed_core_covers_fleet():
    """The [tool.mypy] typed core lists the whole serving/ package —
    fleet.py rides the existing gate (test_housekeeping_r9 runs mypy
    when available); pin that the package entry is still there and the
    module imports cleanly."""
    with open(os.path.join(_REPO, "pyproject.toml")) as f:
        assert "flexflow_tpu/serving" in f.read()
    import flexflow_tpu.serving.fleet  # noqa: F401
