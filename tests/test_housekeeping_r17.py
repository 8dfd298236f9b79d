"""Round-17 housekeeping (ISSUE 17 satellites):

* `--serve-loop` flag: parse-time validation, preflight validation of
  programmatic assignment, documented in python_api.md
  (check_docs_flags stays green).
* host-overhead math with the ISSUE 17 overlap bucket: overlapped host
  work widens the DENOMINATOR only; with no overlap the r16 fraction
  is unchanged.
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


def _read(name):
    with open(os.path.join(REPO, name)) as f:
        return f.read()


# ------------------------------------------------------------------ flag
def test_serve_loop_flag_parse_and_preflight():
    from flexflow_tpu import FFConfig
    from flexflow_tpu.resilience.preflight import (PreflightError,
                                                   preflight_config)

    cfg = FFConfig()
    assert cfg.serve_loop == "sync"  # default stays the reference loop
    cfg.parse_args(["--serve-loop", "async"])
    assert cfg.serve_loop == "async"
    with pytest.raises(ValueError, match="sync\\|async"):
        FFConfig().parse_args(["--serve-loop", "turbo"])
    bad = FFConfig()
    bad.serve_loop = "bogus"  # programmatic assignment: preflight's job
    with pytest.raises(PreflightError, match="serve-loop"):
        preflight_config(bad)
    preflight_config(FFConfig())


def test_serve_loop_flag_documented():
    import check_docs_flags

    assert check_docs_flags.main([]) == 0
    assert "--serve-loop" in _read("docs/python_api.md")


# ------------------------------------------------------------- accounting
def test_host_overhead_fraction_overlap_math():
    """Overlap widens the denominator only; zero overlap reproduces the
    r16 fraction exactly (test_housekeeping_r16 pins that case)."""
    from flexflow_tpu.serving.engine import ServingStats
    from flexflow_tpu.serving.fleet import FleetStats

    st = ServingStats()
    st.host_dispatch_s = 1.0
    st.host_device_s = 5.0
    st.host_bookkeep_s = 1.0
    st.host_overlap_s = 1.0
    assert st.host_overhead_fraction() == 0.25
    st.host_overlap_s = 0.0
    assert st.host_overhead_fraction() == pytest.approx(2.0 / 7.0)
    fs = FleetStats(replicas=1, dispatches=[0])
    fs.host_dispatch_s = 2.0
    fs.host_device_s = 4.0
    fs.host_overlap_s = 2.0
    assert fs.host_overhead_fraction() == 0.25
    # host_syncs surfaces in both summaries only when nonzero
    assert "host_syncs" not in fs.summary()
    fs.host_syncs = 3
    assert fs.summary()["host_syncs"] == 3
    st.host_syncs = 0
    assert "host_syncs" not in st.summary()


def test_fleet_retires_overlap_and_syncs_across_loop_rebuilds():
    """A drain/rejoin rebuild must not lose the retired loop's overlap
    wall or sync count (the 4-element retired_host contract)."""
    from flexflow_tpu.serving.fleet import FleetReplica

    rep = FleetReplica(0, engine=None)
    assert rep.retired_host == [0.0, 0.0, 0.0, 0.0]
    assert rep.retired_syncs == 0
