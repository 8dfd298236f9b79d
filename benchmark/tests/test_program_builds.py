"""The per-layer metrics that move ``setup_s`` (PR 39): seven readers over the
program's own record of what it built (``flexflow_tpu.obs.builds()`` /
``setup_walls()``), on a rehearsal cell's traced run and on a program that
keeps no such record."""
import importlib.util
import os

import pytest

from benchmark.reduce import program_builds as B
from benchmark.tests.test_rehearsal import result, run

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTS = ["step_program_builds", "programs_built", "programs_cache_missed"]
SECONDS = ["program_trace_lower_s", "program_load_s", "program_compile_s",
           "setup_host_s"]
NEW = COUNTS + SECONDS


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "..", "layer_metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert (mod.NAME, mod.LAYER, mod.MOVES) == (name, "entry points",
                                                "setup_s")
    assert mod.CELLS == ["*"]
    return mod


@pytest.mark.parametrize("workload,step_builds", [("bert-tiny", 2),
                                                  ("gpt2-tiny-chat", 1)])
def test_a_traced_rehearsal_run_prints_the_seven(workload, step_builds):
    line = result(run(workload, 1))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), set(NEW) - set(got)
    # the train step is built twice until its two signatures are one
    # (ROADMAP.md S12); the decode step once
    assert got["step_program_builds"] == step_builds
    assert got["programs_built"] >= got["programs_cache_missed"] >= 0
    assert got["programs_built"] > got["step_program_builds"]
    assert all(got[k] >= 0 for k in SECONDS)
    assert got["program_trace_lower_s"] > 0
    # what was not loaded was compiled, and the other way round
    if got["programs_cache_missed"] == 0:
        assert got["program_compile_s"] == 0 and got["program_load_s"] > 0
    if got["programs_cache_missed"] == got["programs_built"]:
        assert got["program_load_s"] == 0 and got["program_compile_s"] > 0
    # the four together are the builder's share of the benchmark's own wall
    # around compile() and the first calls
    together = sum(got[k] for k in SECONDS)
    assert 0 < together <= got["compile_s"] * 1.05


def test_only_the_programs_own_builds_count(monkeypatch):
    import jax
    import numpy as np

    from flexflow_tpu import obs

    ones = np.ones(4, np.float32)
    before = B.own()
    jax.jit(lambda v: v * 5 - 1)(ones)  # no span, no entry point: the caller's
    assert len(B.own()) == len(before)
    walls0 = B.setup_host_s()
    with obs.setup_span("engine_build"):
        jax.jit(lambda v: v * 7 - 1)(ones)
    after = B.own()
    assert len(after) == len(before) + 1
    assert after[-1].phase == "engine_build"
    run_facts = {"step_module": after[-1].name}
    assert reader("step_program_builds").read(run_facts) >= 1
    # the span held little but that build: its seconds are taken off the wall
    assert 0 <= B.setup_host_s() - walls0 < 0.05


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_registry_reports_nothing(monkeypatch, name):
    """The parent commit has no ``flexflow_tpu.obs.builds``: every reader
    gives None and raises nothing."""
    import flexflow_tpu.obs as obs

    monkeypatch.delattr(obs, "builds")
    assert reader(name).read({"step_module": "jit_step"}) is None


@pytest.mark.parametrize("name", NEW)
def test_an_obs_that_does_not_import_reports_nothing(monkeypatch, name):
    import sys

    monkeypatch.setitem(sys.modules, "flexflow_tpu.obs", None)
    assert reader(name).read({"step_module": "jit_step"}) is None


def test_a_registry_that_breaks_costs_no_result(monkeypatch, capsys):
    import flexflow_tpu.obs as obs

    def broken():
        raise RuntimeError("no records today")

    monkeypatch.setattr(obs, "builds", broken)
    assert reader("programs_built").read({}) is None
    monkeypatch.undo()
    monkeypatch.setattr(obs, "setup_walls", broken)
    assert reader("setup_host_s").read({}) is None
    assert reader("programs_built").read({}) is not None
    assert "nothing read" in capsys.readouterr().out
