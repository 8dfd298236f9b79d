"""The ``trinity-mini`` cell's files at the rehearsal size (``trinity-tiny``,
CPU): the sound tree passes through the unedited train driver, and the
controls of ``controls_trinity.py`` — one fault each, planted in the program
— are judged by the unedited ``compare``."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.tests.test_rehearsal import CELLS, ROOT, result, run


def control(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_trace",
                                                    "rehearsal_cache")
    proc = subprocess.run(
        [sys.executable, "benchmark/tests/controls_trinity.py", name,
         "--workload", "trinity-tiny-train", "--seed", str(2**31 + 11),
         "--seconds", "0.3", "--trace", "0", "--cells", CELLS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert f"[bench] control {name} planted" in proc.stdout
    compared = {ln.split()[2]: float(ln.split(": ")[1].split()[0])
                for ln in proc.stdout.splitlines()
                if ln.startswith("[bench] compared ")}
    return json.loads(proc.stdout.strip().splitlines()[-1]), compared


def test_the_sound_tree_passes_and_reports_the_expert_layers_counters():
    proc = run("trinity-tiny-train", 1)
    line = result(proc)
    assert {"check_grad_rel_err_max", "check_loss_rel_err",
            "expert_load_max_over_mean", "loss_after_32_steps",
            "sim_vs_measured"} <= set(line["metrics"])
    assert 1.0 <= line["metrics"]["expert_load_max_over_mean"]["value"] < 4.0
    # the compared groups are the stated ones, the routed weights not
    assert "grad_rel_err l0_mlp" in proc.stdout
    assert "grad_rel_err l2_moeshared" in proc.stdout
    assert "moeexperts" not in proc.stdout and "moerouter" not in proc.stdout


@pytest.mark.parametrize("name", ["a", "b", "c", "d", "e"])
def test_a_planted_fault_is_not_correct(name):
    """Read at this size (CPU, PR 35): sound 0.038; a 1.14, b 1.24, c 1.45,
    d 0.24 (the last expert layer's ``norm4`` gain; 0.19 before the gains
    next to the routed part were compared), e 0.53, against the limit 0.08.
    d is the closest: with the routed weights' own gradients left out of the
    comparison it shows only in the cotangents that pass the routed layers
    (PERF.md sections 6 and 7)."""
    line, compared = control(name)
    assert line["correct"] is False
    assert compared["check_grad_rel_err_max"] > 0.08
