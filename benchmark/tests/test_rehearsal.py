"""run.py end to end on the CPU at the rehearsal size (cells under
tests/cells, platform "cpu" by their own files), one process per run as the
driver makes them; and the refusal to measure a real cell without a TPU."""
import functools
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = os.path.join("benchmark", "tests", "cells")


@functools.lru_cache(maxsize=None)  # tests that read one run share it
def run(workload, trace, cells=CELLS, devices=1, seconds="1.5", seed=3):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the rehearsal leaves no compile cache behind in the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_trace",
                                                    "rehearsal_cache")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", seconds, "--trace", str(trace), "--cells", cells],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def result(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "[bench] platform: cpu" in proc.stdout
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    return line


@pytest.mark.parametrize("trace", [0, 1])
def test_train_cell(trace):
    line = result(run("bert-tiny", trace))
    if trace:
        assert {"compile_s", "loss_after_32_steps"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_a_second_training_configuration_enters_as_files_only():
    """GPT-2 through the train driver: token ids in, token-level labels,
    other node names, no metric the program can compute — its own config,
    reference, generator, mix and builder under tests/, nothing edited."""
    proc = run("gpt2-tiny-train", 0)
    line = result(proc)
    assert "grad_rel_err h0_attn" in proc.stdout and "h1_fc2" in proc.stdout
    assert "check grads_match_reference: True" in proc.stdout
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def test_four_device_cell():
    line = result(run("bert-tiny-4dev", 0, devices=4))
    assert line["device"]["count"] == 4


def test_four_device_cell_with_the_search():
    """The four-chip cell's own flags: search_s and the simulated step come
    from the program's search log."""
    line = result(run("bert-tiny-4dev-searched", 1, devices=4))
    assert {"search_s", "sim_vs_measured"} <= set(line["metrics"])


def test_an_empty_checked_set_stops_the_run(tmp_path):
    """A reference that names no parameter group does not pass quietly."""
    cells = tmp_path / "cells"
    shutil.copytree(os.path.join(ROOT, CELLS), cells)
    ref = cells / "reference" / "bert-large-proxy.py"
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "bert-large-proxy.py")) as f:
        text = f.read()
    ref.write_text(text + "\n\ndef checked_params(params, config):\n"
                   "    return []\n")
    proc = run("bert-tiny", 0, cells=str(cells))
    assert proc.returncode != 0
    assert "nothing to compare gradients on" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell(trace):
    line = result(run("gpt2-tiny-chat", trace, seconds="2"))
    if trace:
        assert {"batch_occupancy", "host_overhead_share", "queue_p90_ms",
                "tpot_p99_ms", "generator_lag_p99_ms",
                "window_tokens_per_s", "ttft_p90_ms"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}


def test_a_real_cell_refuses_the_cpu():
    proc = run("bert-large-s512", 0, cells="benchmark")
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr and "{" not in proc.stdout.strip()[-1:]


def test_wrong_chip_count_is_refused():
    proc = run("bert-tiny-4dev", 0, devices=2)
    assert proc.returncode != 0 and "asks for 4 chip" in proc.stderr


SERVING_READERS = {"batch_occupancy", "host_overhead_share", "queue_p90_ms",
                   "tpot_p99_ms", "generator_lag_p99_ms",
                   "window_tokens_per_s", "ttft_p90_ms", "prefill_tick_share",
                   "decode_tick_ms_p50", "check_logit_gap_max", "compile_s"}


def logged(proc, key):
    return float(re.search(rf"^\[bench\] {key}: ([0-9.]+)$", proc.stdout,
                           re.M).group(1))


def test_trace_steps_close_the_traced_window(tmp_path):
    """A cell whose file gives ``trace_steps`` traces that many decode steps
    and no more (or ``trace_seconds``, whichever comes first), and still
    reports every serving reader; without the key the window is the
    ``trace_seconds`` it was."""
    plain = run("gpt2-tiny-chat", 1, seconds="2")
    assert SERVING_READERS <= set(result(plain)["metrics"])
    assert logged(plain, "window_decode_steps") > 30
    assert logged(plain, "measured_window_s") == pytest.approx(1.0, abs=0.1)

    cells = tmp_path / "cells"
    shutil.copytree(os.path.join(ROOT, CELLS), cells)
    path = cells / "workloads" / "gpt2-tiny-chat.json"
    cell = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cell, trace_steps=8)))
    proc = run("gpt2-tiny-chat", 1, cells=str(cells), seconds="2")
    assert SERVING_READERS <= set(result(proc)["metrics"])
    assert 8 <= logged(proc, "window_decode_steps") <= 9
    assert logged(proc, "measured_window_s") < 0.5 * cell["trace_seconds"]
    # the untraced run does not read the key
    untraced = run("gpt2-tiny-chat", 0, cells=str(cells), seconds="2")
    assert logged(untraced, "measured_window_s") == pytest.approx(2.0, abs=0.1)
    assert result(untraced)["metrics"].keys() == {"tpot_p50_ms", "setup_s"}


SETUP = {"train": ["build_compile_init", "data", "first_step",
                   "reference_check", "warm_fit", "step_text"],
         "serve": ["build_compile_init", "warm_wave", "reference_check",
                   "step_text", "pre_roll"]}
AFTER = {"train": [], "serve": ["drain", "loop_finish"]}


@pytest.mark.parametrize("workload,kind,seconds", [
    ("bert-tiny", "train", "1.5"), ("gpt2-tiny-chat", "serve", "2")])
@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_says_where_its_seconds_went(workload, kind, seconds, trace):
    """One ``[bench] wall:`` line on standard error as each phase ends, in
    the run's order, and the table among standard error's last lines."""
    proc = run(workload, trace, seconds=seconds)
    result(proc)
    err = proc.stderr.splitlines()
    phases = [m.group(1) for line in err for m in [
        re.match(r"\[bench\] wall: (\w+) [0-9.]+ \(since start [0-9.]+\)$",
                 line)] if m]
    want = ["imports_devices"] + SETUP[kind] + ["window"] + AFTER[kind]
    if trace:
        want += ["start_trace", "stop_trace", "load_trace", "xplane_reduce",
                 "program_spans", "layer_readers"]
        want += ["loss_after_32_steps"] if kind == "train" else [
            "request_records"]
    assert sorted(set(phases)) == sorted(want)
    order = [p for p in phases if p in SETUP[kind] + ["window"] + AFTER[kind]]
    assert order == [p for p in want if p in order]
    table = [line for line in err[-20:] if line.startswith("[bench] walls: ")]
    assert len(table) == 1
    walls = json.loads(table[0][len("[bench] walls: "):])
    assert set(walls) == set(want) | {"run_wall_s"}
    assert walls["run_wall_s"] >= walls["window"] > 0
    # beside the compared numbers, which stay standard error's last lines too
    assert any(line.startswith("[bench] compared ") for line in err[-20:])
