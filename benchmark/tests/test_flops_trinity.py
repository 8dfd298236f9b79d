"""The yardstick's closed forms for ``trinity-mini`` (kept in its
``reference/trinity-mini.py``) equal the builder's own today, at the
published widths and at the rehearsal's; the band's pairs are counted, not
approximated; and the new readers read nothing from a run that has nothing
for them."""
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return load(os.path.join(BENCH, "reference", "trinity-mini.py"), "ref_tm")


@pytest.mark.parametrize("path,seq", [
    (os.path.join(BENCH, "configs", "trinity-mini.json"), 8192),
    (os.path.join(HERE, "cells", "configs", "trinity-tiny.json"), 32)])
def test_closed_forms_equal_the_builders(ref, path, seq):
    from flexflow_tpu.models.trinity import (TrinityConfig,
                                             trinity_param_count,
                                             trinity_train_flops_per_token)

    with open(path) as f:
        config = json.load(f)
    cfg = TrinityConfig(batch_size=1, seq_len=seq, **{
        field: config[key]
        for field, key in config["builder"]["fields"].items()})
    assert ref.param_count(config) == trinity_param_count(cfg)
    assert ref.train_flops_per_token(config, seq) == pytest.approx(
        trinity_train_flops_per_token(cfg), rel=1e-12)


def test_the_published_cut_is_the_issues_arithmetic(ref):
    with open(os.path.join(BENCH, "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    assert round(ref.param_count(config) / 1e6, 1) == 705.5
    per_token = ref.train_flops_per_token(config, 8192)
    assert 17.5e12 < per_token * 8192 < 18.5e12     # 18.1 TFLOP a step
    band, full = ref.attention_pairs(config, 8192)[0::4]
    assert band == 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert full == 8192 * 8193 // 2
    calls = ref.attention_calls(config, 1, 8192)
    assert calls[-1] == (1, 1, 32, 8192, 8192, 128, True)
    assert calls[0][4] * 8192 == band and calls[0][-1] is False


@pytest.mark.parametrize("name", [
    "expert_layer_ms_per_step", "expert_matmul_roofline",
    "window_attention_ms_per_step", "expert_load_max_over_mean"])
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    mod = load(os.path.join(BENCH, "layer_metrics", name + ".py"), name)
    assert mod.NAME == name and mod.CELLS == ["*"]
    assert mod.read({"kind": "train", "steps": 16, "trace": {
        "kernel_s": {"flash_attention_fwd": 1.0}}}) is None
    assert mod.read({"kind": "serve"}) is None


def test_window_reader_sums_the_windowed_kernels_alone():
    mod = load(os.path.join(BENCH, "layer_metrics",
                            "window_attention_ms_per_step.py"), "w")
    run = {"steps": 4, "trace": {"kernel_s": {
        "flash_attention_fwd": 1.0, "flash_attention_fwd_window": 0.2,
        "flash_attention_bwd_dkv_window": 0.3,
        "flash_attention_bwd_dq_window": 0.1, "ragged-dot-none": 5.0}}}
    assert mod.read(run) == pytest.approx(1e3 * 0.6 / 4)


def test_expert_closed_forms():
    from benchmark import moe_flops

    assert moe_flops.expert_matmul_flops(8192, 2048, 1024) == \
        6 * 8192 * 3 * 2048 * 1024
    assert moe_flops.expert_matmul_bytes(8192, 16, 2048, 1024) == \
        2 * (3 * 16 * 2048 * 1024 + 2 * 8192 * 2048)


def test_a_trace_that_names_no_cell_raises_and_none_reads_nothing(tmp_path):
    """The cell's name is read from the trace's directory (the run's facts
    carry none): a layout that moved must not read as "nothing to report"."""
    from benchmark.reduce import cell

    assert cell.cell_config({}) is None
    there = os.path.join(BENCH, os.pardir, ".bench_trace",
                         "trinity-mini-train-s8192", "plugins", "profile",
                         "2026_01_01", "host.xplane.pb")
    assert cell.cell_config({"trace_file": there})["hidden_size"] == 2048
    rehearsal = there.replace("trinity-mini-train-s8192",
                              "trinity-tiny-train")
    assert cell.cell_config({"trace_file": rehearsal})["hidden_size"] == 64
    for moved in (str(tmp_path / "host.xplane.pb"),
                  there.replace("trinity-mini-train-s8192", "no-such-cell")):
        with pytest.raises(RuntimeError, match="reduce/cell.py"):
            cell.cell_config({"trace_file": moved})
