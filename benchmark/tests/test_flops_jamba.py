"""``ssm_flops.py``'s closed forms at AI21-Jamba2-3B's published widths equal
the issue's arithmetic and the program's own pricing
(``kvcache.node_slot_bytes``), and the new readers read nothing — and raise
nothing — from a run that has nothing for them."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "ai21-jamba2-3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ssm():
    return load(os.path.join(BENCH, "ssm_flops.py"), "ssm_flops")


def test_published_shapes(ssm, config):
    assert ssm.inner_width(config) == 5120
    assert ssm.mixer_layers(config) == 26        # all but layers 7 and 21
    # 26 x (5,120 x 16 f32 + 5,120 x 3 bf16): the issue's 9.3 MB a slot
    assert ssm.slot_state_bytes(config) == 9_318_400
    assert 192 * ssm.slot_state_bytes(config) == 1_789_132_800


def test_slot_bytes_are_the_programs(ssm, config):
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for
    from flexflow_tpu.serving.kvcache import node_slot_bytes

    op = op_class_for(OperatorType.OP_SSM_MIXER)(
        "l0_ssm", {"inner_dim": ssm.inner_width(config),
                   "state_dim": config["mamba_d_state"],
                   "conv_width": config["mamba_d_conv"],
                   "dt_rank": config["mamba_dt_rank"]},
        DataType.DT_BFLOAT16)
    assert ssm.mixer_layers(config) * node_slot_bytes(op) \
        == ssm.slot_state_bytes(config)


def test_scan_bytes_and_flops(ssm, config):
    # one token, one layer: x, dt, y of 5,120 and B, C of 16 in float32
    per_token = 4 * (3 * 5120 + 2 * 16)
    assert ssm.scan_bytes(1, 0, config) == 26 * per_token
    # a sequence writes its final (16, 5,120) state once a layer
    assert ssm.scan_bytes(0, 1, config) == 26 * 4 * 5120 * 16
    # 2,048 real tokens: 3.3 GB, 4 ms at 819 GB/s — far under the kernel's
    # vector-unit time, which is why the share reads low
    assert 3.2e9 < ssm.scan_bytes(2048, 1, config) < 3.4e9
    assert ssm.scan_flops(1, config) == 7 * 26 * 5120 * 16


@pytest.mark.parametrize("name", [
    "ssm_mixer_ms_per_step", "ssm_scan_ms_per_step", "ssm_state_roofline",
    "selective_scan_roofline", "recurrent_state_gb"])
def test_readers_read_nothing_from_an_empty_run(name):
    """What the driver's parent-side traced run hands them: a run whose
    program has no such span, counter or kernel."""
    mod = load(os.path.join(BENCH, "layer_metrics", f"{name}.py"), name)
    assert mod.NAME == name and mod.MOVES == "tpot_p50_ms"
    assert mod.CELLS == ["jamba2-*", "jamba-*"]
    for run in ({}, {"kind": "serve", "steps": 4, "peaks": {
            "hbm_bytes_per_s": 819e9}, "delta": {
                "prefill_tokens_computed": 10, "prefills": 1},
            "trace": {"kernel_s": {}}}):
        assert mod.read(run) is None
