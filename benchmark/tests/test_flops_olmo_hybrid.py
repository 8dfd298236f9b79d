"""``delta_flops.py``'s closed forms at Olmo-Hybrid-7B's published widths
equal the issue's arithmetic and the program's own pricing
(``Op.slot_state_bytes``), and the new readers read nothing — and raise
nothing — from a run that has nothing for them."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

READERS = ["gdn_mixer_ms_per_step", "gdn_rule_ms_per_step",
           "gdn_state_roofline", "gated_delta_rule_roofline", "gdn_state_gb"]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def delta():
    return load(os.path.join(BENCH, "delta_flops.py"), "delta_flops")


def test_published_shapes(delta, config):
    assert delta.dims(config) == (30, 96, 192, 4)
    assert delta.mixer_layers(config) == 12      # three of every four of 16
    # 12 x (30 x 96 x 192 f32 + 3 x 11,520 bf16): the issue's 27.4 MB a slot
    assert delta.slot_state_bytes(config) == 27_371_520
    assert 64 * delta.slot_state_bytes(config) == 1_751_777_280


def test_slot_bytes_are_the_programs(delta, config):
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for

    h, dk, dv, k = delta.dims(config)
    op = op_class_for(OperatorType.OP_GATED_DELTA_MIXER)(
        "l0_gdn", {"num_heads": h, "key_dim": dk, "value_dim": dv,
                   "conv_width": k, "neg_eigval": True, "norm_eps": 1e-6},
        DataType.DT_BFLOAT16)
    assert delta.mixer_layers(config) * op.slot_state_bytes() \
        == delta.slot_state_bytes(config)


def test_the_chunk_is_the_kernels(delta):
    from flexflow_tpu.kernels.gated_delta_rule import CHUNK

    assert delta.CHUNK == CHUNK == 64


def test_rule_flops_and_bytes(delta, config):
    # one token, one layer and head: K S, Q S and K^T U of 96 x 192, K K^T
    # and Q K^T of 64 x 96 a row, P U of 64 x 192 a row, two operations each
    per_token_head = 2 * (3 * 96 * 192 + 2 * 64 * 96 + 64 * 192)
    assert delta.rule_flops(1, config) == 12 * 30 * per_token_head
    # q, k of 96 and v, o of 192 and g, beta a head, float32
    assert delta.rule_bytes(1, 0, config) == 12 * 4 * 30 * (2 * 96 + 2 * 192
                                                            + 2)
    # a sequence writes its final 30 x (96, 192) state once a layer
    assert delta.rule_bytes(0, 1, config) == 12 * 4 * 30 * 96 * 192
    # 1,024 real tokens: 59 GFLOP, 0.3 ms at 197 TFLOP/s, and 0.9 GB, 1.1 ms
    # at 819 GB/s: the least time is the traffic's
    assert 5.8e10 < delta.rule_flops(1024, config) < 6.0e10
    assert 0.8e9 < delta.rule_bytes(1024, 1, config) < 1.0e9


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_an_empty_run(name):
    """What the driver's parent-side traced run hands them: a run whose
    program has no such span, counter or kernel."""
    mod = load(os.path.join(BENCH, "layer_metrics", f"{name}.py"), name)
    assert mod.NAME == name and mod.MOVES == "tpot_p50_ms"
    assert mod.CELLS == ["olmo-hybrid-*"]
    for run in ({}, {"kind": "serve", "steps": 4, "peaks": {
            "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, "delta": {
                "prefill_tokens_computed": 10, "prefills": 1},
            "trace": {"kernel_s": {}}}):
        assert mod.read(run) is None


def test_the_readers_are_in_benchmark_json():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        mod = load(os.path.join(BENCH, "layer_metrics", f"{name}.py"), name)
        entry = by_name[name]
        assert (entry["unit"], entry["layer"], entry["moves"]) \
            == (mod.UNIT, mod.LAYER, mod.MOVES)
        assert entry["workloads"] == ["olmo-hybrid-7b-assist"]
