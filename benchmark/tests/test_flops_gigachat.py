"""``hybrid_flops.py``'s closed forms at GigaChat3.5-432B-A28B's published
widths against counts by hand and the program's own pricing
(``Op.slot_state_bytes``, ``kvcache.node_token_bytes``); the file's
``parameters_held`` against the builder's closed form; and the new readers
read nothing — and raise nothing — from a run that has nothing for them."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

READERS = ["hybrid_gdn_mixer_ms_per_step", "hybrid_gdn_state_roofline",
           "hybrid_gdn_prefill_roofline", "hybrid_state_gb",
           "hybrid_latent_ms_per_step", "hybrid_latent_read_roofline",
           "hybrid_expert_layer_ms_per_step", "hybrid_expert_matmul_roofline"]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "gigachat35-432b-a28b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def hybrid():
    from benchmark import hybrid_flops

    return hybrid_flops


def test_published_shapes(hybrid, config):
    assert hybrid.delta_dims(config) == (32, 64, 128, 128, 4)
    assert (hybrid.delta_layers(config), hybrid.latent_layers(config)) \
        == (4, 1)
    # 4 x (64 x 128 x 128 f32 + 16,384 channels x 3 x bf16)
    assert hybrid.slot_state_bytes(config) == 4 * (
        64 * 128 * 128 * 4 + 16384 * 3 * 2) == 17_170_432
    assert 128 * hybrid.slot_state_bytes(config) == 2_197_815_296
    assert hybrid.latent_row_bytes(config) == 640 * 2 == 1280


def test_state_and_row_bytes_are_the_programs(hybrid, config):
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for
    from flexflow_tpu.serving.kvcache import node_token_bytes

    hk, hv, dk, dv, k = hybrid.delta_dims(config)
    gdn = op_class_for(OperatorType.OP_GATED_DELTA_MIXER)(
        "l0_gdn", {"num_heads": hv, "num_key_heads": hk, "key_dim": dk,
                   "value_dim": dv, "conv_width": k, "neg_eigval": False,
                   "norm_eps": 1e-6}, DataType.DT_BFLOAT16)
    assert hybrid.delta_layers(config) * gdn.slot_state_bytes() \
        == hybrid.slot_state_bytes(config)
    assert gdn.slot_state_heads_a_row() == 1          # d_v 128: a head a row
    mla = op_class_for(OperatorType.OP_LATENT_ATTENTION)(
        "l4_mla", {"num_heads": 64, "kv_rank": 512, "rope_dim": 64,
                   "q_rank": 1536, "nope_dim": 128, "v_dim": 128,
                   "embed_dim": 7168, "rope_theta": 1e5, "eps": 1e-6},
        DataType.DT_BFLOAT16)
    assert node_token_bytes(mla) == hybrid.latent_row_bytes(config)


def test_the_grouped_rule_by_hand(hybrid, config):
    """One token, one layer, by hand: a key head's two C x d_k x C products,
    a value head's two C x d_k x d_v, its C x C x d_v and its d_k x C x d_v,
    each a token's share (1/C of a chunk's) at two operations a
    multiply-add."""
    from flexflow_tpu.kernels.gated_delta_rule import CHUNK

    assert hybrid.CHUNK == CHUNK == 64
    c = 64
    key_head = 2 * (2 * c * 128 * c) / c
    value_head = 2 * (2 * c * 128 * 128 + c * c * 128 + 128 * c * 128) / c
    assert hybrid.rule_flops(1, config) == 4 * (32 * key_head
                                                + 64 * value_head)
    # the ungrouped count (every value head its own K K^T and Q K^T) is more
    assert hybrid.rule_flops(1, config) < 4 * 64 * (key_head + value_head)
    # q, k at 32 heads, v and o at 64, g and beta: float32; a sequence's
    # state once
    assert hybrid.rule_bytes(10, 2, config) == 4 * 4 * (
        10 * (2 * 32 * 128 + 2 * 64 * 128 + 2 * 64) + 2 * 64 * 128 * 128)


def test_the_latent_read_and_the_experts_by_hand(hybrid, config):
    # a row against 64 heads: a 576-wide score and a 512-wide sum a head
    assert hybrid.absorbed_read_flops(1, config) == 2 * 64 * (576 + 512)
    # the bytes lead at 64 heads: 1,280 B / 819 GB/s against the FLOPs
    assert 1280 / 819e9 > hybrid.absorbed_read_flops(1, config) / 197e12
    expert = 3 * 7168 * 2048
    assert hybrid.expert_flops(5, config) == 2 * 5 * expert
    assert hybrid.expert_bytes(5, 16, config) == 2 * (16 * expert
                                                      + 2 * 5 * 7168)
    # every held expert of the four routed layers, a step: ISSUE 53's 5.6 GB
    assert 5.6e9 < hybrid.expert_bytes(0, 4 * 16, config) < 5.7e9


def test_parameters_held_is_the_builders_closed_form(config):
    from flexflow_tpu.models.gigachat import (GigaChatConfig,
                                              gigachat_param_count)

    cfg = GigaChatConfig(batch_size=8, **{
        f: config[k] for f, k in config["builder"]["fields"].items()})
    assert gigachat_param_count(cfg) == config["parameters_held"] \
        == 4_731_721_728


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_says_nothing(name):
    mod = load(os.path.join(BENCH, "layer_metrics", f"{name}.py"), name)
    assert mod.NAME == name and mod.CELLS == ["gigachat*"]
    assert mod.MOVES == "tpot_p50_ms"
    for run in ({}, {"kind": "serve"}, {"kind": "train", "steps": 3},
                {"kind": "serve", "steps": 3, "peaks": {
                    "hbm_bytes_per_s": 1.0, "bf16_flops_per_s": 1.0},
                 "trace": {}, "delta": {}}):
        assert mod.read(run) is None
