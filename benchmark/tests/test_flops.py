"""The yardstick's closed forms against the program's own, today: copies, so
that a later edit of the program cannot move the yardstick unseen."""
import importlib.util
import json
import os

import pytest

from benchmark import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bert_reference():
    spec = importlib.util.spec_from_file_location(
        "bert_ref", os.path.join(BENCH, "reference", "bert-large-proxy.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(BENCH, "configs", "bert-large-proxy.json")) as f:
        return mod, json.load(f)


def test_bert_form_equals_the_programs():
    from flexflow_tpu.models.bert import (BertConfig, bert_param_count,
                                          bert_train_flops_per_step)

    ref, config = bert_reference()
    for batch, seq in ((32, 512), (1, 4096), (128, 512)):
        cfg = BertConfig(batch_size=batch, seq_len=seq, hidden=1024,
                         num_heads=16, num_layers=24, intermediate=4096)
        assert ref.param_count(config) == bert_param_count(cfg)
        assert ref.train_flops_per_token(config, seq) * batch * seq == \
            bert_train_flops_per_step(cfg)


def test_attention_calls_at_the_two_lengths():
    ref, config = bert_reference()
    for seq, share in ((512, 0.08), (4096, 0.40)):
        (count, b, h, sq, sk, d, causal), = ref.attention_calls(config, 2, seq)
        assert (count, b, h, sq, sk, d, causal) == (24, 2, 16, seq, seq, 64,
                                                    False)
        # forward + backward kernel FLOPs are attention's share of the model's
        attn = count * (flops.flash_fwd_flops(b, h, sq, sk, d)
                        + flops.flash_bwd_flops(b, h, sq, sk, d))
        assert attn / (ref.train_flops_per_token(config, seq) * b * seq) == \
            pytest.approx(share, abs=0.01)


def test_flash_kernel_forms():
    b, h, s, d = 32, 16, 512, 64
    assert flops.flash_fwd_flops(b, h, s, s, d) == 4 * b * h * s * s * d
    assert flops.flash_bwd_flops(b, h, s, s, d) == \
        2 * flops.flash_fwd_flops(b, h, s, s, d)
    assert flops.flash_fwd_flops(b, h, s, s, d, causal=True) == \
        flops.flash_fwd_flops(b, h, s, s, d) / 2
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(flops.flash_fwd_flops(b, h, s, s, d),
                                      flops.flash_fwd_bytes(b, h, s, s, d),
                                      peak)
    assert bound == "compute" and t == pytest.approx(
        4 * b * h * s * s * d / 197e12)


def test_peaks_table():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and p["ici_bits_per_s"] == 1600e9
    for unknown in ("TPU v4", "cpu", "_source"):
        with pytest.raises(KeyError):
            flops.peaks(unknown)
