"""Round-18 housekeeping (ISSUE 18 satellites):

* `--seq-shards` / `--context-buckets` flags: parse-time validation,
  ring-layout combo refusal, preflight validation of programmatic
  assignment (including malformed bucket strings), documented in
  python_api.md (check_docs_flags stays green).
* the 32k-context sizing the sequence axis exists for: a paged KV that
  exceeds one chip's HBM and fits per chip once sharded.
* `kv_hbm_per_chip_bytes` accounting: ServingStats summary and the
  telemetry serving block surface it only when measured, and the
  per-chip division is exact.
* the serving search exposes the per-bucket seq-shard pricer with the
  fallback contract (widest bucket flagged infeasible rather than
  silently dropped).
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


def _read(name):
    with open(os.path.join(REPO, name)) as f:
        return f.read()


# ------------------------------------------------------------------ flags
def test_seq_shards_flag_parse_and_combos():
    from flexflow_tpu import FFConfig

    cfg = FFConfig()
    assert cfg.seq_shards == 1  # default: sequence sharding off
    assert cfg.context_buckets == ""
    cfg.parse_args(["--seq-shards", "4"])
    assert cfg.seq_shards == 4
    with pytest.raises(ValueError, match=">= 1"):
        FFConfig().parse_args(["--seq-shards", "0"])
    cfg2 = FFConfig()
    cfg2.parse_args(["--context-buckets", "1024,8192"])
    assert cfg2.context_buckets == "1024,8192"
    with pytest.raises(ValueError):
        FFConfig().parse_args(["--context-buckets", "8192,1024"])


def test_seq_shards_preflight_programmatic_assignment():
    from flexflow_tpu import FFConfig
    from flexflow_tpu.resilience.preflight import (PreflightError,
                                                   preflight_config)

    ok = FFConfig()
    ok.seq_shards = 2
    ok.context_buckets = "16,32"
    preflight_config(ok)
    bad = FFConfig()
    bad.seq_shards = 0
    with pytest.raises(PreflightError, match="seq-shards"):
        preflight_config(bad)
    garbled = FFConfig()
    garbled.context_buckets = "10,ten"
    with pytest.raises(PreflightError):
        preflight_config(garbled)


def test_seq_shard_flags_documented():
    import check_docs_flags

    assert check_docs_flags.main([]) == 0
    api = _read("docs/python_api.md")
    assert "--seq-shards" in api
    assert "--context-buckets" in api
    # the decode-perf doc carries the shard layout + refusal matrix
    dp = _read("docs/decode_perf.md")
    assert "Sequence-parallel decode" in dp
    assert "Refusal matrix" in dp


# -------------------------------------------------------------- capacity
def test_seqpar_capacity_story_holds():
    """The analytic 32k sizing must actually tell the capacity story:
    total paged KV exceeds ONE chip's HBM, the per-chip share fits
    (GQA 8 KV heads x d128 in bf16, 80 layers, 8 slots, 8 shards)."""
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.serving.kvcache import kv_token_bytes

    machine = TPUMachineModel.from_generation("v5e", 8)
    per_token = 80 * kv_token_bytes(8, 128, 128, 2)
    total = per_token * 32768 * 8
    assert total > machine.hbm_capacity
    assert total // 8 <= machine.hbm_capacity


# ------------------------------------------------------------- accounting
def test_kv_hbm_per_chip_summary_presence_and_math():
    from flexflow_tpu.serving.engine import ServingStats

    st = ServingStats()
    assert "kv_hbm_per_chip_bytes" not in st.summary()  # absent until set
    st.kv_bytes_read = 4096 * 10
    st.decode_steps = 10
    # the serve loop's division: per-step KV read over the shard width
    st.kv_hbm_per_chip_bytes = int(
        st.kv_bytes_read / st.decode_steps / 4)
    assert st.kv_hbm_per_chip_bytes == 1024
    assert st.summary()["kv_hbm_per_chip_bytes"] == 1024


def test_telemetry_serving_block_kv_per_chip():
    from flexflow_tpu.obs.telemetry import StepTelemetry

    tel = StepTelemetry(batch_size=1, phase="serve")
    tel.requests_served = 3
    tel.tokens_generated = 12
    sv = tel.summary()["serving"]
    assert "kv_hbm_per_chip_bytes" not in sv  # None -> omitted
    tel.serving_kv_hbm_per_chip_bytes = 2048
    assert tel.summary()["serving"]["kv_hbm_per_chip_bytes"] == 2048
    # the trace digest renders it (static pin on the script)
    assert "kv_hbm_per_chip_bytes" in _read("scripts/trace_summary.py")


# ----------------------------------------------------------- search units
def test_bucket_seq_shards_pricer_contract():
    """_bucket_seq_shards: width 1 for a context one chip streams
    comfortably; wider for a bucket whose KV swamps one chip; the
    infeasible fallback flags fits=False at the widest width rather
    than silently dropping the bucket."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.serving.search import _bucket_seq_shards

    cfg = GPT2Config(batch_size=2, seq_len=32, hidden=64, num_heads=4,
                     num_layers=2, intermediate=128, vocab_size=100)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    pcg = ff.create_pcg()
    machine = TPUMachineModel.from_generation("v5e", 8)

    s_tiny, _, _, fits = _bucket_seq_shards(
        pcg, machine, 8, slots=8, bucket=64, kv_dtype="native",
        kv_fill=1.0)
    assert s_tiny == 1 and fits  # combine never pays for itself at 64
    s_small, _, _, fits_small = _bucket_seq_shards(
        pcg, machine, 8, slots=8, bucket=1024, kv_dtype="native",
        kv_fill=1.0)
    s_big, t_kv, t_comb, fits_big = _bucket_seq_shards(
        pcg, machine, 8, slots=8, bucket=32768, kv_dtype="native",
        kv_fill=1.0)
    # widths widen monotonically with context, stay on the mesh, and
    # every tiny-model bucket fits one chip
    assert 1 <= s_small <= s_big <= 8 and fits_small and fits_big
    assert t_kv >= 0.0 and t_comb >= 0.0
