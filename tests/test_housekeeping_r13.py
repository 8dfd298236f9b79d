"""ISSUE 12 satellite pins: paged-KV flags, speculative accounting,
typed admission rejections, and docs wiring."""
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ flag parsing
def test_kv_flags_parse():
    from flexflow_tpu.config import FFConfig

    c = FFConfig()
    c.parse_args(["--kv-block-size", "32", "--kv-pool-blocks", "9",
                  "--kv-dtype", "int8"])
    assert (c.kv_block_size, c.kv_pool_blocks, c.kv_dtype) == \
        (32, 9, "int8")


@pytest.mark.parametrize("argv,match", [
    (["--kv-dtype", "fp8"], "native|int8"),
    (["--kv-block-size", "0"], "kv-block-size"),
    (["--kv-pool-blocks", "-1"], "kv-pool-blocks"),
])
def test_kv_flag_validation_fails_fast(argv, match):
    from flexflow_tpu.config import FFConfig

    with pytest.raises(ValueError, match=match):
        FFConfig().parse_args(argv)


def _tiny_gpt2(config):
    from flexflow_tpu import FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2

    cfg = GPT2Config.tiny(batch_size=2)
    config.batch_size = 2
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg


def test_engine_kv_validation():
    """Engine-level validation mirrors the flags for programmatic use."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.serving import ServingEngine

    ff, _cfg = _tiny_gpt2(FFConfig())
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(ff, kv_dtype="fp8")


def test_removed_kv_cache_flag_is_ignored():
    """``--kv-cache`` left with the ring layout (ISSUE 31). The parser
    keeps no message for a removed flag: like every unrecognised flag it
    is ignored, the flags around it still land, and the engine built
    from that config is the paged one."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.serving import ServingEngine

    config = FFConfig()
    config.parse_args(["--kv-cache", "ring", "--max-decode-len", "16",
                       "--kv-block-size", "8"])
    assert not hasattr(config, "kv_cache")
    assert (config.max_decode_len, config.kv_block_size) == (16, 8)
    ff, _cfg = _tiny_gpt2(config)
    eng = ServingEngine(ff, n_slots=2)
    assert eng.kv_cache == "paged" and eng.block_allocator is not None
    out = eng.generate([[5, 6, 7], [1, 2]], max_new_tokens=3)
    assert [len(o) for o in out] == [3, 3]
    assert eng.state.block_tables.shape == (2, 2)


# ---------------------------------------------------------- stats + ewma
def test_stats_summary_spec_and_kv_fields_gated():
    from flexflow_tpu.serving import ServingStats

    st = ServingStats()
    s = st.summary()
    assert "spec_acceptance" not in s and "kv_bytes_per_token" not in s
    assert st.acceptance_rate() is None
    st.spec_rounds, st.spec_proposed, st.spec_accepted = 3, 9, 6
    st.tokens_generated, st.kv_bytes_read = 10, 12345
    s = st.summary()
    assert s["spec_acceptance"] == round(6 / 9, 4)
    assert s["kv_bytes_per_token"] == 1234.5
    assert s["spec_rounds"] == 3


def test_admission_controller_speculation_ewma():
    from flexflow_tpu.serving import AdmissionController

    c = AdmissionController(alpha=0.5)
    assert c.spec_acceptance is None
    c.observe_speculation(0, 0)  # no proposals: no-op
    assert c.spec_acceptance is None
    c.observe_speculation(4, 4)
    assert c.spec_acceptance == 1.0
    c.observe_speculation(0, 4)
    assert c.spec_acceptance == 0.5  # EWMA with alpha 0.5
    # the cost half needs no special casing: committed tokens per round
    # wall flow through observe_step
    c.observe_step(0.01, 5)
    assert c.token_cost_ms == pytest.approx(2.0)


def test_context_overflow_is_exported_rejection():
    from flexflow_tpu.serving import (ContextOverflowError,
                                      ServingRejection)

    assert issubclass(ContextOverflowError, ServingRejection)
    e = ContextOverflowError("too long", queued=2, active=1)
    assert (e.queued, e.active) == (2, 1)


# ------------------------------------------------------------ docs wiring
def test_decode_perf_doc_linked():
    doc = os.path.join(REPO, "docs", "decode_perf.md")
    assert os.path.exists(doc)
    body = open(doc).read()
    for needle in ("flash-decode", "int8", "speculative", "FF006"):
        assert needle.lower() in body.lower(), f"{needle} missing"
    index = open(os.path.join(REPO, "docs", "index.md")).read()
    assert "decode_perf.md" in index
    serving = open(os.path.join(REPO, "docs", "serving.md")).read()
    assert "decode_perf.md" in serving
    assert "Paged KV cache" in serving
    readme = open(os.path.join(REPO, "README.md")).read()
    assert "decode_perf.md" in readme


def test_static_analysis_doc_mentions_paged_ff006():
    body = open(os.path.join(REPO, "docs",
                             "static_analysis.md")).read()
    assert "check_paged_kv" in body
