"""Sequence-parallel paged decode (ISSUE 18, docs/decode_perf.md
"Sequence-parallel decode"): the stream contract — the seq-sharded
decode path emits the single-shard engine's token streams at shards 2
and 4, solo and co-batched, through the prefix-hit and chunked-prefill
paths — plus the combine algebra units, the typed refusal matrix
(speculative), the FF006 seq-shard laws, and the searched bucket
routing. All CPU-deterministic (the seq axis is emulated as a loop over
key segments on one device)."""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.serving import ServingEngine
from flexflow_tpu.serving.kvcache import SeqShardsError, parse_context_buckets


def _build(hidden=64, heads=4, layers=2, seq_len=32, vocab=100, seed=42):
    # hidden 64 / 4 heads is the GPT2Config.tiny family
    cfg = GPT2Config(batch_size=2, seq_len=seq_len, hidden=hidden,
                     num_heads=heads, num_layers=layers,
                     intermediate=hidden * 2, vocab_size=vocab)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    config.seed = seed
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg


@pytest.fixture(scope="module")
def gpt2():
    return _build()


PROMPTS = [[5, 6, 7, 8, 9], [11, 12, 13], [3, 1, 4, 1, 5, 9, 2, 6]]


def _gen(ff, prompts, shards, **kw):
    # kv_block_size=8 -> a 4-block table at max_decode_len 32, so
    # shards 1/2/4 all divide it (FF006 law)
    eng = ServingEngine(ff, n_slots=2, max_decode_len=32,
                        kv_block_size=8, seq_shards=shards, **kw)
    toks = eng.generate(prompts, max_new_tokens=12)
    return toks, eng


# ------------------------------------------------------ stream contract
@pytest.mark.parametrize("shards", [2, 4])
def test_seqpar_stream_equals_single_shard_solo_and_cobatched(gpt2,
                                                              shards):
    """The sharded token stream equals the single-shard stream: solo
    (one slot live) and co-batched (slots at different extents)."""
    ff, _ = gpt2
    ref_solo, _ = _gen(ff, [PROMPTS[0]], 1)
    got_solo, eng = _gen(ff, [PROMPTS[0]], shards)
    assert got_solo == ref_solo
    assert eng.decode_compiles == 1  # single-compile contract holds
    ref_co, _ = _gen(ff, PROMPTS, 1)
    got_co, _ = _gen(ff, PROMPTS, shards)
    assert got_co == ref_co


def test_seqpar_stream_through_prefix_hit_path(gpt2):
    """Prefix-cache hits map blocks without prefill compute; the sharded
    reader must see the identical pool rows (layout untouched)."""
    ff, _ = gpt2
    shared = [7, 7, 7, 7, 7, 7, 7, 7, 2]  # >= one full block shared
    prompts = [shared + [4], shared + [9]]
    ref, _ = _gen(ff, prompts, 1, prefix_cache="on")
    got, eng = _gen(ff, prompts, 2, prefix_cache="on")
    assert got == ref
    assert eng.stats.prefix_hits > 0  # the hit path actually exercised


def test_seqpar_stream_through_chunked_prefill_path(gpt2):
    """Chunked prefill writes KV block-by-block; the sharded decode that
    follows must give the one-shot-prefill single-shard run's stream."""
    ff, _ = gpt2
    long_prompt = list(range(2, 2 + 17))
    ref, _ = _gen(ff, [long_prompt], 1)
    got, _ = _gen(ff, [long_prompt], 2, prefill_chunk_tokens=8)
    assert got == ref


def test_seqpar_fast_path_tokens_match(gpt2):
    """The split-K path merges per-shard online-softmax partials —
    float-associativity differs from the monolithic softmax, but greedy
    argmax must still agree token-for-token, here with more requests
    than slots (a recycled slot's table row is re-partitioned too)."""
    ff, _ = gpt2
    prompts = PROMPTS + [[2] * 10, [9, 8, 7, 6]]
    ref, _ = _gen(ff, prompts, 1)
    got, _ = _gen(ff, prompts, 2)
    assert got == ref


def test_seqpar_kv_per_chip_telemetry(gpt2):
    """kv_hbm_per_chip_bytes = measured per-step KV read / seq_shards:
    the per-chip share halves at shards 2 and surfaces in summary()."""
    ff, _ = gpt2
    _, e1 = _gen(ff, [PROMPTS[0]], 1)
    _, e2 = _gen(ff, [PROMPTS[0]], 2)
    a = e1.stats.kv_hbm_per_chip_bytes
    b = e2.stats.kv_hbm_per_chip_bytes
    assert a > 0 and b > 0
    assert b == a // 2
    assert e2.stats.summary()["kv_hbm_per_chip_bytes"] == b


# ------------------------------------------------------- combine algebra
def test_combine_partials_matches_monolithic_softmax():
    from flexflow_tpu.kernels.seqpar_decode import (combine_partials,
                                                    decode_shard_partial,
                                                    shard_segment)
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    b, h, ext, d = 2, 4, 16, 8
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, ext, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, ext, d)), jnp.float32)
    mask = jnp.ones((b, h, 1, ext), bool)
    scale = 1.0 / np.sqrt(d)

    seg = shard_segment(ext, 4)
    parts = [decode_shard_partial(q, k[:, :, s * seg:(s + 1) * seg],
                                  v[:, :, s * seg:(s + 1) * seg],
                                  mask[..., s * seg:(s + 1) * seg], scale)
             for s in range(4)]
    out = combine_partials(parts)

    import jax.nn

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref = jnp.einsum("bhqk,bhkd->bhqd",
                     jax.nn.softmax(scores, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_combine_fully_masked_shard_contributes_exact_zero():
    """A shard whose key segment lies entirely beyond the live context
    must contribute EXACTLY zero — exp(-1e30 - m*) underflows to 0 — so
    short contexts in a wide bucket are unaffected by dead shards."""
    from flexflow_tpu.kernels.seqpar_decode import (combine_partials,
                                                    decode_shard_partial)
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    b, h, seg, d = 1, 2, 4, 8
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, seg, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, seg, d)), jnp.float32)
    live = jnp.ones((b, h, 1, seg), bool)
    dead = jnp.zeros((b, h, 1, seg), bool)
    scale = 1.0 / np.sqrt(d)

    alone = combine_partials([decode_shard_partial(q, k, v, live, scale)])
    with_dead = combine_partials(
        [decode_shard_partial(q, k, v, live, scale),
         decode_shard_partial(q, jnp.full_like(k, 9.0),
                              jnp.full_like(v, 9.0), dead, scale)])
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(with_dead))


def test_shard_segment_and_pricing_forms():
    from flexflow_tpu.kernels.seqpar_decode import (combine_bytes_per_step,
                                                    query_bytes_per_step,
                                                    shard_segment)

    assert shard_segment(32, 4) == 8
    with pytest.raises(ValueError):
        shard_segment(30, 4)  # ragged split
    with pytest.raises(ValueError):
        shard_segment(32, 0)
    # combine ships (m, l, acc) = (2 + vdim) f32 per (slot, head);
    # a single shard combines nothing
    assert combine_bytes_per_step(4, 8, 2, 2) == 2 * 4 * (2 + 8) * 4
    assert combine_bytes_per_step(4, 8, 2, 1) == 0
    assert query_bytes_per_step(4, 8, 2, 2) == 2 * 4 * 8 * 2


# -------------------------------------------------------- refusal matrix
def test_speculative_refuses_seq_sharded_models():
    target, _ = _build(seed=1)
    drafter, _ = _build(layers=1, seed=2)
    target.config.seq_shards = 2
    from flexflow_tpu.serving import SpeculativeDecoder

    with pytest.raises(SeqShardsError, match="--seq-shards"):
        SpeculativeDecoder(target, drafter)
    target.config.seq_shards = 1
    SpeculativeDecoder(target, drafter)  # single-shard pair is fine


# ------------------------------------------------------------ FF006 laws
def test_ff006_seq_shard_laws(gpt2):
    from flexflow_tpu.analysis.rules import check_paged_kv

    ff, _ = gpt2
    pcg = ff.create_pcg()
    base = dict(block_size=8, pool_blocks=17, max_blocks_per_slot=4,
                max_context=32)
    assert check_paged_kv(pcg, **base, seq_shards=4) == []
    # non-dividing table: 4 blocks across 3 shards is ragged
    bad = check_paged_kv(pcg, **base, seq_shards=3)
    assert any("must divide the block-table width" in d.message
               for d in bad)
    # a bucket past the table would truncate a legal request
    bad = check_paged_kv(pcg, **base, seq_shards=2,
                         context_buckets=(16, 64))
    assert any("bucket" in d.message.lower() for d in bad)
    # the seq axis is a mesh axis: 8 devices shard by 2/4/8, not 3
    base6 = dict(base, max_blocks_per_slot=6)
    bad = check_paged_kv(pcg, **base6, seq_shards=3, n_devices=8)
    assert any("mesh" in d.message or "device" in d.message
               for d in bad)
    # composition with heads-sharded KV: tp * seq_shards must divide
    bad = check_paged_kv(pcg, **base, seq_shards=4, n_devices=8,
                         kv_layout="sharded", tp=4)
    assert any("tp" in d.message or "shard" in d.message for d in bad)
    assert check_paged_kv(pcg, **base, seq_shards=2, n_devices=8,
                          kv_layout="sharded", tp=4) == []
    # seq_shards < 1 is itself diagnosed, not an exception
    bad = check_paged_kv(pcg, **base, seq_shards=0)
    assert any("seq_shards" in d.message for d in bad)


# ------------------------------------------------------- bucket routing
def test_parse_context_buckets_contract():
    assert parse_context_buckets("") == ()
    assert parse_context_buckets("1024, 8192,32768") == (1024, 8192, 32768)
    assert parse_context_buckets((256, 512)) == (256, 512)
    with pytest.raises(ValueError):
        parse_context_buckets("8192,1024")  # must be strictly ascending
    with pytest.raises(ValueError):
        parse_context_buckets("0,1024")
    with pytest.raises(ValueError):
        parse_context_buckets("10,ten")


def test_plan_seq_shards_for_routes_buckets():
    from flexflow_tpu.serving.search import ServingPlan

    plan = ServingPlan(mesh_shape=(8, 1), layout="paged", slots=8,
                       max_decode_len=32768, slo_p99_ms=0.0,
                       sim_decode_ms=1.0, sim_prefill_ms=1.0,
                       sim_p50_ms=1.0, sim_p99_ms=1.0,
                       sim_tokens_per_s=1.0, sim_memory=0, feasible=True,
                       context_buckets=(1024, 8192, 32768),
                       seq_shards_by_bucket={1024: 1, 8192: 4, 32768: 8})
    assert plan.seq_shards_for(500) == 1
    assert plan.seq_shards_for(1024) == 1
    assert plan.seq_shards_for(2000) == 4
    assert plan.seq_shards_for(32768) == 8
    # beyond every bucket -> the widest (must shard hardest)
    assert plan.seq_shards_for(50000) == 8
    # no buckets -> single shard
    plan.context_buckets = ()
    assert plan.seq_shards_for(50000) == 1


def test_admission_stamps_context_bucket(gpt2):
    """generate() routes each request to its smallest covering bucket
    (prompt + budget); requests past every bucket take the largest."""
    ff, _ = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=32,
                        kv_block_size=8, context_buckets=(8, 16, 32))
    from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                                Request)

    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8,
                                     buckets=eng.buckets, max_len=32)
    eng._attach_kv_accounting(sched)
    r = Request(prompt=np.asarray([1, 2, 3], np.int32), max_new_tokens=4)
    eng._stamp_context_bucket(r)
    assert r.context_bucket == 8  # 3 + 4 = 7 fits the first bucket
    r2 = Request(prompt=np.asarray([1] * 20, np.int32), max_new_tokens=10)
    eng._stamp_context_bucket(r2)
    assert r2.context_bucket == 32
