"""Paged KV decode (ISSUE 12, docs/serving.md "Paged KV cache" +
docs/decode_perf.md): the equivalence matrix — fp decode logits against
the whole-sequence forward within the stated tolerance
(tests/serving_oracle.py), int8 within its pinned tolerance band,
speculative greedy output token-identical to the baseline, fleet
migration of a paged stream token-identical on the survivor —
plus allocator laws, occupancy decoupling, admission rejection, the
flash-decode kernel in interpret mode, and the FF006 paged shape
checks. All CPU-deterministic."""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.serving import (BlockAllocator, ContextOverflowError,
                                  ServingEngine, SpeculativeDecoder)
from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                            Request, ServingRejection)
from serving_oracle import assert_matches_reference

# int8 KV tolerance band (docs/decode_perf.md): decode logits of the
# quantized layout vs the fp layout on the reference tiny-GPT2 workload.
# Pinned deliberately — a band regression means the quantizer changed.
KV_INT8_LOGIT_BAND = 0.25
# and the greedy argmax must still agree on almost every step
KV_INT8_ARGMAX_AGREEMENT = 0.9


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


PROMPTS = [[5, 6, 7, 8, 9], [11, 12, 13], [1] * 9,
           [3, 1, 4, 1, 5, 9, 2, 6]]


def _teacher_forced_paged(ff, seq, prompt_len, max_len, **engine_kw):
    """Prefill + paged decode with the TRUE next token fed back each
    step, through the real engine machinery (allocator, table rows,
    _write_slot scatter) — per-position decode logits for the tolerance/
    band comparisons."""
    import jax
    import jax.numpy as jnp

    eng = ServingEngine(ff, n_slots=1, max_decode_len=max_len,
                        **engine_kw)
    bucket = next(b for b in eng.buckets if b >= prompt_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = seq[0, :prompt_len]
    _lg, _last, cache = eng._prefill_fn(bucket)(
        ff.params, [jnp.asarray(padded)],
        jnp.asarray([prompt_len], np.int32))
    eng._ensure_state(cache)
    blocks = eng.block_allocator.alloc(
        eng.block_allocator.blocks_needed(seq.shape[1]))
    row = np.zeros((eng.max_blocks_per_slot,), np.int32)
    row[:len(blocks)] = blocks
    eng._write_slot(cache, 0, prompt_len, int(seq[0, prompt_len - 1]),
                    table_row=row)
    dec = eng._decode_fn()
    state = eng.state
    rows = {}
    for t in range(prompt_len, seq.shape[1]):
        lg, state = dec(ff.params, [jnp.asarray(seq[:1, t:t + 1])], state)
        rows[t] = np.asarray(jax.device_get(lg))[0]
    return rows


def _full_forward_logits(ff, seq):
    fwd = ff.executor.make_forward()
    return np.asarray(fwd(ff.params, [seq]))[0]


# --------------------------------------------------- the equivalence matrix
def test_paged_decode_matches_full_forward(gpt2):
    """Matrix row 1: paged fp decode through the real engine machinery
    matches the whole-sequence forward within the stated tolerance and
    chooses the same greedy token at every position
    (tests/serving_oracle.py) — the gather is pure pointer chasing and
    garbage-block rows are masked to exact zeros."""
    ff, cfg = gpt2
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size,
                       size=(1, cfg.seq_len)).astype(np.int32)
    full = _full_forward_logits(ff, np.repeat(seq, cfg.batch_size, 0))
    rows = _teacher_forced_paged(ff, seq, prompt_len=7,
                                 max_len=cfg.seq_len, kv_block_size=8)
    ts = sorted(rows)
    assert_matches_reference(np.stack([rows[t] for t in ts]), full[ts],
                             "paged decode rows")


def test_int8_layout_within_pinned_band(gpt2):
    """Matrix row 3: the int8 KV layout's decode logits sit inside the
    pinned tolerance band of the fp layout, and greedy argmax agrees on
    >= KV_INT8_ARGMAX_AGREEMENT of positions — the precision the
    searched bandwidth win costs, made explicit."""
    ff, cfg = gpt2
    rng = np.random.default_rng(2)
    seq = rng.integers(0, cfg.vocab_size, size=(1, 24)).astype(np.int32)
    fp = _teacher_forced_paged(ff, seq, 6, cfg.seq_len, kv_block_size=8)
    q8 = _teacher_forced_paged(ff, seq, 6, cfg.seq_len, kv_block_size=8,
                               kv_dtype="int8")
    worst = 0.0
    agree = total = 0
    for t in fp:
        worst = max(worst, float(np.max(np.abs(fp[t] - q8[t]))))
        agree += int(np.argmax(fp[t]) == np.argmax(q8[t]))
        total += 1
    assert worst <= KV_INT8_LOGIT_BAND, \
        f"int8 logit error {worst:.4f} outside the pinned band " \
        f"{KV_INT8_LOGIT_BAND}"
    assert agree / total >= KV_INT8_ARGMAX_AGREEMENT, \
        f"int8 greedy argmax agreement {agree}/{total}"


def test_speculative_greedy_token_identical(gpt2):
    """Matrix row 4: speculative greedy output == the non-speculative
    baseline, token for token (verification runs the same exact-score
    forward tier-1 holds decode logits to ⇒ equal argmax), for both
    a useless random drafter and the perfect drafter (the target
    itself, acceptance 1.0 — every round commits gamma + 1 tokens)."""
    ff, cfg = gpt2
    drafter, _ = _build(hidden=16, heads=2, layers=1, seed=7)
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    base = eng.generate(PROMPTS, max_new_tokens=10)
    spec = SpeculativeDecoder(ff, drafter, gamma=3,
                              max_context=cfg.seq_len,
                              controller=eng.admission)
    assert spec.generate(PROMPTS, max_new_tokens=10) == base
    assert spec.stats.spec_rounds > 0
    assert spec.stats.acceptance_rate() is not None
    # perfect drafter: acceptance 1.0, and FEWER verification rounds
    # than tokens (the speedup mechanism, observable on CPU as round
    # counts rather than wall clock)
    perfect = SpeculativeDecoder(ff, ff, gamma=3,
                                 max_context=cfg.seq_len)
    assert perfect.generate(PROMPTS, max_new_tokens=10) == base
    st = perfect.stats
    assert st.acceptance_rate() == 1.0
    assert st.spec_rounds < st.tokens_generated, \
        "perfect drafter should commit >1 token per round"
    # the EWMA admission model saw the speculative cost + acceptance
    assert eng.admission.spec_acceptance is not None
    assert eng.admission.token_cost_ms > 0


def test_fleet_context_overflow_preempts_not_crashes(gpt2):
    """Regression (review finding): a request beyond the position-table
    bound dispatched through the FLEET must be ledgered (preempted),
    not crash the router with an uncaught ContextOverflowError — other
    in-flight requests complete normally."""
    from flexflow_tpu.serving import ServingFleet

    ff, cfg = gpt2
    fleet = ServingFleet(ff, n_replicas=2, n_slots=2,
                         max_decode_len=1024)
    outs = fleet.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    fleet2 = ServingFleet(ff, n_replicas=2, n_slots=2,
                          max_decode_len=1024)
    outs = fleet2.generate([[1, 2, 3]], max_new_tokens=cfg.seq_len + 8)
    assert outs[0] == []  # ledgered, not crashed
    assert sum(fleet2.stats.outcomes.values()) == 1


def test_speculative_context_bounded_by_position_table(gpt2):
    """Regression (review finding): the speculative decoder's scoring
    bound consults the position table — a default max_context above the
    table would silently alias position rows in verification."""
    ff, cfg = gpt2
    spec = SpeculativeDecoder(ff, ff, gamma=2, max_context=1024)
    assert spec.max_context == cfg.seq_len
    # generation truncates at the bound instead of scoring past it
    out = spec.generate([[1, 2, 3]], max_new_tokens=cfg.seq_len + 50)
    assert 0 < len(out[0]) <= cfg.seq_len - 3


def test_speculative_refuses_temperature(gpt2):
    ff, cfg = gpt2
    spec = SpeculativeDecoder(ff, ff, gamma=2, max_context=cfg.seq_len)
    with pytest.raises(NotImplementedError, match="greedy-only"):
        spec.generate([[1, 2]], max_new_tokens=4, temperature=0.7)


def test_speculative_rejects_vocab_mismatch(gpt2):
    ff, cfg = gpt2
    other, _ = _build(vocab=53, seed=9)
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeDecoder(ff, other)


def test_fleet_migration_paged_bitwise(gpt2):
    """Matrix row 5: a mid-decode replica kill migrates PAGED-KV streams
    to the survivor bitwise-unchanged — the re-prefill from committed
    tokens rebuilds block tables on the survivor's own allocator."""
    from flexflow_tpu.resilience import FleetChaosPlan
    from flexflow_tpu.serving import ServingFleet

    ff, cfg = gpt2
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(3, 7))).tolist()
               for _ in range(6)]
    base = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len).generate(
                             prompts, max_new_tokens=8)
    fleet = ServingFleet(ff, n_replicas=2, n_slots=2,
                         max_decode_len=cfg.seq_len)
    outs = fleet.generate(prompts, max_new_tokens=8,
                          chaos=FleetChaosPlan(kill_replica_at={4: 0}))
    assert outs == base, "migrated paged continuations diverged"
    assert fleet.stats.migrations >= 1
    assert fleet.stats.outcomes == {"ok": 6}


# ------------------------------------------------------- allocator + pool
def test_block_allocator_laws():
    a = BlockAllocator(n_blocks=9, block_size=4)
    assert a.n_usable == 8 and a.in_use == 0
    assert a.blocks_needed(1) == 1 and a.blocks_needed(4) == 1
    assert a.blocks_needed(5) == 2 and a.blocks_needed(32) == 8
    got = a.alloc(3)
    assert got == [1, 2, 3] and a.in_use == 3
    assert a.alloc(6) is None, "over-allocation must refuse, not raise"
    assert a.in_use == 3
    a.free([2])
    assert a.alloc(6) == [4, 5, 6, 7, 8, 2]  # FIFO free list
    assert a.in_use == 8 and a.blocks_hwm == 8
    a.reset()
    assert a.in_use == 0 and len(a.free_blocks) == 8
    with pytest.raises(AssertionError):
        BlockAllocator(n_blocks=1, block_size=4)  # garbage block only


def test_small_pool_decouples_occupancy_and_serializes(gpt2):
    """Occupancy accounting: a pool holding exactly ONE max-size request
    still completes a multi-request workload (admission waits on free
    BLOCKS; recycling unblocks it) with streams identical to the
    full-pool run."""
    ff, cfg = gpt2
    mb = -(-cfg.seq_len // 8)
    eng_small = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                              kv_block_size=8,
                              kv_pool_blocks=mb + 1)
    eng_full = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                             kv_block_size=8)
    out_small = eng_small.generate(PROMPTS, max_new_tokens=6)
    out_full = eng_full.generate(PROMPTS, max_new_tokens=6)
    assert out_small == out_full
    # ISSUE 14: finished prompts' blocks are retained by the prefix trie
    # (that's the cache) — live accounting must equal exactly the trie's
    # holdings, and dropping the trie must leave zero leaked blocks
    for eng in (eng_small, eng_full):
        assert eng.block_allocator.in_use == eng._prefix.n_blocks, \
            "blocks leaked beyond the prefix trie's holdings"
        eng._prefix.clear(free=True)
        assert eng.block_allocator.in_use == 0, "blocks leaked"
    assert eng_small.block_allocator.blocks_hwm <= mb


def test_request_larger_than_pool_refused_at_submit():
    """A request the WHOLE pool cannot hold must refuse at submit (the
    alternative is an admission deadlock). The engine's FF006 check
    already refuses such pools outright; this pins the scheduler-level
    backstop for foreign schedulers."""
    sched = ContinuousBatchScheduler(n_slots=2, max_len=64)
    sched.allocator = BlockAllocator(n_blocks=3, block_size=8)
    req = Request(prompt=np.arange(10, dtype=np.int32),
                  max_new_tokens=16)
    with pytest.raises(ValueError, match="KV blocks"):
        sched.submit(req)


def test_context_overflow_is_serving_rejection(gpt2):
    """ISSUE 12 satellite: position-table overflow rejects at admission
    with a typed ServingRejection naming the max supported context."""
    ff, cfg = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=1024)
    assert eng.max_context == cfg.seq_len
    # a rejection at the door still lands in the ledger (outcome shed)
    outs = eng.generate([[1, 2, 3]], max_new_tokens=cfg.seq_len + 4)
    assert outs[0] == []
    assert eng.stats.outcomes.get("shed") == 1
    sched = ContinuousBatchScheduler(n_slots=2, max_len=1024)
    req = Request(prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=cfg.seq_len)
    with pytest.raises(ContextOverflowError,
                       match="max supported context") as ei:
        eng.admit(sched, req)
    assert isinstance(ei.value, ServingRejection)


def test_kv_bytes_accounting_paged_below_ring(gpt2):
    """The decode bytes-read/token column: the engine's analytic read
    traffic is strictly below the O(max_len) bill a per-slot ring of
    ``max_decode_len`` rows would pay (every slot's full extent, every
    step), for short requests, and it lands in the stats summary."""
    ff, cfg = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                        kv_block_size=8)
    eng.generate(PROMPTS, max_new_tokens=6)
    st = eng.stats
    ring_bill = (st.decode_steps * eng.n_slots * cfg.seq_len
                 * eng._kv_row_bytes())
    assert 0 < st.kv_bytes_read < ring_bill
    p = st.kv_bytes_per_token()
    assert p is not None and p < ring_bill / st.tokens_generated
    assert "kv_bytes_per_token" in st.summary()


# ------------------------------------------------------ flash-decode kernel
def _packed_pool(rng, n_blocks, heads, block_size, kd, vd):
    """A random f32 pool entry and its int8 twin, built the way the
    slot writer builds them: one request's contiguous K and V scattered
    over every block of the pool."""
    import jax.numpy as jnp

    from flexflow_tpu.serving.kvcache import new_kv_pool, scatter_prefill_kv

    kv = tuple(jnp.asarray(rng.normal(
        size=(1, heads, n_blocks * block_size, d)).astype(np.float32))
        for d in (kd, vd))
    row = jnp.arange(n_blocks, dtype=jnp.int32)
    return tuple(scatter_prefill_kv(
        new_kv_pool(kv, n_blocks, block_size, dt), kv, row, block_size)
        for dt in ("native", "int8"))


@pytest.mark.parametrize("heads,kd,vd", [(4, 64, 64), (25, 64, 64),
                                         (3, 128, 128), (2, 16, 48)],
                         ids=["4x64", "25x64", "3x128-256-lanes",
                              "kd-not-vd"])
def test_flash_decode_interpret_matches_reference(heads, kd, vd):
    """The Pallas split-K kernel (interpret mode on CPU) on the packed
    pool matches the masked-gather reference for fp and int8 pools,
    including slots with very different true lengths (the
    clamp-dead-blocks path), at GPT-2 XL's 25 heads of 64, at a width of
    256 lanes and where K and V differ in width."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_decode import (_reference_decode,
                                                   flash_decode_pool)

    rng = np.random.default_rng(0)
    S, BS, MB = 3, 8, 4
    pool, (q8, s8) = _packed_pool(rng, 1 + S * MB, heads, BS, kd, vd)
    tables = np.zeros((S, MB), np.int32)
    tables[0, :2] = [1, 2]
    tables[1, :4] = [3, 4, 5, 6]
    tables[2, :1] = [7]
    tables = jnp.asarray(tables)
    n_keys = jnp.asarray([13, 30, 5], jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, heads, kd)).astype(np.float32))
    sm = 1.0 / np.sqrt(kd)
    out = flash_decode_pool(q, pool, tables, n_keys, interpret=True)
    assert out.shape == (S, heads, vd)
    ref = _reference_decode()(q, pool, tables, n_keys, sm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)
    out8 = flash_decode_pool(q, q8, tables, n_keys, scales=s8,
                             interpret=True)
    ref8 = _reference_decode()(q, q8, tables, n_keys, sm, scales=s8)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref8),
                               atol=2e-6)
    # and int8 sits within a loose band of fp (quantization, not bugs)
    assert float(jnp.max(jnp.abs(out8 - ref))) < 0.1


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("block_size", [8, 16, 32])
@pytest.mark.parametrize("table_width", [3, 8, 20, 40])
def test_flash_decode_interpret_matches_reference_over_tiles(
        table_width, block_size, kv_dtype):
    """The kernel's key tile (``tile_blocks`` table entries a grid
    step) against the masked-gather reference, in interpret mode: table
    widths that are and are not a multiple of the tile (the wrapper pads
    the row with the garbage block), every block size the gate lets
    through, fp and int8 pools, and slots of 0 keys, 1, one less than /
    exactly / one more than a tile, and the full extent. A slot of 0
    keys — what the decode step hands a free slot — comes back as exact
    zeros, and every value is finite."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_decode import (_reference_decode,
                                                   flash_decode_pool,
                                                   tile_blocks)

    rng = np.random.default_rng(table_width * 100 + block_size)
    heads, kd = 2, 64
    extent = table_width * block_size
    tile = block_size * tile_blocks(
        (1, heads, block_size, 2 * kd), 1 if kv_dtype == "int8" else 4,
        table_width)
    assert 0 < tile <= extent
    n_keys = np.asarray(sorted({0, 1, max(tile - 1, 1), tile,
                                min(tile + 1, extent), extent}), np.int32)
    S = len(n_keys)
    entries = _packed_pool(rng, 1 + S * table_width, heads, block_size,
                           kd, kd)
    pool, scales = (entries[0], None) if kv_dtype == "native" \
        else entries[1]
    tables = np.zeros((S, table_width), np.int32)
    for s_, n in enumerate(n_keys):
        used = -(-int(n) // block_size)
        tables[s_, :used] = 1 + s_ * table_width + np.arange(used)
    tables, n_keys_dev = jnp.asarray(tables), jnp.asarray(n_keys)
    q = jnp.asarray(rng.normal(size=(S, heads, kd)).astype(np.float32))
    out = np.asarray(flash_decode_pool(q, pool, tables, n_keys_dev,
                                       scales=scales, interpret=True))
    assert np.all(np.isfinite(out))
    assert np.all(out[n_keys == 0] == 0.0), "a slot of no keys is zeros"
    ref = np.asarray(_reference_decode()(
        q, pool, tables, n_keys_dev, 1.0 / np.sqrt(kd), scales=scales))
    live = n_keys > 0
    np.testing.assert_allclose(out[live], ref[live], atol=2e-6)


def _dense_decode(q, pool, tables, n_keys, scale, kd, v_lanes, tokens):
    """Every form of the read, densely in float64: ``q`` ``(slots,
    tokens * pool heads * group, kd)`` against the slot's whole table row
    (the one shared row where ``tokens`` > 1), token ``t`` of a slot
    seeing ``n_keys + t`` keys; the value is the lanes after K's (the
    plain pool) or the row's first ``v_lanes``. A slot of no keys is
    zeros."""
    S, H, _ = q.shape
    _, h, bs, lanes = pool.shape
    rows = np.broadcast_to(tables, (S, tables.shape[1]))
    ext = pool[rows].transpose(0, 2, 1, 3, 4).reshape(S, h, -1, lanes)
    ext = ext.astype(np.float64)
    qg = q.astype(np.float64).reshape(S, tokens, h, H // (tokens * h), kd)
    sc = np.einsum("sthgd,shnd->sthgn", qg, ext[..., :kd]) * scale
    seen = np.arange(ext.shape[2])[None, None, :] < (
        n_keys[:, None, None] + np.arange(tokens)[None, :, None])
    seen = seen[:, :, None, None, :] & (n_keys > 0)[:, None, None, None,
                                                    None]
    sc = np.where(seen, sc, -np.inf)
    p = np.where(seen, np.exp(sc - np.where(
        seen.any(-1, keepdims=True), sc.max(-1, keepdims=True), 0.0)), 0.0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-300)
    val = ext[..., kd:] if v_lanes is None else ext[..., :v_lanes]
    return np.einsum("sthgn,shnv->sthgv", p, val).reshape(S, H, -1)


@pytest.mark.parametrize("table_width", [20, 48],
                         ids=["row-no-multiple-of-P", "row-of-3-tiles"])
@pytest.mark.parametrize("form", ["plain", "int8", "grouped", "latent",
                                  "chunk"])
def test_flash_decode_every_form_over_slot_states(form, table_width):
    """Every form of the kernel (interpret mode) over slot states in ONE
    call — a free slot of 0 keys, 1 key, a whole 256-key tile exactly,
    one key past it, several tiles, the whole row — at a table width
    that is and is not a multiple of ``P``: free slots come back exact
    zeros, every other slot matches the dense read, and the output is
    the tiled-grid kernel's (the parent's, tests/
    flash_decode_tiled_oracle.py) to the bit: same tiles, same order,
    same arithmetic."""
    import jax.numpy as jnp

    from flash_decode_tiled_oracle import tiled_flash_decode_pool
    from flexflow_tpu.kernels.flash_decode import (flash_decode_pool,
                                                   tile_blocks)

    rng = np.random.default_rng(table_width)
    bs, kd = 16, 64
    pool_heads, group, lanes, v_lanes, tokens = {
        "plain": (3, 1, 128, None, 1), "int8": (3, 1, 128, None, 1),
        "grouped": (2, 4, 128, 64, 1), "latent": (1, 8, 128, 48, 1),
        "chunk": (1, 8, 128, 48, 4)}[form]
    extent = table_width * bs
    n_keys = np.asarray(sorted({0, 1, 256, 257, extent - 19, extent}),
                        np.int32)
    if tokens > 1:   # a slot's last token sees tokens - 1 more
        n_keys = np.minimum(n_keys, extent - tokens + 1).astype(np.int32)
    S = len(n_keys)
    n_blocks = 1 + (1 if tokens > 1 else S) * table_width
    scales = None
    if form in ("plain", "int8"):
        fp, (q8, scales) = _packed_pool(rng, n_blocks, pool_heads, bs, kd,
                                        lanes - kd)
        pool, scales = (fp, None) if form == "plain" else (q8, scales)
    else:
        pool = np.zeros((n_blocks, pool_heads, bs, lanes), np.float32)
        pool[..., :kd] = rng.standard_normal(pool.shape[:-1] + (kd,))
        pool = jnp.asarray(pool)
    assert pool.dtype.itemsize == (1 if form == "int8" else 4)
    assert bs * tile_blocks(pool.shape, pool.dtype.itemsize,
                            table_width) == 256
    if tokens > 1:
        tables = 1 + rng.permutation(table_width)[None].astype(np.int32)
    else:
        tables = np.zeros((S, table_width), np.int32)
        for s_, n in enumerate(n_keys):
            used = -(-int(n) // bs)
            tables[s_, :used] = 1 + s_ * table_width + np.arange(used)
    q = rng.standard_normal(
        (S, tokens * pool_heads * group, kd)).astype(np.float32)
    args = (jnp.asarray(q), pool, jnp.asarray(tables), jnp.asarray(n_keys))
    kw = dict(sm_scale=0.2, scales=scales, v_lanes=v_lanes, tokens=tokens)
    out = np.asarray(flash_decode_pool(*args, interpret=True, **kw))
    assert np.all(np.isfinite(out))
    assert np.all(out[n_keys == 0] == 0.0), "a slot of no keys is zeros"
    dense = np.asarray(pool, np.float32)
    if scales is not None:   # K's lanes by K's scales, V's by V's
        sc = np.asarray(scales)
        dense = dense * np.where(np.arange(lanes) < kd, sc[:, 0, ..., None],
                                 sc[:, 1, ..., None])
    want = _dense_decode(q, dense, tables, n_keys, 0.2, kd, v_lanes, tokens)
    np.testing.assert_allclose(out, want, atol=3e-6)
    np.testing.assert_array_equal(
        out, np.asarray(tiled_flash_decode_pool(*args, **kw)))


def test_free_slot_cursor_stays_zero(gpt2):
    """A free slot costs the decode step nothing that grows: while one
    slot serves request after request for more decode steps than
    ``max_decode_len``, the other's cursor stays 0 (it never indexes the
    position table past its end), its table row stays all garbage, the
    garbage block it writes its discarded tokens into stays finite, and
    the live streams equal the same requests served alone."""
    import jax

    ff, cfg = gpt2
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 9, 3, 12)]
    new = 14
    alone = [ServingEngine(ff, n_slots=1, max_decode_len=cfg.seq_len,
                           kv_block_size=8, prefix_cache="off").generate(
                               [p], max_new_tokens=new)[0] for p in prompts]
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                        kv_block_size=8, prefix_cache="off")
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8,
                                     max_len=cfg.seq_len,
                                     buckets=eng.buckets)
    loop = eng.start_serve(sched)
    streams, steps = [], 0
    for i, p in enumerate(prompts):   # one at a time: a slot stays free
        req = Request(prompt=np.asarray(p, np.int32), max_new_tokens=new,
                      rng_tag=i)
        eng.admit(sched, req)
        while loop.tick():
            free = [s for s, r in enumerate(sched.slots) if r is None]
            assert free, "one request at a time leaves a slot free"
            lengths = np.asarray(eng.state.lengths)
            tables = np.asarray(eng.state.block_tables)
            assert np.all(lengths[free] == 0), (steps, lengths)
            assert np.all(tables[free] == 0), (steps, tables)
        streams.append(list(req.generated))
        steps = loop.stats.decode_steps
    loop.finish()
    assert steps > cfg.seq_len, "more decode steps than max_decode_len"
    assert streams == alone
    for entry in eng.state.caches.values():
        for leaf in jax.tree_util.tree_leaves(entry):
            if leaf.ndim >= 3:
                assert np.all(np.isfinite(np.asarray(leaf[0], np.float32)))
    # the kernel's counted steps: a grid step a slot (2 of them) and a
    # loop iteration a live tile (one tile a row here), and the live
    # ones among them
    st = loop.stats
    assert 0 < st.kv_tiles_live <= st.decode_steps
    assert st.kv_tiles_grid == st.decode_steps * 2 + st.kv_tiles_live
    assert st.decode_grid_live_share() == \
        st.kv_tiles_live / st.kv_tiles_grid
    assert st.summary()["decode_grid_live_share"] == round(
        st.decode_grid_live_share(), 4)


def test_flash_decode_of_separate_pools_packs_them():
    """``flash_decode(q, kpool, vpool, ...)``, the entry for a caller
    that holds K and V apart (the benchmark's ahead-of-time check),
    reads what the packed entry reads."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_decode import (flash_decode,
                                                   flash_decode_pool)

    rng = np.random.default_rng(1)
    pool, (q8, s8) = _packed_pool(rng, 5, 2, 8, 16, 16)
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    n_keys = jnp.asarray([11, 4], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 2, 16)).astype(np.float32))
    for p, sc in ((pool, None), (q8, s8)):
        kw = {} if sc is None else {"kscale": sc[:, 0], "vscale": sc[:, 1]}
        apart = flash_decode(q, p[..., :16], p[..., 16:], tables, n_keys,
                             interpret=True, **kw)
        packed = flash_decode_pool(q, p, tables, n_keys, scales=sc,
                                   interpret=True)
        assert np.array_equal(np.asarray(apart), np.asarray(packed))


def test_flash_decode_gate_off_tpu(monkeypatch):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import _common
    from flexflow_tpu.kernels.flash_decode import use_flash_decode
    from flexflow_tpu.kernels.kv_write import use_kv_write

    def pool(lanes, block, dtype):
        return jax.ShapeDtypeStruct((9, 4, block, lanes), dtype)

    # CPU process: both gates must refuse regardless of dims
    assert not use_flash_decode(128, 16)
    assert not use_kv_write(pool(128, 16, jnp.bfloat16))
    monkeypatch.setattr(_common, "on_tpu", lambda: True)
    # the reader, from what it can see of the pool: kd + vd a multiple
    # of 128 lanes, block_size of 8 sublanes, whatever the dtype — the
    # default block 16 serves an int8 pool through the kernel
    assert use_flash_decode(128, 16)
    assert use_flash_decode(128, 8)
    assert use_flash_decode(256, 32)
    assert not use_flash_decode(64, 16)
    assert not use_flash_decode(120, 16)
    assert not use_flash_decode(128, 12)
    # the writer rewrites whole blocks in place, so it asks for whole
    # tiles: block_size a whole sublane tile of the pool's dtype
    assert use_kv_write(pool(128, 16, jnp.bfloat16))
    assert use_kv_write(pool(256, 8, jnp.float32))
    assert use_kv_write(pool(128, 32, jnp.int8))
    assert not use_kv_write(pool(128, 8, jnp.bfloat16))
    assert not use_kv_write(pool(128, 16, jnp.int8))
    assert not use_kv_write(pool(64, 16, jnp.bfloat16))
    assert not use_kv_write(pool(120, 16, jnp.float32))


# ------------------------------------------------------ satellite: FF006
def test_check_paged_kv_shape_laws(gpt2):
    """FF006 paged extension: misconfigured block tables/pools are
    rejected statically with the rule ID; a clean config passes."""
    from flexflow_tpu.analysis import check_paged_kv

    ff, _cfg = gpt2
    clean = check_paged_kv(ff.pcg, block_size=8, pool_blocks=17,
                           max_blocks_per_slot=4, max_context=32)
    assert clean == []
    short_table = check_paged_kv(ff.pcg, block_size=8, pool_blocks=17,
                                 max_blocks_per_slot=2, max_context=32)
    assert any("block table covers" in d.message for d in short_table)
    assert all(d.rule_id == "FF006" for d in short_table)
    tiny_pool = check_paged_kv(ff.pcg, block_size=8, pool_blocks=3,
                               max_blocks_per_slot=4, max_context=32)
    assert any("deadlock" in d.message for d in tiny_pool)
    bad_shard = check_paged_kv(ff.pcg, block_size=8, pool_blocks=17,
                               max_blocks_per_slot=4, max_context=32,
                               kv_layout="sharded", tp=7)
    assert any("num_heads" in d.message for d in bad_shard)
    # the engine runs the check at construction: a pool too small for
    # one request dies with the rule ID, zero compiles
    from flexflow_tpu.analysis import StaticAnalysisError

    with pytest.raises(StaticAnalysisError, match="FF006"):
        ServingEngine(ff, n_slots=2, max_decode_len=32, kv_block_size=8,
                      kv_pool_blocks=3)


def test_garbage_block_never_poisoned(gpt2):
    """White-box: the chaos poisoner NaNs exactly a LIVE victim's
    occupied blocks — never the shared garbage block (whose finiteness
    the masked-read contract depends on), and a free/cleared
    slot is a no-op (its table row points only at garbage)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.resilience.chaos import poison_decode_state

    ff, cfg = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                        kv_block_size=8)
    # make slot 0 LIVE through the real machinery (prefill + admission
    # scatter), slot 1 free
    prompt = np.asarray(PROMPTS[0], np.int32)
    bucket = next(b for b in eng.buckets if b >= len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    _lg, _last, cache = eng._prefill_fn(bucket)(
        ff.params, [jnp.asarray(padded)],
        jnp.asarray([len(prompt)], np.int32))
    eng._ensure_state(cache)
    blocks = eng.block_allocator.alloc(2)
    row = np.zeros((eng.max_blocks_per_slot,), np.int32)
    row[:2] = blocks
    eng._write_slot(cache, 0, len(prompt), 1, table_row=row)
    state = eng.state
    tables = np.asarray(state.block_tables)
    assert tables[0, 0] == blocks[0]
    poisoned = poison_decode_state(state, 0)
    saw_victim = False
    for entry in poisoned.caches.values():
        for leaf in jax.tree_util.tree_leaves(entry):
            if leaf.ndim >= 3 and jnp.issubdtype(leaf.dtype,
                                                 jnp.floating):
                assert bool(jnp.all(jnp.isfinite(leaf[0]))), \
                    "garbage block was poisoned"
                assert not bool(jnp.all(jnp.isfinite(leaf[blocks[0]])))
                saw_victim = True
    assert saw_victim
    # free slot (all-garbage table row): poisoning it is a pool no-op
    reposoned = poison_decode_state(poisoned, 1)
    for name, entry in reposoned.caches.items():
        for a, b in zip(jax.tree_util.tree_leaves(entry),
                        jax.tree_util.tree_leaves(poisoned.caches[name])):
            if a.ndim >= 3:
                assert np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True)


def test_freed_slot_clears_table_row_and_cursor(gpt2):
    """Regression (review finding): when a slot is freed, its
    device-side block-table row resets to GARBAGE and its cursor to 0 —
    a stale row would keep scattering the freed slot's discarded tokens
    into blocks the allocator already handed to a NEW request in a
    different slot (silent KV corruption). Plus the churn stress: many
    short/long requests through a minimal pool must match, stream for
    stream, each request served alone on a fresh engine."""
    ff, cfg = gpt2
    mb = -(-cfg.seq_len // 8)
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                        kv_block_size=8,
                        kv_pool_blocks=mb + 1)
    eng.generate(PROMPTS[:2], max_new_tokens=4)
    tables = np.asarray(eng.state.block_tables)
    lengths = np.asarray(eng.state.lengths)
    assert np.all(tables == 0), "freed slots kept stale table rows"
    assert np.all(lengths == 0), "freed slots kept stale cursors"
    # churn: interleaved short + LONG prompts (a long prompt admitted
    # into freed blocks is exactly the corruption scenario)
    rng = np.random.default_rng(5)
    churn = []
    for i in range(8):
        n = 24 if i % 2 else 3
        churn.append(rng.integers(0, cfg.vocab_size, size=n).tolist())
    base = [ServingEngine(ff, n_slots=1, max_decode_len=cfg.seq_len,
                          kv_block_size=8, prefix_cache="off").generate(
                              [p], max_new_tokens=7)[0] for p in churn]
    eng2 = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                         kv_block_size=8,
                         kv_pool_blocks=2 * mb + 1)
    assert eng2.generate(churn, max_new_tokens=7) == base
    # in-use == the prefix trie's retained blocks (ISSUE 14), zero once
    # the trie is dropped
    assert eng2.block_allocator.in_use == eng2._prefix.n_blocks
    eng2._prefix.clear(free=True)
    assert eng2.block_allocator.in_use == 0


def test_serving_search_kv_dtype_axis(gpt2):
    """The serving search sweeps kv_dtype next to the KV layout; int8
    candidates price strictly less KV-stream time, the winner records
    its dtype, and --kv-dtype pins the axis."""
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.serving import serving_search

    ff, _cfg = gpt2
    machine = TPUMachineModel.from_generation("v5e", 8)
    plan = serving_search(ff.pcg, ff.config, 8, machine=machine)
    dtypes = {c.kv_dtype for c in plan.ranked}
    assert dtypes == {"native", "int8"}
    # int8 must beat native at the same (mesh, layout): less KV stream
    by_key = {}
    for c in plan.ranked:
        by_key[(tuple(c.mesh_shape), c.layout, c.kv_dtype)] = c
    for (mesh, layout, dt), c in by_key.items():
        if dt == "int8":
            twin = by_key.get((mesh, layout, "native"))
            if twin is not None:
                assert c.sim_decode_ms <= twin.sim_decode_ms
    assert plan.kv_dtype in ("native", "int8")
    ff.config.kv_dtype = "int8"
    try:
        pinned = serving_search(ff.pcg, ff.config, 8, machine=machine)
        assert {c.kv_dtype for c in pinned.ranked} == {"int8"}
    finally:
        ff.config.kv_dtype = "native"
