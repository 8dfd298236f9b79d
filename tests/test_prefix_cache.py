"""Prefix amortization (ISSUE 14, docs/serving.md "Prefix cache &
chunked prefill"): the radix-tree prefix cache over copy-on-write paged
blocks, chunked prefill, and prefix-aware fleet routing.

The acceptance contracts, all CPU-deterministic:

* a request admitted behind a trie hit produces the IDENTICAL token
  stream to a cold run, solo and co-batched, with
  ``prefill_tokens_computed`` strictly lower and zero block leaks after
  eviction churn;
* COW divergence isolation — a writer's clone never perturbs the
  sharer's rows;
* chunked-prefill streams equal to one-shot prefill's, next-token
  logits within the stated tolerance (tests/serving_oracle.py);
* allocator refcount laws (alloc/share/free round trips, typed
  double-free/share-after-free errors, zero leaks under churn);
* fleet migration re-prefills consult the survivor's trie, and fleet
  dispatch routes by cache affinity;
* FF006 chunk shape laws reject misconfigurations with zero compiles.
"""
import os
import sys

import numpy as np
import pytest

from flexflow_tpu import (DataType, FFConfig, FFModel, LossType,
                          SGDOptimizer)
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.serving import (BlockAccountingError, BlockAllocator,
                                  PrefixCache, ServingEngine,
                                  ServingFleet)
from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                            Request)
from serving_oracle import assert_matches_reference


def _build(seq_len=64, seed=42):
    # the GPT2Config.tiny family (hidden 64 / 4 heads) at a longer
    # sequence so prompts can span several KV blocks
    cfg = GPT2Config(batch_size=2, seq_len=seq_len, hidden=64,
                     num_heads=4, num_layers=2, intermediate=128,
                     vocab_size=100)
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


SYS_PROMPT = list(np.random.default_rng(7).integers(1, 99, size=20))
PROMPTS = [SYS_PROMPT + [5, 6, 7], SYS_PROMPT + [8, 9],
           SYS_PROMPT + [5, 6, 1, 2]]


def _engine(ff, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_decode_len", 64)
    kw.setdefault("kv_block_size", 8)
    return ServingEngine(ff, **kw)


def _cold(ff, prompts, max_new=6, **kw):
    return _engine(ff, prefix_cache="off", **kw).generate(
        prompts, max_new_tokens=max_new)


# ------------------------------------------------------- allocator laws
def test_allocator_refcount_laws():
    a = BlockAllocator(n_blocks=9, block_size=4)
    blocks = a.alloc(3)
    assert blocks == [1, 2, 3] and a.in_use == 3
    assert all(a.refcount(b) == 1 for b in blocks)
    a.share(blocks[:2])
    assert a.refcount(1) == 2 and a.refcount(3) == 1
    a.free(blocks)  # drops to [1, 1, 0] — block 3 returns
    assert a.in_use == 2 and a.refcount(3) == 0
    a.free([1, 2])
    assert a.in_use == 0 and len(a.free_blocks) == 8
    # typed laws: double-free, share-after-free, garbage-block touch
    with pytest.raises(BlockAccountingError, match="double free"):
        a.free([1])
    with pytest.raises(BlockAccountingError, match="free"):
        a.share([2])
    with pytest.raises(BlockAccountingError, match="garbage"):
        a.share([0])
    with pytest.raises(BlockAccountingError, match="outside the pool"):
        a.free([99])


def test_allocator_churn_property():
    """Property-style churn: random alloc/share/free sequences keep the
    conservation law (in_use + free == usable, refcounts consistent)
    and end with zero leaks."""
    rng = np.random.default_rng(0)
    a = BlockAllocator(n_blocks=17, block_size=4)
    live = []  # (block, refs) — refs we still owe a free() for
    for _ in range(400):
        op = rng.integers(0, 3)
        if op == 0:
            got = a.alloc(int(rng.integers(1, 4)))
            if got is not None:
                live.extend((b, 1) for b in got)
        elif op == 1 and live:
            i = int(rng.integers(len(live)))
            b, r = live[i]
            a.share([b])
            live[i] = (b, r + 1)
        elif op == 2 and live:
            i = int(rng.integers(len(live)))
            b, r = live.pop(i)
            a.free([b])
            if r > 1:
                live.append((b, r - 1))
        assert a.in_use + len(a.free_blocks) == a.n_usable
        for b in range(1, a.n_blocks):
            owed = sum(r for blk, r in live if blk == b)
            assert a.refcount(b) == owed
    for b, r in live:
        a.free([b] * r)
    assert a.in_use == 0 and not a.leaked()


# ------------------------------------------------------------- trie unit
def test_trie_match_insert_upgrade_evict():
    a = BlockAllocator(n_blocks=33, block_size=4)
    trie = PrefixCache(a, block_size=4)
    toks = list(range(10, 24))  # 14 tokens: 3 full blocks + tail(2)
    blocks = a.alloc(4)
    adopted = trie.insert(toks, blocks)
    assert adopted == 4 and trie.n_blocks == 4
    assert all(a.refcount(b) == 2 for b in blocks)
    # exact full match, capped below the full prompt
    got, n = trie.match(toks, cap=13)
    assert n == 13 and got == blocks  # partial credit on the tail node
    # sub-block floor: a 3-token match is a miss
    got, n = trie.match([10, 11, 12, 99], cap=3)
    assert (got, n) == ([], 0)
    # divergent partial credit inside a full block
    got, n = trie.match(toks[:6] + [77, 78], cap=8)
    assert n == 6 and got == blocks[:2]
    # peek: no LRU mutation, same answer
    assert trie.peek(toks, cap=13) == 13
    # tail upgrade: longer evidence replaces the partial node's block
    toks2 = toks + [50]  # extends the 2-token tail to (22, 23, 50)
    b2 = a.alloc(4)
    trie.insert(toks2, b2)
    assert trie.n_blocks == 4  # upgraded in place, not a sibling
    got, n = trie.match(toks2, cap=15)
    assert n == 15 and got[-1] == b2[3]
    assert a.refcount(blocks[3]) == 1  # trie ref released on upgrade
    # release the requests' own refs; only the trie holds the 4 nodes
    a.free(blocks)
    a.free(b2)
    assert sorted(a.leaked()) == sorted(blocks[:3] + [b2[3]])
    # LRU eviction: leaves at refcount 1 go first, parents follow
    freed = trie.evict(10)
    assert freed == 4 and trie.n_blocks == 0
    assert trie.evictions == 4 and not a.leaked()


def test_trie_retention_cap():
    a = BlockAllocator(n_blocks=65, block_size=4)
    trie = PrefixCache(a, block_size=4, max_blocks=3)
    for i in range(4):
        toks = [100 * i + j for j in range(8)]
        blocks = a.alloc(2)
        trie.insert(toks, blocks)
        a.free(blocks)
    assert trie.n_blocks <= 3 and trie.evictions >= 1


# ------------------------------------------------- bitwise hit contracts
def test_prefix_hit_stream_bitwise_and_cheaper(gpt2):
    """Acceptance: a trie-hit admission's stream is bitwise the cold
    run's, with prefill_tokens_computed strictly lower
    and the reuse ledger filled."""
    ff, _cfg = gpt2
    cold = _cold(ff, PROMPTS)
    eng = _engine(ff)
    r1 = eng.generate(PROMPTS, max_new_tokens=6)
    computed1 = eng.stats.prefill_tokens_computed
    r2 = eng.generate(PROMPTS, max_new_tokens=6)
    s2 = eng.stats
    assert r1 == cold and r2 == cold
    assert s2.prefix_hits == len(PROMPTS)
    assert s2.prefill_tokens_computed < computed1
    assert s2.prefix_tokens_reused > 0
    assert (s2.prefix_reuse_rate() or 0) > 0.5
    # full-prompt hits leave exactly the final token to compute
    assert s2.prefill_tokens_computed == len(PROMPTS)


def test_prefix_hit_cobatched_isolation(gpt2):
    """A hit admitted co-batched with unrelated live streams: the hit is
    bitwise its cold self AND the neighbors are bitwise theirs."""
    ff, _cfg = gpt2
    others = [[9, 8, 7, 6, 5, 4, 3, 2, 1], [33, 44, 55]]
    mixed = [PROMPTS[0], others[0], PROMPTS[1], others[1]]
    cold = _cold(ff, mixed)
    eng = _engine(ff)
    eng.generate([SYS_PROMPT + [1]], max_new_tokens=4)  # warm the trie
    out = eng.generate(mixed, max_new_tokens=6)
    assert out == cold
    assert eng.stats.prefix_hits >= 2


def test_cow_divergence_isolation(gpt2):
    """Copy-on-write: B shares A's partially-filled tail block, then
    diverges — B's clone write must never perturb A's rows (A's prompt
    re-served later is still bitwise its cold self), and B's stream is
    bitwise B-cold."""
    ff, _cfg = gpt2
    a_prompt = SYS_PROMPT[:18]            # blocks: 2 full + tail(2)
    b_prompt = SYS_PROMPT[:17] + [91, 92]  # shares 17, diverges in tail
    cold_a = _cold(ff, [a_prompt])
    cold_b = _cold(ff, [b_prompt])
    eng = _engine(ff)
    assert eng.generate([a_prompt], max_new_tokens=6) == cold_a
    out_b = eng.generate([b_prompt], max_new_tokens=6)
    assert out_b == cold_b, "COW writer diverged from its cold stream"
    assert eng.stats.prefix_hits == 1
    # the sharer's rows survived the writer's divergence bitwise
    assert eng.generate([a_prompt], max_new_tokens=6) == cold_a, \
        "sharer's cached rows were perturbed by the COW writer"


def test_prefix_eviction_churn_zero_leaks(gpt2):
    """Acceptance: under a pool small enough to force LRU trie eviction,
    streams stay bitwise-cold and no block leaks (in_use == exactly the
    trie's retained set; zero once dropped)."""
    ff, _cfg = gpt2
    rng = np.random.default_rng(3)
    churn = [rng.integers(1, 99, size=12).tolist() for _ in range(6)]
    cold = _cold(ff, churn)
    mb = -(-64 // 8)
    eng = _engine(ff, n_slots=1, kv_pool_blocks=mb + 1)
    assert eng.generate(churn, max_new_tokens=6) == cold
    assert eng.stats.cache_evictions > 0, \
        "pool pressure never exercised trie eviction"
    alc = eng.block_allocator
    assert alc.in_use == eng._prefix.n_blocks
    eng._prefix.clear(free=True)
    assert alc.in_use == 0 and not alc.leaked()


# --------------------------------------------------------- chunked prefill
def test_chunked_prefill_matches_one_shot(gpt2):
    """Acceptance: chunked-prefill streams are the one-shot prefill's
    token for token, its next-token logits match the one-shot's within
    the stated tolerance with the same greedy token
    (tests/serving_oracle.py); the chunk program compiles once per
    shape."""
    import jax

    ff, _cfg = gpt2
    rng = np.random.default_rng(4)
    longs = [rng.integers(1, 99, size=40).tolist(),
             rng.integers(1, 99, size=33).tolist(), [7, 8, 9]]
    cold = _cold(ff, longs)
    eng = _engine(ff, prefix_cache="off", prefill_chunk_tokens=16)
    out = eng.generate(longs, max_new_tokens=6)
    assert out == cold
    # 40 -> 3 chunks, 33 -> 3 chunks; the 3-token prompt stays classic
    assert eng.stats.chunked_prefills == 6
    # one-compile-per-shape law: the chunk program is warm after the
    # first run — a second run through THIS engine adds zero cache
    # entries (the executor-shared jit may hold entries for OTHER
    # engines' pool shapes; the law is per (shape, engine))
    fn = eng.executor._serving_jits.get(("chunk", 16, 64, 8, "native"))
    assert fn is not None
    warm = fn._cache_size()
    assert eng.generate(longs, max_new_tokens=6) == cold
    assert fn._cache_size() == warm, "chunk program recompiled"
    # logits-level: the final chunk's next-token logits == one-shot's
    import jax.numpy as jnp

    prompt = np.asarray(longs[0], np.int32)
    eff = len(prompt)
    bucket = next(b for b in eng.buckets if b >= eff)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :eff] = prompt
    _lg, last_ref, _cache = eng._prefill_fn(bucket)(
        ff.params, [jnp.asarray(ids)], jnp.asarray([eff], np.int32))
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8,
                                     buckets=eng.buckets, max_len=64)
    eng._attach_kv_accounting(sched)
    req = Request(prompt=prompt, max_new_tokens=6)
    sched.submit(req)
    act = sched.next_action()
    assert act == "chunked" or act[0] == "prefill_chunk"
    last = None
    while True:
        act = sched.next_action()
        if act is None or act[0] != "prefill_chunk":
            break
        _, r, slot, start, n, shape = act
        ids_c = np.zeros((1, shape), np.int32)
        ids_c[0, :n] = prompt[start:start + n]
        last, eng.state = eng._chunk_fn(shape)(
            ff.params, [jnp.asarray(ids_c)], eng.state,
            jnp.asarray(eng._table_row_for(r), jnp.int32),
            jnp.int32(start), jnp.int32(n))
        if sched.chunk_done(slot, n):
            break
    assert last is not None
    assert_matches_reference(np.asarray(jax.device_get(last)),
                             np.asarray(jax.device_get(last_ref)),
                             "chunked next-token logits vs one-shot")


def test_chunk_actions_interleave_with_decode():
    """Scheduler law (no device): a long prompt's chunks alternate with
    the other slots' decode steps — the head-of-line stall is gone by
    construction."""
    sched = ContinuousBatchScheduler(n_slots=2, max_queue=8, max_len=64)
    sched.allocator = BlockAllocator(n_blocks=17, block_size=8)
    sched.chunk_tokens = 8
    short = Request(prompt=np.asarray([1, 2, 3], np.int32),
                    max_new_tokens=4)
    long_r = Request(prompt=np.asarray(list(range(1, 33)), np.int32),
                     max_new_tokens=4)
    sched.submit(long_r)
    sched.submit(short)
    trail = []
    for _ in range(12):
        act = sched.next_action()
        if act is None:
            break
        kind = act[0]
        trail.append(kind)
        if kind == "prefill":
            _, r, slot, _b = act
            r.prefill_pos = r.prefill_target  # engine completes it
        elif kind == "prefill_chunk":
            _, r, slot, start, n, _shape = act
            sched.chunk_done(slot, n)
        else:  # decode advances every live slot one token
            for slot, r in act[1]:
                if sched.commit_token(slot, 1):
                    break
    # the long prompt chunked; the short one-shot; decodes interleaved
    # between chunks instead of waiting for the whole long prefill
    assert "prefill_chunk" in trail and "decode" in trail
    first_chunk = trail.index("prefill_chunk")
    last_chunk = len(trail) - 1 - trail[::-1].index("prefill_chunk")
    assert "decode" in trail[first_chunk:last_chunk], \
        f"no decode between chunks: {trail}"
    assert long_r.prefill_pos == long_r.prefill_target == 32


# ------------------------------------------------------------ fleet layer
def test_fleet_affinity_routing(gpt2):
    """Dispatch routes a shared-prefix request to the replica whose trie
    holds its longest prefix, tie-broken by the load score."""
    ff, _cfg = gpt2
    fleet = ServingFleet(ff, n_replicas=2, n_slots=2, max_decode_len=64)
    fleet.generate([SYS_PROMPT + [1]], max_new_tokens=4)
    # replica 0 served (and cached) the system prompt; the follow-ups
    # must all chase the warm trie despite round-robin-friendly load
    fleet.generate([SYS_PROMPT + [2], SYS_PROMPT + [3]],
                   max_new_tokens=4)
    assert fleet.stats.affinity_hits >= 2
    assert fleet.stats.affinity_tokens >= 2 * 16
    assert fleet.stats.dispatches[0] == 3, fleet.stats.dispatches


def test_fleet_migration_rehits_survivor_trie(gpt2):
    """Acceptance: a migrated stream's re-prefill consults the
    survivor's trie (prefix hit on the survivor) and continues with
    unchanged tokens."""
    from flexflow_tpu.resilience import FleetChaosPlan

    ff, _cfg = gpt2
    p0 = SYS_PROMPT + [1]
    p1 = SYS_PROMPT + [2]
    cold = _cold(ff, [p0], max_new=10) + _cold(ff, [p1], max_new=10)
    fleet = ServingFleet(ff, n_replicas=2, n_slots=1, max_decode_len=64)
    # both replicas serve (and cache) the shared prefix: two concurrent
    # requests with 1 slot each split across the fleet
    warm = fleet.generate([p0, p1], max_new_tokens=10)
    assert warm == cold
    assert all(d > 0 for d in fleet.stats.dispatches)
    # now kill replica 0 mid-decode: its stream migrates, re-prefilling
    # prompt+committed tokens on replica 1 — whose trie holds the prefix
    hits1_before = fleet.replicas[1].sched.prefix_hits \
        if fleet.replicas[1].sched else 0
    # fleet ticks are cumulative across runs: script the kill a few
    # ticks into THIS run, while replica 0's stream is mid-decode
    kill_tick = fleet.tick_no + 4
    outs = fleet.generate([p0, p1], max_new_tokens=10,
                          chaos=FleetChaosPlan(
                              kill_replica_at={kill_tick: 0}))
    assert outs == cold, "migrated stream diverged from cold truth"
    assert fleet.stats.migrations >= 1
    assert fleet.replicas[1].sched is not None
    assert fleet.replicas[1].sched.prefix_hits > hits1_before, \
        "the survivor's trie was not consulted by the migration"


def test_poisoned_prefix_purged_from_trie(gpt2):
    """Decode poisoning NaNs the victim's blocks IN PLACE — including
    prompt blocks the trie eagerly cached at prefill completion. The
    quarantine release must purge them: the victim's retry re-prefills
    clean (recovering bitwise within its budget) instead of re-matching
    its own poisoned prefix, and no later shared-prefix admission is
    served NaN KV."""
    from flexflow_tpu.resilience import ChaosPlan

    ff, _cfg = gpt2
    prompt = SYS_PROMPT + [42]  # >= one full block: eagerly cached
    cold = _cold(ff, [prompt], max_new=8)
    eng = _engine(ff)
    out = eng.generate([prompt], max_new_tokens=8,
                       chaos=ChaosPlan(poison_decode_at={2: 0}))
    assert eng.stats.quarantines >= 1
    assert out == cold, "poisoned request did not recover bitwise"
    # the poisoned-era blocks are gone from the trie; what it holds now
    # (the clean retry's adoption) serves a fresh request bitwise
    assert eng.generate([prompt], max_new_tokens=8) == cold, \
        "trie served poisoned KV to a later shared-prefix admission"


# -------------------------------------------------- static laws and flags
def test_ff006_chunk_shape_laws(gpt2):
    """FF006 (zero compiles): chunk size not a multiple of the KV block
    size, or a pool that cannot hold one max-context request plus one
    live chunk, rejects at engine construction."""
    from flexflow_tpu.analysis import StaticAnalysisError, check_paged_kv

    ff, _cfg = gpt2
    with pytest.raises(StaticAnalysisError, match="FF006") as ei:
        ServingEngine(ff, n_slots=2, max_decode_len=64, kv_block_size=8,
                      prefill_chunk_tokens=12)
    assert "multiple of" in str(ei.value)
    mb = -(-64 // 8)
    with pytest.raises(StaticAnalysisError, match="FF006") as ei:
        ServingEngine(ff, n_slots=2, max_decode_len=64, kv_block_size=8,
                      prefill_chunk_tokens=16,
                      kv_pool_blocks=mb + 1)  # no room for the chunk
    assert "plus one live" in str(ei.value)
    # the pure-function law, directly
    diags = check_paged_kv(None, block_size=8, pool_blocks=mb + 1 + 2,
                           max_blocks_per_slot=mb, max_context=64,
                           prefill_chunk_tokens=16)
    assert not diags
    diags = check_paged_kv(None, block_size=8, pool_blocks=mb + 1,
                           max_blocks_per_slot=mb, max_context=64,
                           prefill_chunk_tokens=16)
    assert diags and all(d.rule_id == "FF006" for d in diags)


def test_prefix_flag_validation():
    cfg = FFConfig()
    cfg.parse_args(["--prefix-cache", "on", "--prefill-chunk-tokens",
                    "32", "--prefix-cache-blocks", "64"])
    assert (cfg.prefix_cache, cfg.prefill_chunk_tokens,
            cfg.prefix_cache_blocks) == ("on", 32, 64)
    with pytest.raises(ValueError, match="prefix-cache expects"):
        FFConfig().parse_args(["--prefix-cache", "maybe"])
    with pytest.raises(ValueError, match="multiple of"):
        FFConfig().parse_args(["--prefill-chunk-tokens", "12"])
    with pytest.raises(ValueError, match=">= 0"):
        FFConfig().parse_args(["--prefill-chunk-tokens", "-1"])
    with pytest.raises(ValueError, match="prefix-cache on"):
        FFConfig().parse_args(["--prefix-cache-blocks", "8",
                               "--prefix-cache", "off"])


def test_lstm_graphs_gate_prefix_and_chunking():
    """ISSUE 14 scope: attention-only stateful graphs. LSTM engines get
    the prefix cache silently disabled (default) and refuse explicit
    opt-ins loudly."""
    config = FFConfig()
    config.batch_size = 4
    ff = FFModel(config)
    ids = ff.create_tensor((4, 12), dtype=DataType.DT_INT32,
                           name="pl_ids")
    t = ff.embedding(ids, 50, 16, name="pl_embed")
    t, _state = ff.lstm(t, 16, name="pl_lstm")
    ff.dense(t, 50, name="pl_head")
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    eng = ServingEngine(ff, n_slots=2, max_decode_len=12)
    assert eng._prefix is None  # default "on" silently degrades
    with pytest.raises(ValueError, match="LSTM"):
        ServingEngine(ff, n_slots=2, max_decode_len=12,
                      prefix_cache="on")
    with pytest.raises(ValueError, match="LSTM"):
        ServingEngine(ff, n_slots=2, max_decode_len=12,
                      prefill_chunk_tokens=16, kv_block_size=4)


# -------------------------------------------------- pricing, obs, resets
def test_serving_search_prices_prefill_reuse(gpt2):
    """serving_search(prefill_reuse=) scales the p99 prefill-stall term:
    a measured hit rate lowers p99, never the decode cost; the plan
    records the priced rate."""
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.serving import serving_search

    ff, _cfg = gpt2
    machine = TPUMachineModel.from_generation("v5e", 8)
    plan0 = serving_search(ff.pcg, ff.config, 8, machine=machine)
    plan6 = serving_search(ff.pcg, ff.config, 8, machine=machine,
                           prefill_reuse=0.6)
    assert plan0.prefill_reuse == 0.0 and plan6.prefill_reuse == 0.6
    assert plan6.sim_p99_ms < plan0.sim_p99_ms
    assert plan6.sim_decode_ms == plan0.sim_decode_ms
    # clamped to [0, 1]: full reuse means p99 == the decode step
    plan1 = serving_search(ff.pcg, ff.config, 8, machine=machine,
                           prefill_reuse=5.0)
    assert plan1.sim_p99_ms == pytest.approx(plan1.sim_p50_ms)


def test_prefix_telemetry_block_and_digest(gpt2, tmp_path, capsys):
    """The serving_prefix StepTelemetry block and the trace_summary
    one-line digest."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import trace_summary

    ff, _cfg = gpt2
    eng = _engine(ff)
    eng.generate(PROMPTS, max_new_tokens=4)  # warm the trie
    ff._telemetry_requested = True  # consumed per run: arm the hit run
    eng.generate(PROMPTS, max_new_tokens=4)
    tel = ff.get_telemetry()
    blk = tel.summary()["serving_prefix"]
    assert blk["hits"] == len(PROMPTS)
    assert blk["tokens_reused"] > 0 and blk["reuse_rate"] > 0.5
    f = tmp_path / "tel.json"
    tel.write(str(f))
    trace_summary.main([str(f)])
    out = capsys.readouterr().out
    assert "prefix cache: reuse" in out and "hits" in out
    ff._telemetry_requested = False


def test_pool_rebuild_and_reset_drop_trie(gpt2):
    """The trie dies with the pool: reset_decode_pool clears it (the
    allocator forgets wholesale), and a fresh _ensure_state build after
    a state loss frees its references — stale block ids must never be
    matched into a zeroed pool."""
    ff, _cfg = gpt2
    eng = _engine(ff)
    cold = _cold(ff, PROMPTS)
    eng.generate(PROMPTS, max_new_tokens=6)
    assert eng._prefix.n_blocks > 0
    eng.reset_decode_pool()
    assert eng._prefix.n_blocks == 0
    assert eng.block_allocator.in_use == 0
    # device-loss shape: state dropped WITHOUT reset — the next pool
    # build must clear the trie, returning its references
    assert eng.generate(PROMPTS, max_new_tokens=6) == cold
    assert eng._prefix.n_blocks > 0
    eng.state = None
    eng._last_tokens = None
    assert eng.generate(PROMPTS, max_new_tokens=6) == cold
    assert eng.block_allocator.in_use == eng._prefix.n_blocks


# ------------------------------------------- TTFT stamp @ commit (ISSUE 16)
def test_first_token_ms_stamps_at_commit_point():
    """ISSUE 16 satellite pin: the TTFT stamp lands at the COMMIT point
    (``commit_token``), not inside the prefill work — so any admission
    path that skips prefill compute (a full prefix hit, a hedge twin
    resuming copied tokens) still stamps the first token it commits.
    Scheduler-level, fake clock: first commit stamps, later commits
    don't move it, and the max_new_tokens=1 edge (commit and finish in
    the same call) carries both stamps."""
    t = [0.0]
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=4, max_len=32,
                                     clock=lambda: t[0])
    r = Request(prompt=np.zeros(3, np.int32), max_new_tokens=2)
    sched.submit(r)
    sched.next_action()  # admitted; prefill does NOT stamp
    assert r.first_token_ms == 0.0
    t[0] = 3.0
    sched.commit_token(0, 7)
    assert r.first_token_ms == 3.0, "stamp must land at the commit"
    t[0] = 8.0
    sched.commit_token(0, 8)  # finishes (length 2)
    assert r.first_token_ms == 3.0, "first stamp wins"
    assert r.finish_ms == 8.0
    # the one-token edge: the first commit IS the terminal commit
    r1 = Request(prompt=np.zeros(3, np.int32), max_new_tokens=1)
    sched.submit(r1)
    sched.next_action()
    t[0] = 12.0
    sched.commit_token(0, 9)
    assert r1.first_token_ms == 12.0 and r1.finish_ms == 12.0


def test_full_prefix_hit_first_token_stamped(gpt2):
    """A request admitted behind a FULL prefix hit (the trie holds its
    entire prompt; admission caps the mapped hit at effective_len - 1,
    so prefill computes exactly one suffix token) must report a real
    ``first_token_ms`` — including at max_new_tokens=1, where the
    prefill tick commits the only token the request will ever emit."""
    ff, _cfg = gpt2
    eng = _engine(ff)
    warm = SYS_PROMPT + [5, 6, 7]
    eng.generate([warm], max_new_tokens=4)  # trie now spans the prompt
    for max_new in (1, 4):
        sched = ContinuousBatchScheduler(n_slots=2, max_queue=4,
                                         max_len=eng.max_decode_len)
        eng._attach_kv_accounting(sched)
        r = Request(prompt=np.asarray(warm, np.int32),
                    max_new_tokens=max_new, rng_tag=0)
        sched.submit(r)
        eng.serve(sched)
        assert r.prefix_hit_tokens >= len(warm) - 1, \
            "test setup: expected a (capped) full-prompt trie hit"
        assert r.outcome in (None, "ok") and len(r.generated) == max_new
        assert r.first_token_ms > 0, \
            f"TTFT stamp missing on full-hit path (max_new={max_new})"
        assert r.finish_ms >= r.first_token_ms


def test_chunk_overhang_past_context_stays_finite_and_bitwise(gpt2):
    """Regression: a trie-hit suffix chunk admitted deep into the
    prompt can OVERHANG the position table (start + chunk_shape >
    seq_len — here a 40-token hit leaves a 1-token suffix under a
    32-wide chunk program, rows 40..71 against a 64-entry table).
    jnp.take's fill mode turned the pad rows' position gather into NaN
    embeddings; their k/v rows landed in the garbage block and the
    gathered extent's softmax-zero x NaN poisoned the REAL row — the
    warm rerun decoded all-zero tokens and the pool stayed NaN for
    every later request. Pad positions now clamp to the chunk's last
    real row: warm rerun bitwise, pool finite."""
    import jax

    ff, cfg = gpt2
    eng = _engine(ff, prefill_chunk_tokens=32)
    prompt = list(range(1, 42))  # block-aligned 40-token hit, suffix 1
    r1 = eng.generate([prompt], max_new_tokens=8)
    r2 = eng.generate([prompt], max_new_tokens=8)
    assert eng.stats.prefix_hits >= 1
    assert r2 == r1, "overhanging suffix chunk perturbed the warm stream"
    for entry in eng.state.caches.values():
        for leaf in jax.tree_util.tree_leaves(entry):
            assert np.isfinite(np.asarray(jax.device_get(leaf))).all(), \
                "non-finite rows leaked into the KV pool"
