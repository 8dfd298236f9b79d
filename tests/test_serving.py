"""Serving engine (ISSUE 6, flexflow_tpu/serving, docs/serving.md):
prefill/decode equivalence against the whole-sequence forward, the
continuous-batching scheduler's isolation/recycling/backpressure
invariants, the recompile-free decode contract, the serving-objective
search (latency-bounded throughput, selfchecked), elastic mid-serve
re-search, and the satellite fixes (predict tail batch, CacheOp+remat
inversion, flags, telemetry serving block)."""
import json
import os

import numpy as np
import pytest

from flexflow_tpu import (AdamOptimizer, DataType, FFConfig, FFModel,
                          LossType, SGDOptimizer)
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.models.transformer import (TransformerConfig,
                                             build_transformer_decoder)
from flexflow_tpu.serving import (ContinuousBatchScheduler, QueueFullError,
                                  Request, ServingEngine, bucket_for)
from flexflow_tpu.serving.kvcache import DecodeState
from serving_oracle import (assert_matches_reference, logit_gap,
                            logit_tolerance)


def _compile_gpt2(batch=8):
    cfg = GPT2Config.tiny(batch_size=batch)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg


@pytest.fixture(scope="module")
def gpt2():
    return _compile_gpt2()


KV_BLOCK = 8


def _pool_state(cache, prompt_len, max_len):
    """One request's prefill cache on the paged pool, built from the
    public pieces the engine's slot writer uses: a pool of ``mb`` blocks
    plus the garbage block per KV leaf, the one-row table ``[1 .. mb]``,
    the prompt's rows scattered through it. A stateful entry that is no
    KV pair (the LSTM carry) stays slot-major, and a model without KV
    entries carries an empty pool under an all-garbage table — what
    ``ServingEngine._ensure_state`` builds for such a model."""
    import jax.numpy as jnp

    from flexflow_tpu.serving.kvcache import (blocks_per_slot, new_kv_pool,
                                              scatter_prefill_kv)

    mb = blocks_per_slot(max_len, KV_BLOCK)
    row = jnp.arange(1, mb + 1, dtype=jnp.int32)
    caches, paged = {}, False
    for name, entry in cache.items():
        if isinstance(entry, tuple) and all(x.ndim == 4 for x in entry):
            paged = True
            caches[name] = scatter_prefill_kv(
                new_kv_pool(entry, mb + 1, KV_BLOCK, "native"), entry,
                row, KV_BLOCK)
        else:
            caches[name] = entry
    tables = row[None] if paged else jnp.zeros((1, mb), jnp.int32)
    return DecodeState(caches=caches,
                       lengths=jnp.asarray([prompt_len], jnp.int32),
                       block_tables=tables)


def _teacher_forced_decode(ff, seq, prompt_len, max_len, bucket,
                           fault=None):
    """Prefill ``prompt_len`` tokens, then decode over the paged pool
    with the TRUE next token fed back each step (teacher forcing) —
    returns per-position decode logits aligned with the full forward's
    rows. ``fault`` (the control's) edits the decode state this harness
    built, never the product."""
    import jax.numpy as jnp

    pre = ff.executor.make_prefill_step(bucket_len=bucket,
                                        max_decode_len=max_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = seq[0, :prompt_len]
    logits_p, last, cache = pre(ff.params, [jnp.asarray(padded)],
                                jnp.asarray([prompt_len], np.int32))
    state = _pool_state(cache, prompt_len, max_len)
    if fault is not None:
        state = fault(state)
    dec = ff.executor.make_decode_step(max_len, KV_BLOCK)
    rows = {}
    for t in range(prompt_len, seq.shape[1]):
        lg, state = dec(ff.params, [jnp.asarray(seq[:, t:t + 1])], state)
        rows[t] = np.asarray(lg)[0]
    return np.asarray(logits_p), np.asarray(last), rows


def _full_forward_logits(ff, seq, batch):
    fwd = ff.executor.make_forward()
    return np.asarray(fwd(ff.params, [np.repeat(seq, batch, axis=0)]))[0]


def _stack(rows):
    """(positions, logits) of a ``{position: row}`` dict, in order."""
    ts = sorted(rows)
    return ts, np.stack([rows[t] for t in ts])


def test_prefill_decode_matches_full_forward_gpt2(gpt2):
    """Acceptance gate: prefill and decode logits match the
    whole-sequence forward within the stated tolerance and choose the
    same greedy token at every position (tests/serving_oracle.py)."""
    ff, cfg = gpt2
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size,
                       size=(1, cfg.seq_len)).astype(np.int32)
    full = _full_forward_logits(ff, seq, cfg.batch_size)
    L, bucket = 5, 8
    logits_p, last, rows = _teacher_forced_decode(
        ff, seq, L, cfg.seq_len, bucket)
    # prefill rows [0, L) match the full forward
    assert_matches_reference(logits_p[0, :L], full[:L], "prefill rows")
    # the prefill's next-token logits are the row at L-1
    assert_matches_reference(last[0], full[L - 1], "prefill last row")
    # every decoded position matches
    ts, got = _stack(rows)
    assert_matches_reference(got, full[ts], "decode rows")


def test_prefill_decode_matches_full_forward_transformer_decoder():
    cfg = TransformerConfig.tiny(batch_size=4)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    ff = FFModel(config)
    build_transformer_decoder(ff, cfg, vocab_size=60)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(1)
    seq = rng.integers(0, 60, size=(1, cfg.seq_len)).astype(np.int32)
    full = _full_forward_logits(ff, seq, cfg.batch_size)
    logits_p, last, rows = _teacher_forced_decode(
        ff, seq, 4, cfg.seq_len, 4)
    assert_matches_reference(logits_p[0, :4], full[:4], "prefill rows")
    ts, got = _stack(rows)
    assert_matches_reference(got, full[ts], "decode rows")


def test_lstm_decode_state():
    """The NMT-family building block: the LSTM's recurrent carry is its
    decode state. Prefill gathers the carry at the TRUE prompt length
    (not the padded tail); decode continues within the stated tolerance
    of the whole-sequence forward and greedy tokens agree exactly."""
    config = FFConfig()
    config.batch_size = 4
    ff = FFModel(config)
    ids = ff.create_tensor((4, 12), dtype=DataType.DT_INT32, name="lm_ids")
    t = ff.embedding(ids, 50, 16, name="lm_embed")
    t, _state = ff.lstm(t, 16, name="lm_lstm")
    ff.dense(t, 50, name="lm_head")
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, 50, size=(1, 12)).astype(np.int32)
    full = _full_forward_logits(ff, seq, 4)
    L = 4
    logits_p, last, rows = _teacher_forced_decode(ff, seq, L, 12, 8)
    # prefill's next-token logits come from the carry at length-1 — the
    # padded tail the scan marched through must not leak in
    assert np.array_equal(last[0], full[L - 1])
    ts, got = _stack(rows)
    assert_matches_reference(got, full[ts], "decode rows")


def _chunked_next_token_logits(ff, prompt, max_len, chunk, late=0):
    """The prompt through the chunk-prefill program, ``chunk`` tokens at
    a time into an empty pool; returns the final chunk's next-token
    logits. ``late`` (the control's) shifts where the LAST chunk is
    written."""
    import jax
    import jax.numpy as jnp

    pre = ff.executor.make_prefill_step(bucket_len=chunk,
                                        max_decode_len=max_len)
    _lg, _last, cache = pre(ff.params, [jnp.zeros((1, chunk), jnp.int32)],
                            jnp.asarray([1], np.int32))
    state = _pool_state(jax.tree.map(jnp.zeros_like, cache), 0, max_len)
    fn = ff.executor.make_chunk_prefill_step(chunk, max_len, KV_BLOCK)
    row = state.block_tables[0]
    n_all = len(prompt)
    for start in range(0, n_all, chunk):
        n = min(chunk, n_all - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :n] = prompt[start:start + n]
        at = start + (late if start + n == n_all else 0)
        last, state = fn(ff.params, [jnp.asarray(ids)], state, row,
                         jnp.int32(at), jnp.int32(n))
    return np.asarray(last)[0]


def _zero_one_k_row(state):
    """Fault (a): the cached K row of prompt position 2 reads zero in
    the first attention entry (block ``table[0]``, offset 2; K is the
    first half of a pool row's lanes)."""
    name = sorted(n for n, e in state.caches.items()
                  if getattr(e, "ndim", 0) == 4)[0]
    pool = state.caches[name]
    caches = dict(state.caches)
    caches[name] = pool.at[1, :, 2, :pool.shape[-1] // 2].set(0.0)
    return DecodeState(caches=caches, lengths=state.lengths,
                       block_tables=state.block_tables)


def _lengths_one_short(state):
    """Fault (b): the write cursor — and with it the mask and the
    position id — is off by one."""
    return DecodeState(caches=state.caches, lengths=state.lengths - 1,
                       block_tables=state.block_tables)


@pytest.mark.parametrize("fault", ["k_row_zeroed", "lengths_one_short",
                                   "chunk_one_late"])
def test_oracle_tolerance_refuses_planted_faults(gpt2, fault):
    """The control: the tolerance admits the product (the same harness
    without the fault passes) and refuses a fault a reader would call a
    bug, planted in the TEST's copy of the inputs — each reads at least
    100 times the tolerance."""
    ff, cfg = gpt2
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size,
                       size=(1, cfg.seq_len)).astype(np.int32)
    full = _full_forward_logits(ff, seq, cfg.batch_size)
    if fault == "chunk_one_late":
        L = 12
        ref = full[L - 1]
        good = _chunked_next_token_logits(ff, seq[0, :L], cfg.seq_len, 8)
        bad = _chunked_next_token_logits(ff, seq[0, :L], cfg.seq_len, 8,
                                         late=1)
    else:
        plant = {"k_row_zeroed": _zero_one_k_row,
                 "lengths_one_short": _lengths_one_short}[fault]
        _p, _l, rows = _teacher_forced_decode(ff, seq, 5, cfg.seq_len, 8)
        ts, good = _stack(rows)
        _p, _l, rows = _teacher_forced_decode(ff, seq, 5, cfg.seq_len, 8,
                                              fault=plant)
        _ts, bad = _stack(rows)
        ref = full[ts]
    assert_matches_reference(good, ref, "unfaulted")
    gap, tol = logit_gap(bad, ref), logit_tolerance(ref)
    assert gap >= 100 * tol, \
        f"{fault} reads {gap:.3e}, under 100x the tolerance {tol:.3e}"
    with pytest.raises(AssertionError, match="differ from the reference"):
        assert_matches_reference(bad, ref, fault)


def test_decode_recompile_free(gpt2):
    """Acceptance gate: after warmup the decode loop never recompiles —
    one jit cache entry across varied prompt lengths, slot churn and
    request mixes."""
    ff, cfg = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len,
                        buckets=(4, 8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 100, size=n).tolist()
               for n in (3, 5, 7, 2, 6, 4)]
    outs = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    assert eng.decode_compiles == 1, \
        f"decode recompiled: {eng.decode_compiles} cache entries"
    # prefill compiles once per BUCKET, not per prompt length
    pre = ff.executor._serving_jits[("prefill", 4, cfg.seq_len)]
    assert pre._cache_size() == 1


def test_no_cross_request_cache_leakage(gpt2):
    """Greedy continuations are identical whether a request runs alone or
    co-batched with strangers — slots share nothing."""
    ff, cfg = gpt2
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 100, size=int(n)).tolist()
               for n in rng.integers(3, 8, size=5)]
    eng = ServingEngine(ff, n_slots=3, max_decode_len=cfg.seq_len)
    batched = eng.generate(prompts, max_new_tokens=5)
    for i, p in enumerate(prompts):
        solo_eng = ServingEngine(ff, n_slots=1,
                                 max_decode_len=cfg.seq_len)
        solo = solo_eng.generate([p], max_new_tokens=5)
        assert solo[0] == batched[i], f"request {i} leaked across slots"


def test_eos_slot_recycling_and_continuous_admission(gpt2):
    """More requests than slots: EOS/length-finished slots are recycled
    into the waiting queue until everything drains."""
    ff, cfg = gpt2
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 100, size=4).tolist() for _ in range(6)]
    base = eng.generate(prompts, max_new_tokens=6)
    eos = base[0][1]  # force an early stop for at least request 0
    eng2 = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    outs = eng2.generate(prompts, max_new_tokens=6, eos_id=eos)
    assert len(outs) == 6 and all(len(o) >= 1 for o in outs)
    assert outs[0][-1] == eos and len(outs[0]) == 2
    for o in outs:  # eos never appears mid-stream
        assert eos not in o[:-1]
    assert eng2.stats.requests_served == 6
    assert eng2.stats.queue_depth_hwm >= 4  # queue really backed up


def test_scheduler_deterministic_under_seeded_arrival(gpt2):
    """The schedule (and therefore every token stream) is a deterministic
    function of the submission sequence — greedy results are ALSO
    invariant to the arrival order itself (per-request isolation)."""
    ff, cfg = gpt2
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 100, size=int(n)).tolist()
               for n in rng.integers(3, 8, size=5)]
    order = np.random.default_rng(7).permutation(5)
    shuffled = [prompts[i] for i in order]

    def run(ps, temp=0.0, seed=0):
        eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
        return eng.generate(ps, max_new_tokens=4, temperature=temp,
                            top_k=3, seed=seed)

    a, b = run(shuffled), run(shuffled)
    assert a == b, "same seeded arrival produced different streams"
    plain = run(prompts)
    for i, pos in enumerate(order):  # greedy is arrival-order invariant
        assert a[i] == plain[pos]
    s1, s2 = run(shuffled, temp=0.9, seed=11), run(shuffled, temp=0.9,
                                                   seed=11)
    assert s1 == s2, "sampled decode not deterministic under a seed"
    s3 = run(shuffled, temp=0.9, seed=12)
    assert s1 != s3, "seed does not vary the sampled stream"


def test_scheduler_backpressure_and_capacity():
    sched = ContinuousBatchScheduler(n_slots=1, max_queue=2, max_len=32)
    sched.submit(Request(prompt=np.zeros(4, np.int32), max_new_tokens=4))
    sched.submit(Request(prompt=np.zeros(4, np.int32), max_new_tokens=4))
    with pytest.raises(QueueFullError):
        sched.submit(Request(prompt=np.zeros(4, np.int32),
                             max_new_tokens=4))
    with pytest.raises(ValueError, match="ring capacity"):
        ContinuousBatchScheduler(n_slots=1, max_queue=8, max_len=16).submit(
            Request(prompt=np.zeros(10, np.int32), max_new_tokens=10))
    assert bucket_for(5, (4, 8, 16)) == 8
    with pytest.raises(ValueError, match="largest prefill bucket"):
        bucket_for(99, (4, 8, 16))
    # a prompt no bucket covers is refused AT SUBMIT — never after
    # next_action() already claimed a slot (slot-pool corruption)
    narrow = ContinuousBatchScheduler(n_slots=1, max_queue=8,
                                      buckets=(4,), max_len=32)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        narrow.submit(Request(prompt=np.zeros(8, np.int32),
                              max_new_tokens=2))
    assert narrow.queued == 0 and not narrow.active


def test_serving_engine_rejects_non_autoregressive():
    from flexflow_tpu.models.transformer import build_transformer

    cfg = TransformerConfig.tiny(batch_size=8)
    config = FFConfig()
    config.batch_size = 8
    ff = FFModel(config)
    build_transformer(ff, cfg)  # bidirectional encoder + pooled head
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    with pytest.raises(ValueError):
        ServingEngine(ff)


def test_serving_search_beats_naive_dp(monkeypatch):
    """Acceptance gate: search_all(objective='serving') on a simulated
    8-device mesh returns a feasible plan whose simulated tokens/sec beats
    naive dp replication while meeting the SLO, under
    FLEXFLOW_TPU_SEARCH_SELFCHECK (cached == cold pricing)."""
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.search.unity import search_all

    monkeypatch.setenv("FLEXFLOW_TPU_SEARCH_SELFCHECK", "1")
    cfg = GPT2Config()  # gpt2-small-sized graph; pcg only, no params
    config = FFConfig()
    config.batch_size = cfg.batch_size
    config.max_inflight = 8
    config.max_decode_len = 128
    config.slo_p99_ms = 50.0
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    pcg = ff.create_pcg()
    machine = TPUMachineModel.from_generation("v5e", 8)
    plan = search_all(pcg, config, 8, objective="serving", machine=machine)
    assert plan.feasible
    assert plan.sim_p99_ms <= 50.0
    assert plan.sim_memory <= machine.hbm_capacity
    naive = [c for c in plan.ranked if tuple(c.mesh_shape) == (8, 1)]
    assert naive, "naive dp candidate missing from the ranked chain"
    assert plan.sim_tokens_per_s > naive[0].sim_tokens_per_s, \
        "searched serving plan does not beat naive dp"
    # the decode-state layout axis is really searched: for the winning
    # mesh, the sharded KV layout prices no worse than replicated
    twins = {c.layout: c for c in plan.ranked
             if tuple(c.mesh_shape) == tuple(plan.mesh_shape)}
    if "sharded" in twins and "replicated" in twins:
        assert twins["sharded"].sim_tokens_per_s >= \
            twins["replicated"].sim_tokens_per_s
    with pytest.raises(ValueError, match="objective"):
        search_all(pcg, config, 8, objective="latency")


def test_elastic_replan_mid_serve_keeps_answers_identical(gpt2):
    """PR 4/5 carry-over: losing chips mid-serve re-searches (warm
    delta-cost sim) and rebuilds the serving jits; the in-flight
    DecodeState survives, so continuations are bit-identical to an
    uninterrupted run."""
    ff, cfg = gpt2
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 100, size=4).tolist() for _ in range(4)]
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    base = eng.generate(prompts, max_new_tokens=5)
    eng2 = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    first = eng2.generate(prompts[:2], max_new_tokens=5)
    plan = eng2.elastic_replan(4)  # half the fleet gone
    assert plan.mesh_shape[0] * plan.mesh_shape[1] <= 4
    rest = eng2.generate(prompts[2:], max_new_tokens=5)
    assert first == base[:2] and rest == base[2:]
    # the warm simulator was reused: a second replan shares its caches
    sim = eng2._search_sim
    assert sim is not None
    hits0 = sim.cost_cache_hits
    eng2.elastic_replan(2)
    assert eng2._search_sim is sim and sim.cost_cache_hits > hits0


def test_cacheop_graphs_remat(recwarn):
    """ISSUE 6 inversion of the old 'CacheOp graphs opt out of remat'
    rule: cache state now threads through the checkpointed blocks, so a
    cache-carrying model trains under --remat without a fallback."""
    config = FFConfig()
    config.batch_size = 16
    config.remat = "selective"
    from flexflow_tpu.ffconst import ActiMode

    ff = FFModel(config)
    x = ff.create_tensor((16, 32), name="in")
    h = ff.dense(x, 32, activation=ActiMode.AC_MODE_RELU, name="d1")
    h = ff.cache(h, num_batches=2, name="hcache")
    h = ff.dense(h, 32, name="d2")
    ff.softmax(ff.dense(h, 4, name="cls"))
    ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-3),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(32, 32)).astype(np.float32)
    ys = rng.integers(0, 4, size=(32, 1)).astype(np.int32)
    ff.fit(xs, ys, epochs=1)
    assert ff.executor.remat_plan is not None, \
        "CacheOp graph fell back off the remat path"
    assert not [w for w in recwarn.list
                if "remat disabled" in str(w.message)]


def test_predict_pads_tail_batch_single_compile(gpt2):
    """Satellite: predict's final partial batch is padded-and-trimmed
    (one jit specialization) and host transfer happens once."""
    ff, cfg = gpt2
    ff.executor._forward_jit = None  # fresh forward: count its compiles
    rng = np.random.default_rng(6)
    x = rng.integers(0, 100, size=(13, cfg.seq_len)).astype(np.int32)
    out = ff.predict(x)
    assert out.shape[0] == 13
    fwd = ff.executor.make_forward()
    assert fwd._cache_size() == 1, "tail batch forced a second compile"
    ref = np.asarray(fwd(ff.params, [np.repeat(x[12:13], cfg.batch_size,
                                               axis=0)]))[0]
    assert np.array_equal(out[12], ref)


def test_serving_flags_parse_and_validate():
    config = FFConfig()
    config.parse_args(["--serve", "--max-decode-len", "256",
                       "--max-inflight", "16", "--slo-p99-ms", "12.5"])
    assert config.serve and config.max_decode_len == 256
    assert config.max_inflight == 16 and config.slo_p99_ms == 12.5
    with pytest.raises(ValueError, match="max-decode-len"):
        FFConfig().parse_args(["--max-decode-len", "0"])
    with pytest.raises(ValueError, match="max-inflight"):
        FFConfig().parse_args(["--max-inflight", "0"])
    with pytest.raises(ValueError, match="slo-p99-ms"):
        FFConfig().parse_args(["--slo-p99-ms", "-1"])


def test_serving_telemetry_block_and_trace_summary(gpt2, tmp_path,
                                                   capsys):
    """Obs satellite: StepTelemetry gains a 'serving' block and
    trace_summary prints the serving digest from both the telemetry
    record and the prefill/decode tracer spans."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import trace_summary

    ff, cfg = gpt2
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 100, size=4).tolist() for _ in range(3)]
    ff._telemetry_requested = True
    eng = ServingEngine(ff, n_slots=2, max_decode_len=cfg.seq_len)
    eng.generate(prompts, max_new_tokens=3)
    tel = ff.get_telemetry()
    blk = tel.summary()["serving"]
    assert blk["requests_served"] == 3
    assert blk["tokens_generated"] == 9
    assert blk["queue_depth_hwm"] >= 1
    assert blk["p99_token_ms"] > 0
    # telemetry digest
    f = tmp_path / "tel.json"
    tel.write(str(f))
    trace_summary.main([str(f)])
    out = capsys.readouterr().out
    assert "serving: 3 requests, 9 tokens" in out
    # trace-span digest
    trace = {"traceEvents": [
        {"ph": "X", "name": "decode_step", "dur": 1000.0},
        {"ph": "X", "name": "decode_step", "dur": 3000.0},
        {"ph": "X", "name": "prefill", "dur": 2000.0}]}
    tf = tmp_path / "trace.json"
    tf.write_text(json.dumps(trace))
    trace_summary.main([str(tf)])
    out = capsys.readouterr().out
    assert "serving digest: 2 decode steps" in out and "1 prefills" in out


def test_serving_rejects_fused_stateful_regions():
    """--fusion folds attention/position constants into OP_FUSED regions
    the serving machinery cannot thread decode state through — the engine
    must refuse loudly instead of generating history-free garbage."""
    cfg = GPT2Config.tiny(batch_size=8)
    config = FFConfig()
    config.batch_size = 8
    config.perform_fusion = True
    config.only_data_parallel = True
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    with pytest.raises(NotImplementedError, match="fusion"):
        ServingEngine(ff, max_decode_len=cfg.seq_len)


def test_position_table_bounds_rejected_at_admission(gpt2):
    """ISSUE 12 satellite: a decode ring longer than the position-
    embedding table used to warn-and-clamp at engine construction; now
    the table bound is the engine's max supported CONTEXT and admission
    rejects a too-long request with a typed ServingRejection naming the
    limit — a request that fits still serves at full ring capacity."""
    from flexflow_tpu.serving.scheduler import (ContextOverflowError,
                                                ServingRejection)

    ff, cfg = gpt2
    # pool sized in blocks of 16 over max_decode_len 1024; the position
    # table (seq_len) is the binding context bound
    eng = ServingEngine(ff, n_slots=2, max_decode_len=1024)
    assert eng.max_context == cfg.seq_len
    assert eng.max_decode_len == 1024  # capacity no longer clamped
    # a request whose prompt + max_new exceeds the table is REJECTED at
    # admission, naming the max supported context
    outs = eng.generate([[1, 2, 3]], max_new_tokens=cfg.seq_len + 8)
    assert outs[0] == []  # shed at admission, empty continuation
    sched_probe = eng.stats
    assert sched_probe.outcomes.get("shed", 0) == 1
    from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                                Request)

    sched = ContinuousBatchScheduler(n_slots=2, max_len=1024)
    req = Request(prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=cfg.seq_len + 8)
    with pytest.raises(ContextOverflowError,
                       match="max supported context") as ei:
        eng.admit(sched, req)
    assert isinstance(ei.value, ServingRejection)
    assert str(cfg.seq_len) in str(ei.value)
    # a request inside the bound serves normally
    outs = eng.generate([[1, 2, 3]], max_new_tokens=4)
    assert len(outs[0]) == 4


def test_pipeline_microbatches_position_constants():
    """Rider fix: a GPipe stage slices batch-shaped position-id constants
    to its microbatch rows — previously gpt2 under a searched pipeline
    died on (microbatch, s, d) + (batch, s, d) broadcasting."""
    from flexflow_tpu.parallel.strategy import data_parallel_strategy

    cfg = GPT2Config.tiny(batch_size=8)
    config = FFConfig()
    config.batch_size = 8
    ff = FFModel(config)
    build_gpt2(ff, cfg)

    def strategy_fn(pcg):
        s = data_parallel_strategy(pcg, 2)
        s.pipeline = (2, 1, 4)  # pp=2, dp=1 -> 2-row microbatches
        return s

    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy_fn=strategy_fn)
    rng = np.random.default_rng(0)
    stream = rng.integers(0, cfg.vocab_size, size=(16, cfg.seq_len + 1))
    perf = ff.fit(stream[:, :-1].astype(np.int32),
                  stream[:, 1:].astype(np.int32), epochs=1)
    assert perf is not None


def test_model_generate_api(gpt2):
    """model.generate: greedy default, engine cached across calls, EOS
    threaded, sampling knobs accepted."""
    ff, cfg = gpt2
    prompts = [[1, 2, 3], [4, 5, 6, 7]]
    a = ff.generate(prompts, max_new_tokens=4)
    b = ff.generate(prompts, max_new_tokens=4)
    assert a == b and all(len(g) == 4 for g in a)
    assert ff._serving_engine is not None
    s = ff.generate(prompts, max_new_tokens=4, temperature=0.7, top_k=4,
                    seed=3)
    assert all(len(g) == 4 for g in s)
    # eos_id is per-call: a prior call's EOS must not leak through the
    # cached engine and truncate an eos-less call
    eos = a[0][1]
    cut = ff.generate(prompts, max_new_tokens=4, eos_id=eos)
    assert len(cut[0]) == 2 and cut[0][-1] == eos
    again = ff.generate(prompts, max_new_tokens=4)
    assert again == a, "cached engine leaked a previous call's eos_id"
