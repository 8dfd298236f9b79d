"""Every way a request's KV rows reach the pool, held to the
whole-sequence forward.

The benchmark's check compares 16 tokens of eight warm-wave requests;
its twins part within a few tokens, so the prefix-hit path, the chunk
write and a recycled slot are barely compared there (ROADMAP.md S8
(iii)). Here each path serves one request through the real engine —
allocator, block tables, slot write or chunk write, copy-on-write clone,
the decode step's one-token write — for 32 decode steps, and every
step's logits are held to the forward over prompt + generated tokens by
the stated tolerance (tests/serving_oracle.py), greedy tokens equal.
"""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.serving import ServingEngine
from serving_oracle import assert_matches_reference

SEQ_LEN, DECODE_STEPS, BLOCK = 64, 32, 8
PROMPT = [int(t) for t in
          np.random.default_rng(11).integers(1, 99, size=21)]


@pytest.fixture(scope="module")
def gpt2():
    cfg = GPT2Config(batch_size=2, seq_len=SEQ_LEN, hidden=64, num_heads=4,
                     num_layers=2, intermediate=128, vocab_size=100)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    config.seed = 42
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(optimizer=SGDOptimizer(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg


def _engine(ff, **kw):
    return ServingEngine(ff, n_slots=1, max_decode_len=SEQ_LEN,
                         kv_block_size=BLOCK, **kw)


def _record_decode_logits(eng):
    """Wrap the engine's decode step: ``{position fed: logits row}`` of
    slot 0 for every step dispatched from now on."""
    real, rows = eng._decode_fn, {}

    def decode_fn(guard=False):
        fn = real(guard)

        def step(params, xs, state):
            at = int(np.asarray(state.lengths)[0])  # before the donation
            out = fn(params, xs, state)
            rows[at] = np.asarray(out[0])[0]
            return out

        return step

    eng._decode_fn = decode_fn
    return rows


def _count_calls(eng, method):
    real, calls = getattr(eng, method), []

    def counted(*a, **k):
        calls.append(a)
        return real(*a, **k)

    setattr(eng, method, counted)
    return calls


def _bucketed(ff):
    eng = _engine(ff, prefix_cache="off")
    writes = _count_calls(eng, "_write_slot")
    return eng, PROMPT, lambda: len(writes) == 1


def _chunked(ff):
    eng = _engine(ff, prefix_cache="off", prefill_chunk_tokens=8)
    # 21 tokens in chunks of 8: three chunk writes, the last with pad
    # rows, and they place every row (no slot write for this prompt)
    return eng, PROMPT, lambda: eng.stats.chunked_prefills == 3


def _prefix_hit_with_cow(ff):
    eng = _engine(ff)
    clones = _count_calls(eng, "_cow_clone")
    # the sharer leaves 2 full blocks and a tail of 2 rows in the trie;
    # the request shares 17 tokens, so it clones the tail block and
    # writes its suffix chunk into the clone
    eng.generate([PROMPT[:18]], max_new_tokens=4)
    prompt = PROMPT[:17] + [91, 92, 93, 94]
    return eng, prompt, lambda: (eng.stats.prefix_hits == 1
                                 and len(clones) == 1)


def _recycled_slot(ff):
    # a pool of exactly one request's blocks: the second request gets
    # the blocks (and the slot) the first one filled and freed
    eng = _engine(ff, prefix_cache="off",
                  kv_pool_blocks=SEQ_LEN // BLOCK + 1)
    eng.generate([[7] * 30], max_new_tokens=30)
    frees = _count_calls(eng, "_clear_slot_tables")
    return eng, PROMPT, lambda: len(frees) == 1


@pytest.mark.parametrize("path", [_bucketed, _chunked, _prefix_hit_with_cow,
                                  _recycled_slot],
                         ids=lambda f: f.__name__.strip("_"))
def test_decode_after_each_write_path_matches_the_forward(gpt2, path):
    ff, cfg = gpt2
    eng, prompt, took_the_path = path(ff)
    rows = _record_decode_logits(eng)
    out = eng.generate([prompt], max_new_tokens=DECODE_STEPS + 1)[0]
    assert len(out) == DECODE_STEPS + 1
    assert took_the_path(), "the request did not take the path under test"
    seq = np.zeros((1, SEQ_LEN), np.int32)
    seq[0, :len(prompt) + len(out)] = prompt + out
    full = np.asarray(ff.executor.make_forward()(
        ff.params, [np.repeat(seq, cfg.batch_size, axis=0)]))[0]
    fed = list(range(len(prompt), len(prompt) + DECODE_STEPS))
    assert sorted(rows) == fed
    assert_matches_reference(np.stack([rows[t] for t in fed]), full[fed],
                             "decode rows")
    # and the tokens the engine committed are the reference's greedy ones
    assert out[1:] == [int(t) for t in np.argmax(full[fed], axis=-1)]


def test_the_decode_step_gives_no_real_block_to_two_slots(gpt2, monkeypatch):
    """``write_kv_rows(consecutive=False)`` takes one grid step a row, and
    on the chip two steps that name one block lose the first's row (the
    aliased call fetches the next step's block before this one's is
    written back) — a hazard the interpreter and the CPU's scatter do not
    reproduce. What stands between it and the pool is the callers'
    discipline (copy-on-write, one token a slot), so that is what is read
    here: through four slots that share a prefix, the block ids of every
    one-row-a-slot write the engine dispatches name each real block at
    most once. A new caller that breaks it (several tokens of one slot in
    one call) fails here, not first on the chip."""
    import jax

    from flexflow_tpu.serving import kvcache

    ff, _cfg = gpt2
    real, calls = kvcache.write_kv_rows, []

    def spy(pool, rows, block_ids, offsets, *, consecutive=False, **kw):
        if not consecutive:
            jax.debug.callback(lambda b: calls.append(np.asarray(b)),
                               block_ids)
        return real(pool, rows, block_ids, offsets,
                    consecutive=consecutive, **kw)

    monkeypatch.setattr(kvcache, "write_kv_rows", spy)
    eng = ServingEngine(ff, n_slots=4, max_decode_len=SEQ_LEN,
                        kv_block_size=BLOCK)
    eng.generate([PROMPT[:18]], max_new_tokens=2)  # seeds the trie
    prompts = [PROMPT[:17] + [90 + i] for i in range(4)]
    outs = eng.generate(prompts, max_new_tokens=12)
    jax.effects_barrier()
    assert all(len(o) == 12 for o in outs)
    assert eng.stats.prefix_hits >= 4
    shared = 0
    for ids in calls:
        live = ids[ids != kvcache.GARBAGE_BLOCK]
        assert len(set(live)) == len(live), ids
        shared = max(shared, len(live))
    assert shared == 4, "no step wrote four live slots at once"
