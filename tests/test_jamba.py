"""The hybrid state-space / attention decoder (models/jamba.py) against its
plain reference (tests/reference_jamba.py): float32 on the CPU at tiny widths
(hidden 64, E 128, N 16, R 4, K 4; a 4-layer period of 3 mixers and 1
one-K/V-head attention layer, twice). The forward pass; the ``selective_scan``
kernel in interpret mode against the ``lax.scan`` form; the serving path
through ``ServingEngine`` — prompts of different lengths in one bucket, a
request admitted while others decode, a slot freed and taken again — against
the reference's FULL forward under tests/serving_oracle.py's contract (logits,
not tokens); the grouped read through ``flash_decode``; the planted faults the
oracle must refuse; the recurrent state's pricing and counters; the compiled
programs for a described v5e (every pool and state leaf aliased, the five
scopes and the kernels in the text); the benchmark's copy of the reference.

The tolerance is the oracle's form — float32 ulp of the reference's largest
logit — at ``ULP_LIMIT`` 256. Measured basis (CPU, f32, jax 0.9.0, PR 44):
the whole-sequence forward reads 34 and 45 ulp, the engine's prefill and
decode rows 32 at most (a recurrence of exponentials under eight layers: the
GPT-2 fixtures the oracle's 64 was set on read 2-8); the limit is 5.7 times
the largest reading. The planted faults, as multiples of the limit: a state
one step stale 36,700 x, a conv window shifted one row 82,600 x, ``b_dt``
dropped 63,600 x, a state rounded to bf16 every step 240 x, the state taken
at the padded tail instead of the last real token 49,000 x.
"""
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_jamba as ref
from serving_oracle import (assert_matches_reference, logit_gap,
                            logit_tolerance)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmark")
with open(os.path.join(BENCH, "tests", "cells", "configs",
                       "jamba-tiny.json")) as _f:
    TINY = json.load(_f)
FIELDS = TINY["builder"]["fields"]
BATCH, SEQ, BLOCK, MAX_LEN = 2, 32, 8, 64
ULP_LIMIT = 256


def assert_matches(got, want, what):
    assert_matches_reference(got, want, what, ulp_limit=ULP_LIMIT)


def tolerance(want):
    return logit_tolerance(want, ULP_LIMIT)


def jamba_config(**overrides):
    from flexflow_tpu.models.jamba import JambaConfig

    kwargs = {field: TINY[key] for field, key in FIELDS.items()}
    kwargs.update(batch_size=BATCH, seq_len=SEQ)
    kwargs.update(overrides)
    return JambaConfig(**kwargs)


def build(cfg, seed=5, argv=()):
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.jamba import build_jamba

    config = FFConfig()
    config.parse_args(["-b", str(cfg.batch_size), *argv])
    config.seed = seed
    ff = FFModel(config)
    build_jamba(ff, cfg)
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


@pytest.fixture(scope="module")
def system():
    ff = build(jamba_config())
    params0 = jax.device_get(ff.params)
    # lift the gains, the skip and the conv bias off their constants so that
    # one left out would show
    rng = np.random.default_rng(11)
    for group in params0.values():
        for w in group:
            if group[w].ndim == 1 and w != "b_dt":
                group[w] = (group[w] + 0.1 * rng.standard_normal(
                    group[w].shape)).astype(np.float32)
    ff.params = jax.device_put(params0)
    return ff, params0


def ids(seed=0, n=SEQ):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).astype(np.int32)


def reference_logits(params0, seq, fault=None):
    return ref.logits(params0, seq, TINY, fault=fault)


def engine(ff, **kw):
    from flexflow_tpu.serving import ServingEngine

    kw.setdefault("n_slots", 4)
    return ServingEngine(ff, max_decode_len=MAX_LEN, kv_block_size=BLOCK,
                         buckets=(16, 32), **kw)


def record_logits(eng):
    """Wrap the engine's programs: ``prefill[n]`` the next-token row of the
    n-th prefill, ``decode[(slot, position fed)]`` every live slot's row of
    every decode step dispatched from now on."""
    real_decode, real_prefill = eng._decode_fn, eng._prefill_fn
    prefill, decode = [], {}

    def decode_fn(guard=False):
        fn = real_decode(guard)

        def step(params, xs, state):
            at = np.asarray(state.lengths)            # before the donation
            live = np.asarray(state.block_tables).any(axis=1)
            out = fn(params, xs, state)
            rows = np.asarray(out[0])
            for slot in np.flatnonzero(live):
                decode[(int(slot), int(at[slot]))] = rows[slot]
            return out

        return step

    def prefill_fn(bucket):
        fn = real_prefill(bucket)

        def step(params, xs, lengths):
            out = fn(params, xs, lengths)
            prefill.append(np.asarray(out[1])[0])
            return out

        return step

    eng._decode_fn, eng._prefill_fn = decode_fn, prefill_fn
    return prefill, decode


# ------------------------------------------------------------ the forward
def test_parameter_count_is_the_builders(system):
    from flexflow_tpu.models.jamba import jamba_param_count

    ff, _ = system
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ff.params))
    assert held == jamba_param_count(jamba_config())


def test_published_parameter_count():
    """The configuration file's ``parameters_held`` is the builder's count at
    the published widths, and the tied count is the issue's."""
    from flexflow_tpu.models.jamba import JambaConfig, jamba_param_count

    with open(os.path.join(BENCH, "configs", "ai21-jamba2-3b.json")) as f:
        config = json.load(f)
    cfg = JambaConfig(**{field: config[key] for field, key in FIELDS.items()})
    assert jamba_param_count(cfg) == config["parameters_held"] \
        == 3_197_109_632
    assert jamba_param_count(cfg, tied=True) == 3_029_337_472
    assert config["reduced"] == []


@pytest.fixture(scope="module")
def forward(system):
    ff, _ = system
    x = np.stack([ids(0), ids(1)])
    return x, np.asarray(ff.executor.make_forward()(ff.params, [x]))


@pytest.mark.parametrize("row", range(BATCH))
def test_forward_matches_the_reference(system, forward, row):
    _, params0 = system
    x, got = forward
    assert_matches(got[row], reference_logits(params0, x[row]),
                             "whole-sequence forward")


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_comparison_refuses_a_planted_fault(system, forward, fault):
    """A state one step stale under the ``C`` contraction, the conv's window
    shifted one row, ``b_dt`` dropped, the state rounded to bf16 every step:
    each, planted in the reference's recurrence, puts the system at least
    ten times outside the tolerance the sound comparison meets."""
    _, params0 = system
    x, got = forward
    want = reference_logits(params0, x[0], fault=fault)
    assert logit_gap(got[0], want) > 10 * tolerance(want)


# ---------------------------------------------------------------- the kernel
@pytest.mark.parametrize("batch,length,width,state,short,initial", [
    (1, 128, 1024, 16, 0, False),     # whole tiles, no state, full length
    (2, 37, 128, 16, 5, True),        # E and L under a tile, rows cut short
    (1, 200, 1100, 8, 72, True),      # neither a multiple of its tile
    (3, 130, 2048, 16, 129, False),   # one real token of 130
])
def test_selective_scan_kernel_equals_the_scan(batch, length, width, state,
                                               short, initial):
    from flexflow_tpu.kernels.selective_scan import (
        selective_scan, selective_scan_reference)

    rng = np.random.default_rng(length)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, b, c = f32(batch, length, width), f32(batch, length, state), \
        f32(batch, length, state)
    dt = jnp.abs(f32(batch, length, width)) * 0.1
    a = -jnp.exp(f32(state, width))
    s0 = f32(batch, state, width) if initial else None
    lengths = jnp.asarray([length - short] * batch, jnp.int32)
    want_y, want_s = selective_scan_reference(x, dt, b, c, a, s0=s0,
                                              lengths=lengths)
    got_y, got_s = selective_scan(x, dt, b, c, a, s0=s0, lengths=lengths,
                                  interpret=True)
    n = length - short
    np.testing.assert_allclose(got_y[:, :n], want_y[:, :n], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)
    # rows past the length leave the state where the last real token put it
    cut_y, cut_s = selective_scan_reference(
        x[:, :n], dt[:, :n], b[:, :n], c[:, :n], a, s0=s0)
    np.testing.assert_allclose(got_s, cut_s, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ serving path
@pytest.mark.parametrize("lengths", [(13, 16, 3), (17, 32, 25)])
def test_prompts_of_different_lengths_in_one_bucket(system, lengths):
    """Prefill then decode through ``ServingEngine``: every request's
    next-token row and every decode row within the oracle's tolerance of the
    reference's FULL forward over prompt + answer — so the state handed to
    the slot is the one after the LAST REAL token, whatever the padding."""
    ff, params0 = system
    eng = engine(ff)
    prefill, decode = record_logits(eng)
    prompts = [[int(t) for t in ids(20 + k, n)]
               for k, n in enumerate(lengths)]
    outs = eng.generate(prompts, max_new_tokens=8)
    # slots are taken in order; requests finish together
    for slot, (prompt, out) in enumerate(zip(prompts, outs)):
        seq = np.asarray(prompt + out, np.int32)
        want = reference_logits(params0, seq[:-1])
        n = len(prompt)
        assert_matches(prefill[slot], want[n - 1], "prefill row")
        rows = np.stack([decode[(slot, t)] for t in range(n, len(seq) - 1)])
        assert_matches(rows, want[n:], "decode rows")
    st = eng.stats
    assert st.recurrent_slots_live == sum(len(o) - 1 for o in outs)
    assert st.recurrent_state_bytes == st.decode_steps * 2 * eng.n_slots \
        * eng._recurrent_slot_bytes()
    assert st.summary()["recurrent_state_bytes"] == st.recurrent_state_bytes


def test_a_request_admitted_while_others_decode(system):
    """Two requests decode; a third is admitted between their steps (its
    prefill and slot write run beside their state). The first two's logits
    are the reference's all through, and so are the newcomer's."""
    from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                                Request)

    ff, params0 = system
    eng = engine(ff)
    prefill, decode = record_logits(eng)
    sched = ContinuousBatchScheduler(n_slots=eng.n_slots, max_queue=8,
                                     buckets=eng.buckets, max_len=MAX_LEN)
    loop = eng.start_serve(sched)
    reqs = [Request(prompt=ids(30 + k, n), max_new_tokens=12, eos_id=None,
                    rng_tag=k) for k, n in enumerate((11, 14))]
    for r in reqs:
        eng.admit(sched, r)
    for _ in range(6):           # two prefills, four decode steps
        loop.tick()
    late = Request(prompt=ids(33, 9), max_new_tokens=6, eos_id=None,
                   rng_tag=2)
    eng.admit(sched, late)
    while loop.tick():
        pass
    loop.finish()
    assert [len(r.generated) for r in reqs + [late]] == [12, 12, 6]
    for slot, r in enumerate(reqs + [late]):
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        want = reference_logits(params0, seq[:-1])
        n = len(r.prompt)
        assert_matches(prefill[slot], want[n - 1], "prefill row")
        rows = np.stack([decode[(slot, t)] for t in range(n, len(seq) - 1)])
        assert_matches(rows, want[n:],
                                 f"decode rows of request {slot}")


def test_a_slot_freed_and_taken_again(system):
    """One slot: the second tenant's state is written whole over the
    first's, so its stream and logits equal a fresh engine's; and a slot
    nobody holds rests at zero."""
    ff, params0 = system
    first, second = [int(t) for t in ids(40, 15)], \
        [int(t) for t in ids(41, 10)]
    eng = engine(ff, n_slots=1)
    eng.generate([first], max_new_tokens=9)
    name = next(k for k in eng.state.caches if "ssm" in k)
    eng.executor  # the slot is free now: run one step over the empty batch
    state = eng._decode_fn()(ff.params, [eng._last_tokens], eng.state)[1]
    eng.state = state
    for leaf in jax.tree.leaves(state.caches[name]):
        assert not np.asarray(leaf).any(), "a free slot's state is not zero"
    prefill, decode = record_logits(eng)
    out = eng.generate([second], max_new_tokens=9)[0]
    fresh = engine(ff, n_slots=1)
    assert out == fresh.generate([second], max_new_tokens=9)[0]
    seq = np.asarray(second + out, np.int32)
    want = reference_logits(params0, seq[:-1])
    n = len(second)
    rows = np.stack([decode[(0, t)] for t in range(n, len(seq) - 1)])
    assert_matches(rows, want[n:], "the second tenant's rows")


def test_state_taken_at_the_padded_tail_is_refused(system):
    """The planted fault of the hand-over: a prefill told that its padding
    is real hands the slot the state after the padded tail. Decoding from
    that state (the K/V rows are the sound ones) is refused."""
    from flexflow_tpu.serving.kvcache import (DecodeState, blocks_per_slot,
                                              is_prefill_kv_entry,
                                              new_kv_pool,
                                              scatter_prefill_kv)

    ff, params0 = system
    seq = ids(50, 24)
    n, bucket = 11, 16
    want = reference_logits(params0, seq)[n]
    pre = ff.executor.make_prefill_step(bucket_len=bucket,
                                        max_decode_len=MAX_LEN)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = seq[:n]
    caches = [pre(ff.params, [jnp.asarray(padded)],
                  jnp.asarray([length], np.int32))[2]
              for length in (n, bucket)]
    mb = blocks_per_slot(MAX_LEN, BLOCK)
    row = jnp.arange(1, mb + 1, dtype=jnp.int32)
    dec = ff.executor.make_decode_step(MAX_LEN, BLOCK)
    gaps = []
    for recurrent_from in (0, 1):
        entries = {}
        for name, entry in caches[0].items():
            if is_prefill_kv_entry(entry):
                entries[name] = scatter_prefill_kv(
                    new_kv_pool(entry, mb + 1, BLOCK, "native"), entry, row,
                    BLOCK)
            else:
                entries[name] = caches[recurrent_from][name]
        state = DecodeState(caches=entries,
                            lengths=jnp.asarray([n], jnp.int32),
                            block_tables=row[None])
        got = np.asarray(dec(ff.params, [jnp.asarray(seq[None, n:n + 1])],
                             state)[0])[0]
        gaps.append(logit_gap(got, want))
    tol = tolerance(want)
    assert gaps[0] <= tol
    assert gaps[1] > 10 * tol


def test_chunking_and_the_prefix_cache_are_refused(system):
    from flexflow_tpu.serving import ServingEngine

    ff, _ = system
    with pytest.raises(ValueError, match="recurrent node"):
        ServingEngine(ff, prefill_chunk_tokens=8)
    with pytest.raises(ValueError, match="recurrent node"):
        ServingEngine(ff, prefix_cache="on")
    eng = engine(ff)
    assert eng._prefix is None
    fn = ff.executor.make_chunk_prefill_step(8, MAX_LEN, BLOCK)
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="Reach R8"):
        fn(ff.params, [jnp.zeros((1, 8), jnp.int32)], eng.state,
           jnp.zeros((eng.max_blocks_per_slot,), jnp.int32), jnp.int32(0),
           jnp.int32(3))


@pytest.mark.parametrize("extra", [{"window": 4}, {"rope_theta": 10000.0}],
                         ids=["window", "rotary"])
def test_window_and_rotary_still_raise_under_a_serving_context(extra):
    """What is left of R3 (a): grouped heads serve, a sliding window and
    rotary positions do not, and the message says which."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import OpContext, op_class_for
    from flexflow_tpu.serving.kvcache import ServingState

    op = op_class_for(OperatorType.OP_MULTIHEAD_ATTENTION)(
        "l0_attn", {"embed_dim": 16, "num_heads": 4, "num_kv_heads": 2,
                    "causal": True, "bias": False, **extra},
        DataType.DT_FLOAT, num_inputs=3)
    params = {k: jnp.zeros(s[0], jnp.float32)
              for k, s in op.weight_specs([(1, 8, 16)] * 3).items()}
    sv = ServingState(mode="prefill", max_len=16,
                      positions=jnp.zeros((1,), jnp.int32))
    h = jnp.zeros((1, 8, 16), jnp.float32)
    with pytest.raises(NotImplementedError,
                       match="sliding window and rotary"):
        op.forward(params, [h, h, h], OpContext(training=False, serving=sv))


# ------------------------------------------------------- the grouped read
@pytest.mark.parametrize("kv_heads,block_size,table_width", [
    (1, 16, 3), (1, 16, 20), (2, 8, 40), (4, 16, 8)])
def test_grouped_read_through_flash_decode(kv_heads, block_size,
                                           table_width):
    """A K/V head's group of query rows against the pool's V-then-K rows
    through the kernel's latent read (interpret mode), against the gather
    path: free slots, lengths at tile edges."""
    from flexflow_tpu.kernels.flash_decode import flash_decode_pool
    from flexflow_tpu.serving import kvcache

    heads, kd, slots = 8, 128, 5
    rng = np.random.default_rng(table_width)
    n_blocks = slots * table_width + 1
    k = jnp.asarray(rng.normal(size=(n_blocks, kv_heads, block_size, kd)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_blocks, kv_heads, block_size, kd)),
                    jnp.float32)
    pool = jnp.concatenate([v, k], axis=-1)          # the grouped layout
    tables = jnp.asarray(1 + rng.permutation(slots * table_width).reshape(
        slots, table_width), jnp.int32)
    extent = table_width * block_size
    n_keys = jnp.asarray([1, extent, 0, min(extent, 257),
                          max(extent - 3, 1)], jnp.int32)
    tables = tables.at[2].set(0)                     # a free slot
    q = jnp.asarray(rng.normal(size=(slots, heads, kd)), jnp.float32)
    scale = 1.0 / np.sqrt(kd)
    got = flash_decode_pool(
        jnp.pad(q, ((0, 0), (0, 0), (kd, 0))), pool, tables, n_keys,
        sm_scale=scale, v_lanes=kd, interpret=True)
    kc, vc = kvcache.read_kv(pool, tables, kd, jnp.float32, v_first=True)
    group = heads // kv_heads
    kc, vc = (jnp.repeat(t, group, axis=1) for t in (kc, vc))
    logits = jnp.einsum("bhd,bhkd->bhk", q, kc) * scale
    mask = jnp.arange(extent)[None, None, :] < n_keys[:, None, None]
    prob = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    want = jnp.einsum("bhk,bhkd->bhd", prob, vc)
    want = jnp.where((n_keys > 0)[:, None, None], want, 0.0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_grouped_pool_rows_round_trip():
    """The layout is kvcache.py's alone: what the prefill entry, the slot
    writer and the token write put down, ``read_kv`` hands back as K and V."""
    from flexflow_tpu.serving import kvcache

    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(1, 1, 13, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, 13, 16)), jnp.float32)
    entry = kvcache.prefill_kv_entry(k, v, 32, v_first=True)
    pool = kvcache.new_kv_pool(entry, 6, 8, "native")
    row = jnp.asarray([2, 4, 1, 3], jnp.int32)
    pool = kvcache.scatter_prefill_kv(pool, entry, row, 8)
    k1 = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    v1 = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    pool = kvcache.write_token_kv(pool, k1, v1, jnp.asarray([13]),
                                  row[None], 8, v_first=True)
    kc, vc = kvcache.read_kv(pool, row[None], 16, jnp.float32, v_first=True)
    np.testing.assert_array_equal(kc[0, 0, :13], k[0, 0])
    np.testing.assert_array_equal(vc[0, 0, :13], v[0, 0])
    np.testing.assert_array_equal(kc[0, 0, 13], k1[0, 0, 0])
    np.testing.assert_array_equal(vc[0, 0, 13], v1[0, 0, 0])


# ------------------------------------------------------------- the pricing
def test_state_is_priced_a_slot_and_the_pool_a_kv_head(system):
    from flexflow_tpu.serving.kvcache import (is_recurrent, node_slot_bytes,
                                              node_token_bytes)

    ff, _ = system
    nodes = {n.name: n.op for n in ff.executor.pcg.compute_nodes()}
    mixer = next(op for name, op in nodes.items() if "_ssm" in name)
    attn = next(op for name, op in nodes.items() if "_attn" in name)
    # (N x E) float32 and (K - 1) x E in the node's dtype (float32 here)
    assert node_slot_bytes(mixer) == 128 * 16 * 4 + 128 * 3 * 4
    assert node_slot_bytes(attn) == 0 and node_token_bytes(mixer) == 0
    assert is_recurrent(mixer) and not is_recurrent(attn)
    # ONE K/V head of 16 + 16 numbers, not the four query heads'
    assert node_token_bytes(attn) == (16 + 16) * 4
    eng = engine(ff)
    assert eng._recurrent_slot_bytes() == 6 * node_slot_bytes(mixer)
    assert eng._kv_row_bytes() == 2 * node_token_bytes(attn)


def test_published_slot_and_token_bytes():
    """The issue's arithmetic at the published widths: 9.3 MB of recurrent
    state a slot over 26 mixers, 1,024 B of K/V a token over 2 layers."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for
    from flexflow_tpu.serving.kvcache import (node_slot_bytes,
                                              node_token_bytes)

    mixer = op_class_for(OperatorType.OP_SSM_MIXER)(
        "l0_ssm", {"inner_dim": 5120, "state_dim": 16, "conv_width": 4,
                   "dt_rank": 160}, DataType.DT_BFLOAT16)
    attn = op_class_for(OperatorType.OP_MULTIHEAD_ATTENTION)(
        "l7_attn", {"embed_dim": 2560, "num_heads": 20, "num_kv_heads": 1},
        DataType.DT_BFLOAT16, num_inputs=3)
    assert 26 * node_slot_bytes(mixer) == 26 * (5120 * 16 * 4
                                                + 5120 * 3 * 2) == 9_318_400
    assert 2 * node_token_bytes(attn) == 1024


# ----------------------------------------- the compiled programs, for a v5e
SLOTS, POOL_BLOCKS, DEPTH = 8, 65, 4


@pytest.fixture(scope="module")
def programs():
    """The prefill, the decode step and the slot write of a 4-layer model
    (3 mixers, 1 one-K/V-head attention layer) at lane-aligned widths (E
    1,024, heads of 128), lowered for a described v5e with the kernels'
    gates answering as on a TPU."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from flexflow_tpu.kernels import _common
    from flexflow_tpu.serving import ServingEngine

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ff = build(jamba_config(batch_size=8, hidden=512, num_heads=4,
                            num_layers=DEPTH, intermediate=256,
                            mamba_dt_rank=32, vocab_size=512),
               argv=["--compute-dtype", "bf16", "--param-dtype", "bf16",
                     "--only-data-parallel", "--mesh-shape", "1"])
    eng = ServingEngine(ff, n_slots=SLOTS, max_decode_len=128,
                        kv_block_size=16, kv_pool_blocks=POOL_BLOCKS,
                        buckets=(128,))

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    i32 = on(jnp.int32(0))
    x = on(jnp.zeros((1, 128), jnp.int32))
    one = on(jnp.ones((1,), jnp.int32))
    real_on_tpu = _common.on_tpu
    cache = jax.eval_shape(eng._prefill_fn(128), ff.params, [x], one)[2]
    eng._ensure_state(cache)
    state, last = on(eng.state), on(eng._last_tokens)
    row = on(jnp.zeros((eng.max_blocks_per_slot,), jnp.int32))
    params = on(ff.params)
    _common.on_tpu = lambda: True
    try:
        yield eng, {
            "prefill": (ff.executor.make_prefill_step(128, 129),
                        (params, [x], one)),
            "decode_step": (eng._decode_fn(), (
                params, [on(jnp.zeros((SLOTS, 1), jnp.int32))], state)),
            "slot_write": (eng._write_slot_program(), (
                state, last, on(cache), i32, i32, i32, row)),
        }
    finally:
        _common.on_tpu = real_on_tpu
        jax.config.update("jax_enable_compilation_cache", before)


def compiled_text(programs, name):
    fn, args = programs[1][name]
    return fn.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()


def kernels_in(text):
    return {m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r"(\w+)\)*/pallas_call", line)] if m}


@pytest.mark.parametrize("name", ["decode_step", "slot_write"])
def test_pool_and_state_are_written_in_place(programs, name):
    """Every leaf of the decode state — the one-head K/V pools, each
    mixer's float32 state and conv tail — is aliased onto an output of the
    decode step and of the slot write, and none is rewritten by a copy."""
    eng = programs[0]
    text = compiled_text(programs, name)
    entry = text[text.index("\nENTRY "):]
    header = text[:text.index("\n")]
    start = header.index("input_output_alias={")
    aliased = {int(n) for n in re.findall(
        r"\}: \((\d+), ", header[start:header.index(" }", start)])}
    shapes = {
        "pool": f"bf16[{POOL_BLOCKS},1,16,256]",
        "state": f"f32[{SLOTS},16,1024]",
        "tail": f"bf16[{SLOTS},3072]",
    }
    want = {"pool": 1, "state": DEPTH - 1, "tail": DEPTH - 1}
    assert sorted(leaf.shape for leaf in jax.tree.leaves(
        eng.state.caches)) == sorted(
            [(POOL_BLOCKS, 1, 16, 256)] + [(SLOTS, 16, 1024)] * 3
            + [(SLOTS, 3072)] * 3)
    for kind, shape in shapes.items():
        leaves = {int(n) for n in re.findall(
            r"= " + re.escape(shape) + r"\{[^}]*\} parameter\((\d+)\)",
            entry)}
        assert len(leaves) == want[kind], (kind, leaves)
        assert leaves <= aliased, f"{name}: a {kind} leaf is not aliased"
        ops = set(re.findall(
            r"= " + re.escape(shape) + r"\{[^}]*\} ([\w-]+)\(", text))
        assert "copy" not in ops, f"{name} copies a {kind} leaf: {ops}"


def test_scopes_and_kernels_are_in_the_compiled_programs(programs):
    """The five ``l<i>_ssm*`` scopes in the decode step and the prefill;
    ``selective_scan`` in the prefill (the decode step's one-token update is
    a fused expression), ``flash_decode`` and ``kv_write`` in the decode
    step, reading the one-head pool."""
    decode = compiled_text(programs, "decode_step")
    prefill = compiled_text(programs, "prefill")
    for text in (decode, prefill):
        for what in ("in", "conv", "proj", "scan", "out"):
            assert re.search(rf"l\d+_ssm{what}\b", text), what
    assert "selective_scan" in kernels_in(prefill)
    assert "selective_scan" not in kernels_in(decode)
    assert {"flash_decode", "kv_write"} <= kernels_in(decode)


# ---------------------------------------------------- the benchmark's copy
def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(HERE, "reference_jamba.py"), "rb") as f:
        mine = hashlib.sha256(f.read()).hexdigest()
    with open(os.path.join(BENCH, "reference", "ai21-jamba2-3b.py"),
              "rb") as f:
        theirs = hashlib.sha256(f.read()).hexdigest()
    assert mine == theirs


def test_reference_class_is_the_drivers_interface(system):
    _, params0 = system
    seq = ids(60, 12)
    np.testing.assert_array_equal(
        ref.Reference(params0, TINY).logits(seq),
        reference_logits(params0, seq))
