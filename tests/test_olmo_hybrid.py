"""The hybrid gated-delta-rule / attention decoder (models/olmo_hybrid.py)
against its plain reference (tests/reference_olmo_hybrid.py): float32 on the
CPU at tiny widths (hidden 64, H 2, d_k 16, d_v 32, K 4; a 4-layer period of 3
delta-rule mixers and 1 full-attention layer, twice). The parameter counts at
the published widths; the forward pass; the ``gated_delta_rule`` and
``gated_delta_update`` kernels in interpret mode against the ``lax.scan``
form; the serving path through ``ServingEngine`` — prompts of different
lengths in one bucket, a request admitted while others decode, a slot freed
and taken again — against the reference's FULL forward under
tests/serving_oracle.py's contract (logits, not tokens); the planted faults
the oracle must refuse; the recurrent state's pricing and counters; the
compiled programs for a described v5e (every pool, state and tail leaf
aliased, the five scopes and the kernels in the text); the benchmark's copy
of the reference.

The tolerance is the oracle's form — float32 ulp of the reference's largest
logit — at ``ULP_LIMIT`` 2,048. Measured basis (CPU, f32, jax 0.9.0, PR 46):
a single mixer equals the reference to the bit and a one-layer model reads 9
ulp, but a delta-rule layer hands a relative perturbation on more than
doubled (its Jacobian on a random direction reads 2.2-2.8 x at every width
tried, the q . k and S^T k contractions cancelling; eight full-attention
layers together read 2.5 x), so the eight layers here read 110 and 101 ulp on
the two rows of the whole-sequence forward and 11-75 on the engine's prefill
and decode rows; over four other seeds of weights and prompts a position read
21-326 ulp and one 1,298. The limit is 18.6 times the largest reading of the
arrays compared here and 1.6 times the largest seen anywhere. The planted
faults, as multiples of the limit: a state one step stale 7,800 x, the decay
applied after the correction 4,800 x, ``beta`` without its 2 7,900 x, a conv
window shifted one row 7,900 x, a state rounded to bf16 every step 480 x, the
state taken at the padded tail instead of the last real token 6,100 x — the
limit's width costs the comparison nothing it has to see (``FAULT_FLOOR``).
"""
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmo_hybrid as ref
from serving_oracle import (assert_matches_reference, logit_gap,
                            logit_tolerance)
from test_jamba import kernels_in, record_logits

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmark")
with open(os.path.join(BENCH, "tests", "cells", "configs",
                       "olmo-hybrid-tiny.json")) as _f:
    TINY = json.load(_f)
FIELDS = TINY["builder"]["fields"]
BATCH, SEQ, BLOCK, MAX_LEN = 2, 32, 8, 64
ULP_LIMIT = 2048
#: every planted fault must read at least this many times the limit (the
#: issue asks for 10; the gentlest, the bf16 state, reads 480)
FAULT_FLOOR = 100


def assert_matches(got, want, what):
    assert_matches_reference(got, want, what, ulp_limit=ULP_LIMIT)


def tolerance(want):
    return logit_tolerance(want, ULP_LIMIT)


def olmo_config(**overrides):
    from flexflow_tpu.models.olmo_hybrid import OlmoHybridConfig

    kwargs = {field: TINY[key] for field, key in FIELDS.items()}
    kwargs.update(batch_size=BATCH, seq_len=SEQ)
    kwargs.update(overrides)
    return OlmoHybridConfig(**kwargs)


def build(cfg, seed=5, argv=()):
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.olmo_hybrid import build_olmo_hybrid

    config = FFConfig()
    config.parse_args(["-b", str(cfg.batch_size), *argv])
    config.seed = seed
    ff = FFModel(config)
    build_olmo_hybrid(ff, cfg)
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


@pytest.fixture(scope="module")
def system():
    ff = build(olmo_config())
    params0 = jax.device_get(ff.params)
    # lift the gains (the blocks' norms, the head's norm, the whole-width q
    # and k norms) off their constants so that one left out would show
    rng = np.random.default_rng(11)
    for group in params0.values():
        for w in group:
            if w in ("scale", "norm_w", "q_norm", "k_norm"):
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


# ------------------------------------------------------------ the counts
def test_parameter_count_is_the_builders(system):
    from flexflow_tpu.models.olmo_hybrid import olmo_hybrid_param_count

    ff, _ = system
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ff.params))
    assert held == olmo_hybrid_param_count(olmo_config())


def test_published_parameter_counts():
    """The closed form at the published widths: 88,750,332 a mixer, the
    configuration file's ``parameters_held`` at its 16 layers, and the whole
    model at the published 32."""
    from flexflow_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                 olmo_hybrid_mixer_params,
                                                 olmo_hybrid_param_count)

    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    kwargs = {field: config[key] for field, key in FIELDS.items()}
    cfg = OlmoHybridConfig(**kwargs)
    assert olmo_hybrid_mixer_params(cfg) == 88_750_332
    assert olmo_hybrid_param_count(cfg) == config["parameters_held"] \
        == 4_100_788_944
    kwargs["layer_types"] = config["published"]["layer_types"]
    assert olmo_hybrid_param_count(OlmoHybridConfig(**kwargs)) \
        == 7_430_870_688
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["layer_types"] == config["published"]["layer_types"][:16]


# ------------------------------------------------------------ the forward
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
    """A state one step stale under the ``q`` contraction, the decay applied
    after the correction instead of before it, ``beta`` without its factor
    2, the state rounded to bf16 every step, a conv window shifted one row:
    each, planted in the reference's recurrence, puts the system far outside
    the tolerance the sound comparison meets."""
    _, params0 = system
    x, got = forward
    want = reference_logits(params0, x[0], fault=fault)
    assert logit_gap(got[0], want) > FAULT_FLOOR * tolerance(want)


def test_mixer_layer_equals_the_reference():
    """One mixer alone, the op's whole-sequence form against the
    reference's: the same equations in the same order."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import OpContext, op_class_for

    op = op_class_for(OperatorType.OP_GATED_DELTA_MIXER)(
        "l0_gdn", {"num_heads": 2, "key_dim": 16, "value_dim": 32,
                   "conv_width": 4, "neg_eigval": True, "norm_eps": 1e-6},
        DataType.DT_FLOAT)
    key = jax.random.PRNGKey(0)
    params = {w: init(jax.random.fold_in(key, i), shape, jnp.float32)
              for i, (w, (shape, _t, init)) in enumerate(
                  op.weight_specs([(1, SEQ, 64)]).items())}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    got = op.forward(params, [u], OpContext(training=False))[0][0]
    with jax.default_matmul_precision("highest"):
        want = ref.delta_mixer(u[0], params, TINY)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_whole_width_qk_norm_is_not_the_norm_a_head():
    """``qk_norm_whole`` normalises q and k over every head at once with a
    gain a channel; the norm a head keeps its one gain vector."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.attention import _head_rms_norm, _whole_rms_norm
    from flexflow_tpu.ops.base import op_class_for

    attrs = {"embed_dim": 64, "num_heads": 2, "causal": True, "bias": False,
             "qk_norm": 1e-6}
    make = lambda a: op_class_for(OperatorType.OP_MULTIHEAD_ATTENTION)(
        "l3_attn", a, DataType.DT_FLOAT, num_inputs=3)
    shapes = [(1, 8, 64)] * 3
    assert make(attrs).weight_specs(shapes)["q_norm"][0] == (32,)
    whole = make({**attrs, "qk_norm_whole": True}).weight_specs(shapes)
    assert whole["q_norm"][0] == whole["k_norm"][0] == (2, 32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 2, 8, 32)),
                    jnp.float32) * jnp.asarray([1.0, 3.0])[None, :, None,
                                                           None]
    gain = jnp.ones((2, 32))
    got = _whole_rms_norm(x, gain, 1e-6)
    flat = jnp.transpose(x, (0, 2, 1, 3)).reshape(1, 8, 64)
    want = ref.rms_norm(flat, jnp.ones((64,)), 1e-6).reshape(
        1, 8, 2, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert float(jnp.max(jnp.abs(
        got - _head_rms_norm(x, gain[0], 1e-6)))) > 0.1


# --------------------------------------------------------------- the kernels
def rule_inputs(batch, length, heads, dk, dv, seed, equal_keys=False):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = f32(batch, length, heads, dk), f32(batch, length, heads, dk)
    if equal_keys:      # a run of equal keys: A is all ones below the diagonal
        k = jnp.broadcast_to(k[:, :1], k.shape)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.abs(f32(batch, length, heads)) * (0.01 if equal_keys else 1.5)
    beta = 2.0 * jax.nn.sigmoid(f32(batch, length, heads))
    return q, k, f32(batch, length, heads, dv), g, beta, \
        f32(batch, heads, dk, dv)


@pytest.mark.parametrize("batch,length,heads,dk,dv,short,initial,equal", [
    (1, 128, 2, 16, 32, 0, False, False),   # whole chunks, no state
    (2, 37, 2, 16, 32, 5, True, False),     # under a chunk, rows cut short
    (1, 200, 3, 24, 40, 72, True, False),   # no multiple of 64, a carried state
    (3, 130, 2, 16, 32, 129, False, False),  # one real token of 130
    (1, 192, 2, 16, 32, 0, True, True),     # equal keys: the solve's hard case
])
def test_chunked_kernel_equals_the_scan(batch, length, heads, dk, dv, short,
                                        initial, equal):
    from flexflow_tpu.kernels.gated_delta_rule import (
        gated_delta_rule, gated_delta_rule_reference)

    q, k, v, g, beta, s0 = rule_inputs(batch, length, heads, dk, dv, length,
                                       equal)
    s0 = s0 if initial else None
    lengths = jnp.asarray([length - short] * batch, jnp.int32)
    want_o, want_s = gated_delta_rule_reference(q, k, v, g, beta, s0=s0,
                                                lengths=lengths)
    got_o, got_s = gated_delta_rule(q, k, v, g, beta, s0=s0, lengths=lengths,
                                    interpret=True)
    n = length - short
    np.testing.assert_allclose(got_o[:, :n], want_o[:, :n], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-5, atol=2e-5)
    # rows past the length leave the state where the last real token put it
    _, cut_s = gated_delta_rule_reference(
        q[:, :n], k[:, :n], v[:, :n], g[:, :n], beta[:, :n], s0=s0)
    np.testing.assert_allclose(got_s, cut_s, rtol=2e-5, atol=2e-5)


#: (heads, d_v) -> the heads a row of the state at rest holds
HEADS_A_ROW = [((30, 192), 2), ((4, 128), 1), ((4, 256), 1), ((4, 64), 2),
               ((4, 96), 4), ((8, 32), 4), ((3, 192), 1), ((2, 32), 1),
               ((6, 96), 1), ((8, 16), 1)]


@pytest.mark.parametrize("heads,dv,p", [hd + (p,) for hd, p in HEADS_A_ROW])
def test_pack_state_round_trip(heads, dv, p):
    """``p`` from the shapes alone (the least heads whose lanes are whole
    128-lane tiles, when it divides H and is at most 4); head ``r p + i``
    rests on row ``r``, lanes ``[i d_v, (i + 1) d_v)``; unpacking is the
    inverse; at ``p = 1`` nothing moves."""
    from flexflow_tpu.kernels.gated_delta_rule import (pack_state,
                                                       state_heads_a_row,
                                                       unpack_state)

    dk = 8
    assert state_heads_a_row(heads, dv) == p
    s = jnp.arange(3 * heads * dk * dv, dtype=jnp.float32).reshape(
        3, heads, dk, dv)
    packed = pack_state(s)
    assert packed.shape == (3, heads // p, dk, p * dv)
    assert p == 1 or (p * dv) % 128 == 0
    for h in range(heads):
        r, i = divmod(h, p)
        np.testing.assert_array_equal(
            packed[:, r, :, i * dv:(i + 1) * dv], s[:, h])
    np.testing.assert_array_equal(unpack_state(packed, dv), s)
    if p == 1:
        assert packed is s and unpack_state(s, dv) is s
    # a leading axis more or less: the layers' stack, one slot
    np.testing.assert_array_equal(pack_state(s[0]), packed[0])
    np.testing.assert_array_equal(unpack_state(packed[None], dv), s[None])


def _pr46_update(s, q, k, v, g, beta):
    """PR 46's ``gated_delta_update`` — a head a row of the state, ``k`` and
    ``q`` a ``(d_k, 1)`` column a head — kept here as the oracle of "the same
    arithmetic on the same numbers in the same order": the packed kernel's
    results are bit-equal to it."""
    import functools

    import jax.lax as lax
    from jax.experimental import pallas as pl

    from flexflow_tpu.kernels.gated_delta_rule import update_heads

    f32 = jnp.float32
    rows, H, dk, dv = s.shape
    hb = update_heads(H, dk, dv)
    n_t = H // hb

    def kernel(q_ref, k_ref, v_ref, a_ref, b_ref, kq_ref, s_ref, o_ref,
               s_out_ref):
        row = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
        eye = jnp.where(row == col, 1.0, 0.0).astype(f32)
        columns = lambda ref: lax.dot_general(
            eye, ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=lax.Precision.HIGHEST)
        kc, qc = columns(k_ref), columns(q_ref)
        v, a, b, kq = v_ref[0, 0], a_ref[0, 0], b_ref[0, 0], kq_ref[0, 0]
        for i in range(hb):
            s = s_ref[0, i]
            k_i, q_i, a_i = kc[:, i:i + 1], qc[:, i:i + 1], a[i:i + 1]
            sk = jnp.sum(s * k_i, axis=0, keepdims=True)
            sq = jnp.sum(s * q_i, axis=0, keepdims=True)
            u = b[i:i + 1] * (v[i:i + 1] - a_i * sk)
            o_ref[0, 0, i:i + 1, :] = a_i * sq + kq[i:i + 1] * u
            s_out_ref[0, i] = a_i * s + k_i * u

    spread = lambda t: jnp.broadcast_to(
        t[..., None], (rows, H, dv)).reshape(rows, n_t, hb, dv)
    tiles = lambda t: t.reshape(rows, n_t, hb, t.shape[-1])
    per_head = lambda w: pl.BlockSpec((1, 1, hb, w), lambda r, t: (r, t, 0, 0))
    state = pl.BlockSpec((1, hb, dk, dv), lambda r, t: (r, t, 0, 0))
    o, s_new = pl.pallas_call(
        kernel, grid=(rows, n_t),
        in_specs=[per_head(dk), per_head(dk)] + [per_head(dv)] * 4 + [state],
        out_specs=[per_head(dv), state],
        out_shape=[jax.ShapeDtypeStruct((rows, n_t, hb, dv), f32),
                   jax.ShapeDtypeStruct((rows, H, dk, dv), f32)],
        interpret=True,
    )(tiles(q), tiles(k), tiles(v), spread(jnp.exp(g)), spread(beta),
      spread(jnp.sum(k * q, axis=-1)), s)
    return o.reshape(rows, H, dv), s_new


@pytest.mark.parametrize("rows,heads,dk,dv,p", [
    (2, 30, 96, 192, 2),     # the published widths: two heads a row
    (3, 4, 16, 128, 1),      # whole lane tiles already: PR 46's form
    (3, 4, 16, 64, 2),
    (3, 4, 16, 96, 4),
    (2, 3, 16, 192, 1),      # an odd count of heads falls to a head a row
    (3, 2, 16, 32, 1),       # four would share a row: two heads do not
    (4, 6, 8, 32, 1),
])
def test_update_kernel_on_the_state_at_rest(rows, heads, dk, dv, p):
    """64 decode steps of the ``gated_delta_update`` kernel on the state as
    it rests, ``p`` heads a row: bit-equal, output and state, to the same
    kernel on the unpacked state and to PR 46's kernel; within rounding of
    the fused expression step by step and of the scan over the 64 tokens; a
    row told it is free (decay 0, beta 0) stays zero."""
    from flexflow_tpu.kernels.gated_delta_rule import (
        gated_delta_rule_reference, gated_delta_update, one_token_update,
        pack_state, state_heads_a_row, unpack_state, update_heads)

    steps = 64
    assert state_heads_a_row(heads, dv) == p
    q, k, v, g, beta, s0 = rule_inputs(rows, steps, heads, dk, dv, rows)
    g, beta = g.at[0].set(-jnp.inf), beta.at[0].set(0.0)
    s0 = s0.at[0].set(0.0)
    kernel = jax.jit(lambda *a: gated_delta_update(*a, interpret=True))
    fused, pr46 = jax.jit(one_token_update), jax.jit(_pr46_update)
    s_packed = s_fused = pack_state(s0)
    s_plain = s_old = s0
    assert s_packed.shape == (rows, heads // p, dk, p * dv)
    outs = []
    for t in range(steps):
        row = (q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        o, s_packed = kernel(s_packed, *row)
        o_plain, s_plain = kernel(s_plain, *row)
        o_old, s_old = pr46(s_old, *row)
        o_fused, s_fused = fused(s_fused, *row)
        for other in (o_plain, o_old):
            np.testing.assert_array_equal(o, other)
        np.testing.assert_allclose(o, o_fused, rtol=2e-5, atol=2e-5)
        outs.append(o)
    got_s = unpack_state(s_packed, dv)
    assert s_packed.shape == s_fused.shape == pack_state(s0).shape
    np.testing.assert_array_equal(got_s, s_plain)
    np.testing.assert_array_equal(got_s, s_old)
    np.testing.assert_allclose(s_packed, s_fused, rtol=2e-5, atol=2e-5)
    got_o = jnp.stack(outs, axis=1)
    assert not np.asarray(got_s[0]).any() and not np.asarray(got_o[0]).any()
    scan_o, scan_s = gated_delta_rule_reference(
        q[1:], k[1:], v[1:], g[1:], beta[1:], s0=s0[1:])
    np.testing.assert_allclose(got_o[1:], scan_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_s[1:], scan_s, rtol=2e-5, atol=2e-5)
    assert (heads // p) % update_heads(heads // p, dk, p * dv) == 0


def test_a_state_of_another_width_is_refused():
    from flexflow_tpu.kernels.gated_delta_rule import gated_delta_update

    q, k, v, g, beta, s0 = rule_inputs(2, 1, 4, 16, 64, 0)
    with pytest.raises(ValueError, match="does not hold 4 heads of 64"):
        gated_delta_update(s0[:, :3], q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                           beta[:, 0], interpret=True)


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
    # the prefills' rows: the buckets', and the real ones among them
    assert st.prefill_rows_real == sum(lengths)
    assert st.prefill_rows == sum(16 if n <= 16 else 32 for n in lengths)
    assert st.summary()["prefill_rows"] == st.prefill_rows


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
        assert_matches(rows, want[n:], f"decode rows of request {slot}")


def test_a_slot_freed_and_taken_again(system):
    """One slot: the second tenant's state is written whole over the
    first's, so its stream and logits equal a fresh engine's; and a slot
    nobody holds rests at zero."""
    ff, params0 = system
    first, second = [int(t) for t in ids(40, 15)], \
        [int(t) for t in ids(41, 10)]
    eng = engine(ff, n_slots=1)
    eng.generate([first], max_new_tokens=9)
    name = next(k for k in eng.state.caches if "gdn" in k)
    # the slot is free now: run one step over the empty batch
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


@pytest.fixture(scope="module")
def paired():
    """The tiny model at ``d_v`` 64: its two heads share a 128-lane row of
    the state at rest (``system``'s ``d_v`` 32 would put four in a row and
    has two: a head a row)."""
    ff = build(olmo_config(linear_value_head_dim=64))
    return ff, jax.device_get(ff.params), dict(TINY, linear_value_head_dim=64)


def test_prefill_hand_off_then_decode_two_heads_a_row(paired):
    """The prefill packs its last state once as it hands it to the slot and
    the decode steps update it as it rests: every row equals the
    reference's FULL forward, and the counters say how the state rests."""
    ff, params0, config = paired
    eng = engine(ff)
    prefill, decode = record_logits(eng)
    prompts = [[int(t) for t in ids(70 + k, n)]
               for k, n in enumerate((13, 16, 27))]
    outs = eng.generate(prompts, max_new_tokens=8)
    for slot, (prompt, out) in enumerate(zip(prompts, outs)):
        seq = np.asarray(prompt + out, np.int32)
        want = ref.logits(params0, seq[:-1], config)
        n = len(prompt)
        assert_matches(prefill[slot], want[n - 1], "prefill row")
        rows = np.stack([decode[(slot, t)] for t in range(n, len(seq) - 1)])
        assert_matches(rows, want[n:], "decode rows")
    states = [entry[1] for name, entry in eng.state.caches.items()
              if name not in eng._paged_entry_names]
    # H 2, d_k 16, d_v 64: one row of two heads, 128 lanes
    assert [s.shape for s in states] == [(4, 1, 16, 128)] * 6
    st = eng.stats
    assert st.state_heads_a_row == 2
    # the states rest in whole tiles (the logical bytes); a tail of 3 x
    # (2 x 2 x 16 + 2 x 64) = 576 numbers a slot rests 4 slots in 8
    # sublanes and 576 lanes in 640
    assert st.recurrent_state_bytes_at_rest \
        == 6 * (4 * 2 * 16 * 64 * 4 + 8 * 640 * 4)
    assert eng._recurrent_slot_bytes() \
        == 6 * (2 * 16 * 64 * 4 + 576 * 4)
    summary = st.summary()
    assert summary["state_heads_a_row"] == 2
    assert summary["recurrent_state_bytes_at_rest"] \
        == st.recurrent_state_bytes_at_rest


def test_the_counters_of_the_state_at_rest(system):
    """``recurrent_state_bytes_at_rest`` counts what the chip pads — a head
    a row of ``d_v`` 32 rests in 128 lanes, four times its bytes — beside
    the logical ``recurrent_state_bytes``; both reach the telemetry's
    serving block, once, not summed over the steps."""
    from flexflow_tpu.serving.kvcache import tiled_bytes

    ff, _ = system
    ff._telemetry_requested = True
    eng = engine(ff)
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=6)
    st = eng.stats
    state = 4 * 2 * 16 * 128 * 4          # (4, 2, 16, 32) f32 in 128 lanes
    tail = 8 * 384 * 4                    # (4, 384) f32 in 8 sublanes
    assert st.state_heads_a_row == 1
    assert st.recurrent_state_bytes_at_rest == 6 * (state + tail)
    assert st.recurrent_state_bytes_at_rest \
        > eng.n_slots * eng._recurrent_slot_bytes()
    assert st.recurrent_state_bytes == st.decode_steps * 2 * eng.n_slots \
        * eng._recurrent_slot_bytes()
    assert tiled_bytes((64, 15, 96, 384), 4) == 64 * 15 * 96 * 384 * 4
    assert tiled_bytes((64, 30, 96, 192), 4) == 64 * 30 * 96 * 256 * 4
    assert tiled_bytes((64, 34560), 2) == 64 * 34560 * 2
    assert tiled_bytes((5, 100), 2) == 16 * 128 * 2
    assert tiled_bytes((7,), 4) == 128 * 4
    block = ff.get_telemetry().summary()["serving"]
    assert block["recurrent_state_bytes"] == st.recurrent_state_bytes
    assert block["recurrent_state_bytes_at_rest"] == 6 * (state + tail)
    assert block["state_heads_a_row"] == 1


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
    assert gaps[1] > FAULT_FLOOR * tol


def test_chunking_and_the_prefix_cache_are_refused(system):
    from flexflow_tpu.serving import ServingEngine

    ff, _ = system
    with pytest.raises(ValueError,
                       match="recurrent node.*OP_GATED_DELTA_MIXER"):
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


# ------------------------------------------------------------- the pricing
def test_the_op_says_what_a_slot_holds(system):
    """``Op.slot_state_bytes``: 0 by default, the three recurrent ops
    answer, and kvcache, the engine and the fusion rule ask the op."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for
    from flexflow_tpu.serving.kvcache import (is_recurrent, node_slot_bytes,
                                              node_token_bytes)

    ff, _ = system
    nodes = {n.name: n.op for n in ff.executor.pcg.compute_nodes()}
    mixer = next(op for name, op in nodes.items() if "_gdn" in name)
    attn = next(op for name, op in nodes.items() if "_attn" in name)
    # H x d_k x d_v float32 and (K - 1) x (2 H d_k + H d_v) in the node's
    # dtype (float32 here)
    assert mixer.slot_state_bytes() == node_slot_bytes(mixer) \
        == 2 * 16 * 32 * 4 + 3 * (2 * 2 * 16 + 2 * 32) * 4
    assert attn.slot_state_bytes() == 0 and node_token_bytes(mixer) == 0
    assert is_recurrent(mixer) and not is_recurrent(attn)
    assert all(op.slot_state_bytes() == 0 for name, op in nodes.items()
               if "_gdn" not in name)
    # two K/V heads of 32 + 32 numbers
    assert node_token_bytes(attn) == 2 * (32 + 32) * 4
    eng = engine(ff)
    assert eng._recurrent_slot_bytes() == 6 * mixer.slot_state_bytes()
    assert eng._kv_row_bytes() == 2 * node_token_bytes(attn)
    lstm = op_class_for(OperatorType.OP_LSTM)(
        "lstm", {"hidden_size": 12}, DataType.DT_FLOAT)
    assert lstm.slot_state_bytes() == node_slot_bytes(lstm) == 2 * 12 * 4
    assert lstm.slot_state_bytes(2) == 2 * 12 * 2


def test_the_serving_search_prices_the_state_a_slot(system):
    """``_graph_cost``: four slots more cost four slots' state (what the op
    says a slot holds) and four slots' pool rows, nothing else."""
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.search.simulator import Simulator
    from flexflow_tpu.serving.kvcache import node_token_bytes
    from flexflow_tpu.serving.search import _graph_cost, reshape_graph

    ff, _ = system
    pcg = ff.executor.pcg
    sim = Simulator(TPUMachineModel.detect(1))
    g = reshape_graph(pcg, 4, 1)
    t4, mem4, _ = _graph_cost(sim, g, 1, 1, 4, MAX_LEN, decode=True)
    t8, mem8, _ = _graph_cost(sim, g, 1, 1, 8, MAX_LEN, decode=True)
    ops = [n.op for n in pcg.compute_nodes()]
    state = sum(op.slot_state_bytes() for op in ops)
    pool = MAX_LEN * sum(node_token_bytes(op) for op in ops)
    assert state == 6 * (2 * 16 * 32 * 4 + 3 * 128 * 4)
    assert mem8 - mem4 == 4 * (state + pool)
    m = sim.machine
    assert t8 - t4 == pytest.approx(
        4 * (pool + 2 * state) / (m.hbm_bandwidth * m.hbm_efficiency))


def test_published_slot_and_token_bytes():
    """The issue's arithmetic at the published widths: 2,280,960 B of state
    a layer and slot (2,211,840 of float32 state, 69,120 of bf16 tails),
    27,371,520 B a slot over 12 mixers, 61,440 B of K/V a token over 4
    full-attention layers."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for
    from flexflow_tpu.serving.kvcache import node_token_bytes

    mixer = op_class_for(OperatorType.OP_GATED_DELTA_MIXER)(
        "l0_gdn", {"num_heads": 30, "key_dim": 96, "value_dim": 192,
                   "conv_width": 4, "neg_eigval": True, "norm_eps": 1e-6},
        DataType.DT_BFLOAT16)
    attn = op_class_for(OperatorType.OP_MULTIHEAD_ATTENTION)(
        "l3_attn", {"embed_dim": 3840, "num_heads": 30},
        DataType.DT_BFLOAT16, num_inputs=3)
    assert mixer.slot_state_bytes() == 2_211_840 + 69_120
    assert 12 * mixer.slot_state_bytes() == 27_371_520
    assert node_token_bytes(attn) == 15_360
    assert 4 * node_token_bytes(attn) == 61_440


# ----------------------------------------- the compiled programs, for a v5e
SLOTS, POOL_BLOCKS, DEPTH = 8, 65, 4
HEADS, DK, DV = 4, 64, 192
#: the state at rest: two heads of 192 lanes a row, three whole lane tiles
STATE = (SLOTS, HEADS // 2, DK, 2 * DV)
#: the cell's: 64 slots, 30 heads, d_k 96, d_v 192
PUBLISHED = (64, 30, 96, 192)


@pytest.fixture(scope="module")
def programs():
    """The prefill, the decode step and the slot write of a 4-layer model
    (3 mixers, 1 full-attention layer) at the published lane widths (d_v
    192: two heads a row of the state; the attention heads 128), lowered
    for a described v5e with the kernels' gates answering as on a TPU; and
    the update kernel alone at the cell's shape, on the state as it rests
    and on a head a row."""
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
    ff = build(olmo_config(batch_size=8, hidden=512, num_heads=4,
                           head_dim=128, layer_types=TINY["layer_types"][:4],
                           intermediate=256, linear_num_key_heads=HEADS,
                           linear_num_value_heads=HEADS,
                           linear_key_head_dim=DK, linear_value_head_dim=DV,
                           vocab_size=512),
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

    def update(state_shape):
        from flexflow_tpu.kernels.gated_delta_rule import gated_delta_update

        slots, heads, dk, dv = PUBLISHED
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                                  sharding=chip)
        return (jax.jit(gated_delta_update, donate_argnums=(0,)),
                (f32(*state_shape), f32(slots, heads, dk),
                 f32(slots, heads, dk), f32(slots, heads, dv),
                 f32(slots, heads), f32(slots, heads)))

    try:
        yield eng, {
            "update_at_rest": update((64, 15, 96, 384)),
            "update_a_head_a_row": update(PUBLISHED),
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


def compiled(programs, name):
    fn, args = programs[1][name]
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()


def compiled_text(programs, name):
    return compiled(programs, name).as_text()


def async_copies_of(text, shape):
    """The compiled program's asynchronous copies that carry ``shape``."""
    return [line for line in text.splitlines() if shape in line
            and re.search(r"\b(copy|slice|dynamic-slice)-start\(", line)]


@pytest.mark.parametrize("name,lanes", [("update_at_rest", 384),
                                        ("update_a_head_a_row", 256)])
def test_update_kernel_rests_its_state_where_it_is_put(programs, name,
                                                       lanes):
    """``gated_delta_update`` alone at the cell's shape, through Mosaic for
    a described v5e: the state parameter keeps the layout it is handed
    (``{3,2,1,0}`` in ``(8, 128)`` tiles), is aliased onto the result, and
    the program holds no temporary. Two heads a row the arguments are the
    matrices' own bytes; a head a row (PR 46's shape, the form ``p = 1``
    keeps for an odd count of heads) they are a third more."""
    c = compiled(programs, name)
    text, ma = c.as_text(), c.memory_analysis()
    state = programs[1][name][1][0].shape
    shape = "f32[%d,%d,%d,%d]" % state
    assert re.search(re.escape(shape) + r"\{3,2,1,0:T\(8,128\)\} "
                     r"parameter\(0\)", text)
    assert "gated_delta_update" in kernels_in(text)
    assert ma.temp_size_in_bytes == 0
    at_rest = state[0] * state[1] * state[2] * lanes * 4
    assert ma.alias_size_in_bytes == at_rest
    assert (lanes == 384) == (at_rest == 64 * 30 * 96 * 192 * 4)
    assert at_rest < ma.argument_size_in_bytes < at_rest + 5e6
    assert not async_copies_of(text, shape) and not re.search(
        r"= " + re.escape(shape) + r"\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("name", ["decode_step", "slot_write"])
def test_pool_and_state_are_written_in_place(programs, name):
    """Every leaf of the decode state — the K/V pool, each mixer's float32
    state and its conv tails — is aliased onto an output of the decode step
    and of the slot write, and none is rewritten by a copy."""
    eng = programs[0]
    text = compiled_text(programs, name)
    entry = text[text.index("\nENTRY "):]
    header = text[:text.index("\n")]
    start = header.index("input_output_alias={")
    aliased = {int(n) for n in re.findall(
        r"\}: \((\d+), ", header[start:header.index(" }", start)])}
    channels = HEADS * (2 * DK + DV)
    shapes = {
        "pool": f"bf16[{POOL_BLOCKS},4,16,256]",
        "state": "f32[%d,%d,%d,%d]" % STATE,
        "tail": f"bf16[{SLOTS},{3 * channels}]",
    }
    want = {"pool": 1, "state": DEPTH - 1, "tail": DEPTH - 1}
    assert sorted(leaf.shape for leaf in jax.tree.leaves(
        eng.state.caches)) == sorted(
            [(POOL_BLOCKS, 4, 16, 256)] + [STATE] * 3
            + [(SLOTS, 3 * channels)] * 3)
    for kind, shape in shapes.items():
        leaves = {int(n) for n in re.findall(
            r"= " + re.escape(shape) + r"\{[^}]*\} parameter\((\d+)\)",
            entry)}
        assert len(leaves) == want[kind], (kind, leaves)
        assert leaves <= aliased, f"{name}: a {kind} leaf is not aliased"
        ops = set(re.findall(
            r"= " + re.escape(shape) + r"\{[^}]*\} ([\w-]+)\(", text))
        assert "copy" not in ops, f"{name} copies a {kind} leaf: {ops}"
    # the state rests as the update computes on it, in whole tiles (at
    # these 1.5 MB a layer the compiler prefetches a leaf into fast memory
    # beside other ops; at the cell's 142 MB it cannot:
    # test_the_cells_decode_step_rests_its_state_two_heads_a_row)
    assert len(re.findall(re.escape(shapes["state"])
                          + r"\{3,2,1,0:T\(8,128\)\} parameter\(", entry)) \
        == DEPTH - 1


def test_the_cells_decode_step_rests_its_state_two_heads_a_row(programs):
    """The ``olmo-hybrid-7b-assist`` cell's decode step — its widths, slots,
    pool and vocabulary at depth 4, three mixers and a full-attention layer,
    no weight ever made — compiled for a described v5e: each state leaf is
    ``f32[64,15,96,384]`` in whole ``(8, 128)`` tiles, aliased onto the
    step's result, and nothing carries it but the update itself — no copy
    into or out of it and NO asynchronous copy, so the time the benchmark's
    ``gdn_state_roofline`` reads under ``l_gdnrule`` is the whole of the
    state's and the share cannot pass 100%."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.execution.executor import Executor
    from flexflow_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                 build_olmo_hybrid)
    from flexflow_tpu.serving import ServingEngine
    from flexflow_tpu.serving.kvcache import DecodeState

    with open(os.path.join(BENCH, "workloads",
                           "olmo-hybrid-7b-assist.json")) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    chip = programs[1]["update_at_rest"][1][0].sharding
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    kwargs = {field: config[key]
              for field, key in config["builder"]["fields"].items()}
    kwargs.update(batch_size=8, layer_types=config["layer_types"][:DEPTH])
    cfg = OlmoHybridConfig(**kwargs)
    ffc = FFConfig()
    ffc.parse_args(["-b", "8"] + config["compile_flags"]
                   + cell["compile_flags"])

    def shapes(self, seed=0):
        out = {}
        for node, wname, shape, _dtype, _init in self.weight_entries():
            out.setdefault(node.name, {})[wname] = sds(tuple(shape),
                                                       jnp.bfloat16)
        return out

    real_init = Executor.init_params
    Executor.init_params = shapes
    try:
        ff = FFModel(ffc)
        build_olmo_hybrid(ff, cfg)
        ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    finally:
        Executor.init_params = real_init
    e = cell["engine"]
    eng = ServingEngine(ff, n_slots=e["n_slots"],
                        max_decode_len=e["max_decode_len"],
                        kv_pool_blocks=e["kv_pool_blocks"],
                        buckets=tuple(e["buckets"]))
    # the decode state, from the shapes a prefill hands the slots
    b0 = eng.buckets[0]
    cache = jax.eval_shape(eng._prefill_fn(b0), ff.params,
                           [sds((1, b0), jnp.int32)],
                           sds((1,), jnp.int32))[2]
    state_shape = (e["n_slots"], 15, 96, 384)
    caches, eng._paged_entry_names = {}, set()
    for name, entry in cache.items():
        if "_attn" in name:
            eng._paged_entry_names.add(name)
            caches[name] = sds((eng.kv_pool_blocks, cfg.num_heads,
                                eng.kv_block_size, 2 * cfg.head_dim),
                               jnp.bfloat16)
        else:
            tail, s = entry
            assert s.shape == (1,) + state_shape[1:]
            caches[name] = tuple(sds((eng.n_slots,) + leaf.shape[1:],
                                     leaf.dtype) for leaf in (tail, s))
    state = DecodeState(
        caches=caches, lengths=sds((eng.n_slots,), jnp.int32),
        block_tables=sds((eng.n_slots, eng.max_blocks_per_slot), jnp.int32))
    c = eng._decode_fn(guard=False).trace(
        ff.params, [sds((eng.n_slots, 1), jnp.int32)], state).lower(
            lowering_platforms=("tpu",)).compile()
    text = c.as_text()
    entry = text[text.index("\nENTRY "):]
    header = text[:text.index("\n")]
    start = header.index("input_output_alias={")
    aliased = {int(n) for n in re.findall(
        r"\}: \((\d+), ", header[start:header.index(" }", start)])}
    shape = "f32[%d,%d,%d,%d]" % state_shape
    leaves = {int(n) for n in re.findall(
        r"= " + re.escape(shape) + r"\{3,2,1,0:T\(8,128\)\} "
        r"parameter\((\d+)\)", entry)}
    assert len(leaves) == DEPTH - 1 and leaves <= aliased
    ops = set(re.findall(
        r"= " + re.escape(shape) + r"\{[^}]*\} ([\w-]+)\(", text))
    assert ops <= {"parameter", "get-tuple-element"}, ops
    assert not async_copies_of(text, shape)
    assert {"gated_delta_update", "flash_decode", "kv_write"} \
        <= kernels_in(text)
    # the step's temporaries: the logits and a layer's activations, a
    # fifth of ONE mixer's state over the slots
    assert c.memory_analysis().temp_size_in_bytes < 64 * 15 * 96 * 384 * 4 / 4


def test_scopes_and_kernels_are_in_the_compiled_programs(programs):
    """The five ``l<i>_gdn*`` scopes in the decode step and the prefill;
    ``gated_delta_rule`` in the prefill, ``gated_delta_update``,
    ``flash_decode`` and ``kv_write`` in the decode step."""
    decode = compiled_text(programs, "decode_step")
    prefill = compiled_text(programs, "prefill")
    for text in (decode, prefill):
        for what in ("in", "conv", "gate", "rule", "out"):
            assert re.search(rf"l\d+_gdn{what}\b", text), what
    assert "gated_delta_rule" in kernels_in(prefill)
    assert "gated_delta_rule" not in kernels_in(decode)
    assert {"gated_delta_update", "flash_decode", "kv_write"} \
        <= kernels_in(decode)


# ---------------------------------------------------- the benchmark's copy
def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(HERE, "reference_olmo_hybrid.py"), "rb") as f:
        mine = hashlib.sha256(f.read()).hexdigest()
    with open(os.path.join(BENCH, "reference", "olmo-hybrid-7b.py"),
              "rb") as f:
        theirs = hashlib.sha256(f.read()).hexdigest()
    assert mine == theirs


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(HERE, "reference_olmo_hybrid.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import) +flexflow_tpu", text, re.M)
    assert "lax.scan" in text and "chunk" not in text.split('"""')[2]


def test_reference_class_is_the_drivers_interface(system):
    _, params0 = system
    seq = ids(60, 12)
    np.testing.assert_array_equal(
        ref.Reference(params0, TINY).logits(seq),
        reference_logits(params0, seq))
