"""The hybrid delta-rule / latent-attention decoder over a routed layer
(models/gigachat.py) against its plain reference
(benchmark/reference/gigachat35-432b-a28b.py, loaded by its path: there is
one copy): float32 on the CPU at tiny widths (hidden 64; 2 key heads under 4
value heads of 16 x 16; 4 latent heads on a 16 + 8 row; 8 of 16 experts held;
one dense delta-rule layer, then three delta-rule layers and one latent layer
over experts). The parameter counts at the published widths; the forward pass;
prefill then decode through BOTH caches in one graph — the latent pool and the
slot-major state — against the reference's FULL forward on logits, with a
free slot between two live ones; the pieces one at a time (grouped key heads,
the sigmoid2 gate, YaRN and its softmax scale, the neighbour pairing, the
latent gate in the absorbed and the materialised form, the clamp in the dense
MLP and in the routed experts on both of their paths); the shares adding up
to the uncut layer; the engine's refusals and counters; the programs of the
two configurations whose ops this one shares, digest-equal to their parent's.

The tolerance is the serving oracle's form — float32 ulp of the reference's
largest logit — at ``ULP_LIMIT`` 2,048, tests/test_olmo_hybrid.py's: a
delta-rule layer hands a relative perturbation on more than doubled (measured
there), and this graph has four of them under a latent layer. Measured here
(CPU, f32, jax 0.9.0, PR 53): the whole-sequence forward reads 72 ulp on
each of its two rows, the engine's prefill rows 13-41 and its decode rows
13-55: the limit is 28 times the largest. A piece changed in the reference
alone reads over 20 times the limit (``test_comparison_refuses...``).
"""
import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_oracle import assert_matches_reference, logit_tolerance
from test_jamba import record_logits

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "reference", "gigachat35-432b-a28b.py"),
            "reference_gigachat")
with open(os.path.join(BENCH, "tests", "cells", "configs",
                       "gigachat-tiny.json")) as _f:
    TINY = json.load(_f)
with open(os.path.join(BENCH, "configs", "gigachat35-432b-a28b.json")) as _f:
    PUBLISHED = json.load(_f)
FIELDS = TINY["builder"]["fields"]
BATCH, SEQ, BLOCK, MAX_LEN = 2, 32, 8, 96
ULP_LIMIT = 2048
NO_ALT = jnp.zeros((0,), jnp.int32)


def assert_matches(got, want, what):
    assert_matches_reference(got, want, what, ulp_limit=ULP_LIMIT)


def giga_config(source=TINY, **overrides):
    from flexflow_tpu.models.gigachat import GigaChatConfig

    kwargs = {field: source[key] for field, key in FIELDS.items()}
    kwargs.update(batch_size=BATCH, seq_len=SEQ)
    kwargs.update(overrides)
    return GigaChatConfig(**kwargs)


def build(cfg, seed=5, argv=()):
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.gigachat import build_gigachat

    config = FFConfig()
    config.parse_args(["-b", str(cfg.batch_size), *argv])
    config.seed = seed
    ff = FFModel(config)
    build_gigachat(ff, cfg)
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


@pytest.fixture(scope="module")
def system():
    ff = build(giga_config())
    return ff, jax.device_get(ff.params)


def ids(seed=0, n=SEQ):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).astype(np.int32)


def reference_logits(params0, seq, config=TINY):
    return ref.Reference(params0, config, route_tie=0.0).logits(seq)


def engine(ff, **kw):
    from flexflow_tpu.serving import ServingEngine

    kw.setdefault("n_slots", 3)
    return ServingEngine(ff, max_decode_len=MAX_LEN, kv_block_size=BLOCK,
                         buckets=(16, 32), **kw)


# ------------------------------------------------------------ the counts
def test_parameter_count_is_the_builders(system):
    from flexflow_tpu.models.gigachat import gigachat_param_count

    ff, params0 = system
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ff.params))
    assert held == gigachat_param_count(giga_config())
    assert not any("expert_bias" in g for g in ff.params.values())
    # no zero-centred gain is the constant: a gain left out would show
    for name, group in params0.items():
        if "norm" in name:
            assert np.std(group["scale"]) > 0.005, name
        if "gdn" in name:
            assert np.std(group["norm_w"]) > 0.005, name


@pytest.mark.parametrize("what,want", [
    ("delta_mixer", 235_864_320), ("latent", 159_844_352),
    ("expert_part", 750_518_272), ("whole", 4_731_721_728)])
def test_published_parameter_counts(what, want):
    """ISSUE 53's table, from the builder's closed forms at the published
    widths and the cell's cut; the whole is the file's ``parameters_held``."""
    from flexflow_tpu.models import gigachat as g

    cfg = giga_config(PUBLISHED)
    got = {"delta_mixer": g.gigachat_delta_mixer_params,
           "latent": g.gigachat_latent_params,
           "expert_part": g.gigachat_expert_part_params,
           "whole": g.gigachat_param_count}[what](cfg)
    assert got == want
    if what == "whole":
        assert got == PUBLISHED["parameters_held"]


def test_no_width_differs_from_the_catalog_row():
    """Every key of the configuration's file that is not in ``reduced``
    holds the published value; the reduced ones say so with the published
    value beside them."""
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "kv_lora_rank", "q_lora_rank",
              "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
              "num_experts_per_tok", "linear_key_head_dim",
              "linear_value_head_dim", "linear_num_key_heads",
              "linear_num_value_heads", "linear_conv_kernel_dim")
    want = (7168, 18432, 2048, 64, 512, 1536, 64, 128, 128, 8, 128, 128, 32,
            64, 4)
    assert tuple(PUBLISHED[k] for k in widths) == want
    assert not set(widths) & set(PUBLISHED["reduced"])
    assert set(PUBLISHED["reduced"]) == set(PUBLISHED["published"])
    assert PUBLISHED["router_experts"] == 256


# ------------------------------------------------------------ the forward
@pytest.fixture(scope="module")
def forward(system):
    ff, _ = system
    x = np.stack([ids(0), ids(1)])
    return x, np.asarray(ff.executor.make_forward()(ff.params, [x]))


@pytest.mark.parametrize("row", range(BATCH))
def test_forward_matches_the_reference(system, forward, row):
    x, got = forward
    assert_matches(got[row], reference_logits(system[1], x[row]),
                   "whole-sequence logits")


@pytest.mark.parametrize("piece,change", [
    ("gating weight 2.1", {"layernorm_gating_weight": 2.1}),
    ("rope rotate-half", {"rope_interleave": False}),
    ("no yarn", {"rope_scaling": None}),
    ("no m^2", {"use_mla_scaling_factor": False}),
    ("no latent gate", {"gated_attention": False}),
    ("no clamp", {"swiglu_limit": None}),
    ("route scale 1", {"routed_scaling_factor": 1.0})])
def test_comparison_refuses_a_piece_left_out(system, forward, piece, change):
    """Each piece of the mathematics this configuration brought, changed in
    the reference alone, puts the program outside the limit — except the
    clamp, which these weights never reach (its own tests make it bite)."""
    x, got = forward
    want = reference_logits(system[1], x[0], dict(TINY, **change))
    gap = np.abs(got[0] - want).max() / logit_tolerance(want, ULP_LIMIT)
    if piece == "no clamp":
        assert gap < 1
    else:
        assert gap > 20, (piece, gap)


def test_norm_gain_is_one_function_a_side():
    from flexflow_tpu.ops.normalization import norm_gain

    w = jnp.linspace(-3, 3, 13)
    np.testing.assert_allclose(norm_gain(w, "sigmoid2"),
                               ref.norm_gain(w, TINY), rtol=1e-7)
    assert float(norm_gain(jnp.zeros(1), "sigmoid2")[0]) == 1.0
    np.testing.assert_array_equal(norm_gain(w), w)
    with pytest.raises(ValueError, match="sigmoid2"):
        norm_gain(w, "tanh")


# ----------------------------------------------- through both caches
def test_prefill_then_decode_through_both_caches(system):
    """Three requests through ``ServingEngine``; the middle one leaves after
    one decode step, so the other two decode 27 more steps with a FREE slot
    between them. Every prefill row and every decode row within the oracle's
    tolerance of the reference's full forward over prompt + answer: the
    latent rows in the paged pool and the matrix state in the slot, each
    slot its own length."""
    from flexflow_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                                Request)

    ff, params0 = system
    eng = engine(ff)
    prefill, decode = record_logits(eng)
    sched = ContinuousBatchScheduler(n_slots=eng.n_slots, max_queue=8,
                                     buckets=eng.buckets, max_len=MAX_LEN)
    loop = eng.start_serve(sched)
    reqs = [Request(prompt=ids(30 + k, n), max_new_tokens=new, eos_id=None,
                    rng_tag=k)
            for k, (n, new) in enumerate(((13, 30), (5, 2), (21, 28)))]
    for r in reqs:
        eng.admit(sched, r)
    while loop.tick():
        pass
    stats = loop.finish()
    assert [len(r.generated) for r in reqs] == [30, 2, 28]
    for slot, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        want = reference_logits(params0, seq[:-1])
        n = len(r.prompt)
        assert_matches(prefill[slot], want[n - 1], "prefill row")
        rows = np.stack([decode[(slot, t)] for t in range(n, len(seq) - 1)])
        assert_matches(rows, want[n:], f"decode rows of slot {slot}")
    alone = [t for s, t in decode if s == 0 and (1, t - 13 + 5) not in decode]
    assert len(alone) >= 24, "slots 0 and 2 decode with slot 1 free"
    # both kinds counted: the slot-major state every slot a step, the pool
    # rows of the live slots a step
    assert stats.recurrent_state_bytes == stats.decode_steps * 2 \
        * eng.n_slots * eng._recurrent_slot_bytes()
    assert stats.latent_rows_read == sum(t + 1 for _s, t in decode)
    assert stats.kv_bytes_read >= stats.latent_rows_read \
        * eng._kv_row_bytes()
    kinds = stats.cache_bytes_by_kind
    assert set(kinds) == {"latent_pool", "recurrent_state"}
    assert kinds["recurrent_state"] == eng.n_slots \
        * eng._recurrent_slot_bytes()
    assert kinds["latent_pool"] == eng.kv_pool_blocks * BLOCK * 128 * 4
    assert stats.summary()["cache_bytes_by_kind"] == kinds
    assert stats.summary()["latent_rows_read"] == stats.latent_rows_read


def test_the_engine_holds_a_pool_and_a_state_for_one_graph(system):
    """The pool from the latent node's traced rows (one 24-number row a
    token on a 128-lane row, no V), the state slot-major a delta-rule node;
    chunked prefill and the prefix cache refused by name, the sentence the
    ops' own."""
    from flexflow_tpu.ops.base import no_chunk_carry
    from flexflow_tpu.serving import ServingEngine

    ff, _ = system
    eng = engine(ff)
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    assert eng._prefix is None
    paged = eng._paged_entry_names
    assert [n for n in paged] == [n for n in eng.state.caches if "mla" in n]
    pool = jax.tree.leaves(eng.state.caches[next(iter(paged))])
    assert [leaf.shape for leaf in pool] == [
        (eng.kv_pool_blocks, 1, BLOCK, 128)]
    for name, entry in eng.state.caches.items():
        if name not in paged:
            tail, s = entry
            assert s.shape[0] == tail.shape[0] == eng.n_slots
            assert s.dtype == jnp.float32 and s.size == 3 * 4 * 16 * 16
    assert eng._kv_row_bytes() == 128 * 4          # one latent layer
    assert eng._recurrent_slot_bytes() == 4 * (4 * 16 * 16 * 4
                                               + 3 * 128 * 4)
    with pytest.raises(ValueError, match=r"--prefill-chunk-tokens: chunked "
                       r"prefill and the prefix cache.*l0_gdn.*Reach R8"):
        ServingEngine(ff, prefill_chunk_tokens=8)
    with pytest.raises(ValueError, match=r"--prefix-cache on: .*recurrent "
                       r"node \(l0_gdn.*OP_GATED_DELTA_MIXER"):
        ServingEngine(ff, prefix_cache="on")
    assert "l0_gdn" in no_chunk_carry("l0_gdn", "its state")
    fn = ff.executor.make_chunk_prefill_step(8, MAX_LEN, BLOCK)
    with pytest.raises(NotImplementedError, match="Reach R8"):
        fn(ff.params, [jnp.zeros((1, 8), jnp.int32)], eng.state,
           jnp.zeros((eng.max_blocks_per_slot,), jnp.int32), jnp.int32(0),
           jnp.int32(3))


def test_the_serving_search_prices_both_kinds(system):
    """``_graph_cost``: four slots more cost four slots' state (what the
    delta-rule ops say a slot holds) and four slots' latent rows."""
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
    assert state == 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4) and pool > 0
    assert mem8 - mem4 == 4 * (state + pool)
    m = sim.machine
    assert t8 - t4 == pytest.approx(
        4 * (pool + 2 * state) / (m.hbm_bandwidth * m.hbm_efficiency))


# ------------------------------------------------- the delta-rule mixer
def layer_of(ff, name):
    return next(l for l in ff._layers if re.fullmatch(name + r"(_\d+)?",
                                                      l.name))


def mixer_op(**attrs):
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for

    base = {"num_heads": 4, "key_dim": 16, "value_dim": 16, "conv_width": 4,
            "neg_eigval": False, "norm_eps": 1e-6}
    op = op_class_for(OperatorType.OP_GATED_DELTA_MIXER)(
        "l0_gdn", dict(base, **attrs), DataType.DT_FLOAT)
    key = jax.random.PRNGKey(0)
    params = {w: init(jax.random.fold_in(key, i), shape, jnp.float32)
              for i, (w, (shape, _t, init)) in enumerate(
                  op.weight_specs([(1, SEQ, 64)]).items())}
    return op, params


def test_grouped_key_heads_equal_the_references_scan():
    """2 key heads under 4 value heads with the sigmoid2 gate, the op's
    whole-sequence form against the reference's scan with its explicit
    ``j // r``; and the last state, a value head a matrix."""
    from flexflow_tpu.ops.base import OpContext

    op, params = mixer_op(num_key_heads=2, gate="sigmoid2_zero_centered")
    assert params["w_q"].shape == params["w_k"].shape == (64, 32)
    assert params["w_v"].shape == (64, 64) and params["w_a"].shape == (64, 4)
    assert params["conv_w"].shape == (2 * 32 + 64, 4)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    got = op.forward(params, [u], OpContext(training=False))[0][0]
    with jax.default_matmul_precision("highest"):
        want, state = ref.delta_mixer(u[0], NO_ALT, params, TINY, SEQ,
                                      with_state=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert state.shape == (4, 16, 16)
    # heads 0 and 1 share key head 0 and differ in their values: the
    # states are not each other's copies
    assert np.abs(np.asarray(state[0] - state[1])).max() > 1e-3


def test_as_many_key_heads_as_value_heads_is_the_parents_op():
    """``num_key_heads == num_heads`` given or left out: one set of
    attributes, one program, the same bits."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ops.base import OpContext

    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, SEQ, 64), name="x")
    kw = dict(num_heads=2, key_dim=16, value_dim=32, conv_width=4,
              neg_eigval=True, norm_eps=1e-6)
    ff.gated_delta_mixer(x, name="a", **kw)
    ff.gated_delta_mixer(x, name="b", num_key_heads=2, gate="silu", **kw)
    a, b = (layer_of(ff, n) for n in "ab")
    assert dict(a.attrs) == dict(b.attrs)
    assert "num_key_heads" not in a.attrs and "gate" not in a.attrs
    with pytest.raises(ValueError, match="no multiple"):
        ff.gated_delta_mixer(x, name="c", num_key_heads=3, **dict(
            kw, num_heads=4))
    with pytest.raises(ValueError, match="gate"):
        ff.gated_delta_mixer(x, name="d", gate="tanh", **kw)
    op, params = mixer_op(num_heads=2, value_dim=32, neg_eigval=True)
    same, _ = mixer_op(num_heads=2, value_dim=32, neg_eigval=True,
                       num_key_heads=2, gate="silu")
    u = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    np.testing.assert_array_equal(
        op.forward(params, [u], OpContext(training=False))[0],
        same.forward(params, [u], OpContext(training=False))[0])


def test_the_gates_differ_and_the_zero_centred_gain_counts():
    from flexflow_tpu.ops.base import OpContext

    silu, params = mixer_op(num_key_heads=2)
    sig, _ = mixer_op(num_key_heads=2, gate="sigmoid2_zero_centered")
    u = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    run = lambda op, p: np.asarray(
        op.forward(p, [u], OpContext(training=False))[0])
    assert np.abs(run(silu, params) - run(sig, params)).max() > 0.01
    lifted = dict(params, norm_w=params["norm_w"] + 0.5)
    assert np.abs(run(sig, params) - run(sig, lifted)).max() > 0.01


@pytest.mark.parametrize("hk,hv,dk,dv,el,want", [
    (2, 4, 16, 16, 4, 4 * 16 * 16 * 4 + 3 * 128 * 4),
    (32, 64, 128, 128, 2, 17_170_432 // 4)])
def test_a_slot_is_priced_by_value_heads(hk, hv, dk, dv, el, want):
    """The state a value head, the tails at the channels' width (keys at
    the key heads'): ISSUE 53's 17,170,432 B a slot over four layers."""
    op, _ = mixer_op(num_heads=hv, num_key_heads=hk, key_dim=dk,
                     value_dim=dv)
    assert op.slot_state_bytes(el) == want
    b, s, d = 1, 1, 64
    channels = 2 * hk * dk + hv * dv
    assert op.flops([(b, s, d)], [(b, s, d)]) == 2 * d * (
        channels + hv * dv + 2 * hv) + 2 * channels * 4 \
        + 6 * hv * dk * dv + 2 * hv * dv * d


# ------------------------------------------------------ the latent node
def latent_node(ff):
    from flexflow_tpu.ops.latent_attention import LatentAttentionOp

    node = next(n for n in ff.executor.pcg.compute_nodes()
                if isinstance(n.op, LatentAttentionOp))
    return node.op, ff.params[node.name]


YARN = PUBLISHED["rope_scaling"]


def closed_form_inv_freq(d=64, theta=100000.0, sc=YARN):
    """The DeepSeek-V3 family's YaRN, written out a pair at a time."""
    out = []
    orig, factor = sc["original_max_position_embeddings"], sc["factor"]
    dim = lambda rot: d * np.log(orig / (rot * 2 * np.pi)) / (
        2 * np.log(theta))
    low = max(np.floor(dim(sc["beta_fast"])), 0)
    high = min(np.ceil(dim(sc["beta_slow"])), d - 1)
    for j in range(d // 2):
        plain = theta ** (-2 * j / d)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1 - ramp))
    return np.asarray(out), low, high


def test_yarn_frequencies_and_softmax_scale_at_the_published_keys():
    from flexflow_tpu.ops.latent_attention import (rope_at, yarn_inv_freq,
                                                   yarn_mscale)

    want, low, high = closed_form_inv_freq()
    assert (low, high) == (14, 24)
    got = yarn_inv_freq(64, 100000.0, YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(64, 100000.0, YARN), want,
                               rtol=1e-6)
    assert want[0] == 1.0 and want[-1] == pytest.approx(
        100000.0 ** (-62 / 64) / 8)
    m = 0.1 * np.log(8) + 1
    assert m == pytest.approx(1.2079, abs=5e-5)
    assert yarn_mscale(8, 1) == pytest.approx(m)
    op = latent_node_of_width()
    assert op._scale() == pytest.approx(192 ** -0.5 * m * m)
    assert ref.softmax_scale(PUBLISHED) == pytest.approx(op._scale())
    # three positions past the original context, neighbour pairs
    pos = np.asarray([32769, 100000, 262143])
    x = np.random.default_rng(0).standard_normal((3, 64)).astype(np.float32)
    ang = pos.astype(np.float32)[:, None] * got[None, :]
    a, b = x[:, 0::2], x[:, 1::2]
    by_hand = np.stack([a * np.cos(ang) - b * np.sin(ang),
                        b * np.cos(ang) + a * np.sin(ang)], -1).reshape(3, 64)
    for rope in (
            rope_at(jnp.asarray(x), jnp.asarray(pos), 100000.0, YARN, True),
            ref.rope(jnp.asarray(x), jnp.asarray(pos), PUBLISHED)):
        # float32 angles of 1e5 radians: XLA's range reduction on the CPU
        # and numpy's differ by up to 0.01 in cos and sin there (measured)
        np.testing.assert_allclose(rope, by_hand, rtol=0, atol=3e-2)
    plain = rope_at(jnp.asarray(x), jnp.asarray(pos), 100000.0, None, True)
    assert np.abs(np.asarray(plain) - by_hand).max() > 0.5


def latent_node_of_width():
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for

    return op_class_for(OperatorType.OP_LATENT_ATTENTION)(
        "l4_mla", {"num_heads": 64, "kv_rank": 512, "rope_dim": 64,
                   "q_rank": 1536, "nope_dim": 128, "v_dim": 128,
                   "embed_dim": 7168, "rope_theta": 1e5, "eps": 1e-6,
                   "rope_scaling": dict(YARN), "rope_interleave": True,
                   "gated": True}, DataType.DT_BFLOAT16)


def test_neighbour_pairs_are_rotate_half_under_the_column_permutation():
    from flexflow_tpu.ops.latent_attention import rope_at

    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 5, 8)).astype(np.float32))
    pos = jnp.asarray([[3, 40000, 7, 9, 11], [0, 1, 2, 3, 4]])
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    for sc in (None, TINY["rope_scaling"]):
        pairs = rope_at(x, pos, 10000.0, sc, interleave=True)
        halves = rope_at(x[..., perm], pos, 10000.0, sc, interleave=False)
        np.testing.assert_allclose(pairs[..., perm], halves, rtol=1e-6,
                                   atol=1e-6)
    # the default path is the parent's own expression
    np.testing.assert_allclose(
        rope_at(x, pos, 10000.0),
        rope_at(x, pos, 10000.0, TINY["rope_scaling"] | {"factor": 1}),
        rtol=1e-5, atol=1e-5)


def test_absorbed_equals_materialised_with_the_gate_on(system):
    """One decode row and a three-row chunk against 19 cached rows, both
    forms of the node, through the gate and ``W_o``."""
    from flexflow_tpu.serving.kvcache import (new_kv_pool, prefill_kv_entry,
                                              scatter_prefill_kv)

    ff, _ = system
    op, p = latent_node(ff)
    assert op.attrs["gated"] and op.attrs["rope_interleave"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 19, 64)).astype(np.float32))
    pos = jnp.arange(19, dtype=jnp.int32)[None]
    q_n, q_r = op._queries(p, x, pos)
    rows = op._rows(p, x, pos)
    entry = prefill_kv_entry(rows[:, None], None, 24)
    table = jnp.arange(1, 4, dtype=jnp.int32)
    pool = scatter_prefill_kv(new_kv_pool(entry, 4, 8, "native"), entry,
                              table, 8)
    for c in (1, 3):
        mask = (jnp.arange(19)[None, None, :]
                <= jnp.arange(19 - c, 19)[None, :, None])
        mat = op._out(p, op._materialised(
            p, q_n[:, -c:], q_r[:, -c:], rows, mask), x[:, -c:])
        seen = jnp.arange(19 - c + 1, 20, dtype=jnp.int32)[None]
        ab = op._out(p, op._absorbed(
            p, q_n[:, -c:], q_r[:, -c:], pool, table[None], seen,
            tokens=0 if c > 1 else 1), x[:, -c:])
        np.testing.assert_allclose(np.asarray(ab), np.asarray(mat),
                                   rtol=2e-5, atol=2e-6)
    # a gate of zeros halves every head's output: the gate is in the path
    half = op._out(dict(p, wg=jnp.zeros_like(p["wg"])), op._materialised(
        p, q_n[:, -1:], q_r[:, -1:], rows, mask[:, -1:]), x[:, -1:])
    bare = type(op)(op.name, {k: v for k, v in op.attrs.items()
                              if k != "gated"}, op.data_type)
    full = bare._out(p, bare._materialised(
        p, q_n[:, -1:], q_r[:, -1:], rows, mask[:, -1:]), x[:, -1:])
    np.testing.assert_allclose(np.asarray(half) * 2, np.asarray(full),
                               rtol=1e-5, atol=1e-6)


def test_a_long_prompts_score_tile_runs_in_blocks_of_query_rows(system):
    """Past ``SCORE_TILE_BYTES`` the materialised core runs a block of
    query rows at a time: the same numbers."""
    from flexflow_tpu.ops import latent_attention as la

    ff, _ = system
    op, p = latent_node(ff)
    assert la.score_blocks(64, 2048, 2048) == 4
    assert la.score_blocks(64, 256, 256) == la.score_blocks(128, 32, 32) == 1
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 32, 64)).astype(np.float32))
    whole = op.forward(p, [x], _ctx())[0]
    real = la.SCORE_TILE_BYTES
    la.SCORE_TILE_BYTES = 2 * 4 * 8 * 32 * 4      # a block of 8 rows
    try:
        assert la.score_blocks(8, 32, 32) == 4
        blocked = op.forward(p, [x], _ctx())[0]
    finally:
        la.SCORE_TILE_BYTES = real
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)


def _ctx():
    from flexflow_tpu.ops.base import OpContext

    return OpContext(training=False)


def test_latent_flops_count_the_gate():
    op = latent_node_of_width()
    plain = type(op)("l4_mla", {k: v for k, v in op.attrs.items()
                                if k != "gated"}, op.data_type)
    shapes = [(1, 1, 7168)], [(1, 1, 7168)]
    assert op.flops(*shapes) - plain.flops(*shapes) \
        == 2 * 7168 * 64 * 128


# ------------------------------------------------------------- the clamp
def test_the_clamp_bites_in_the_gated_mlp():
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for

    make = lambda **a: op_class_for(OperatorType.OP_GATED_MLP)(
        "mlp", dict(intermediate=32, **a), DataType.DT_FLOAT)
    rng = np.random.default_rng(5)
    p = {k: jnp.asarray(rng.standard_normal(s).astype(np.float32))
         for k, s in (("gate", (64, 32)), ("up", (64, 32)),
                      ("down", (32, 64)))}
    x = jnp.asarray(rng.standard_normal((2, 7, 64)).astype(np.float32)) * 3
    g, u = np.asarray(x @ p["gate"]), np.asarray(x @ p["up"])
    assert (g > 10).mean() > 0.2 and (np.abs(u) > 10).mean() > 0.4
    gc, uc = np.minimum(g, 10), np.clip(u, -10, 10)
    want = (gc / (1 + np.exp(-gc)) * uc) @ np.asarray(p["down"])
    got = make(limit=10.0).forward(p, [x], _ctx())[0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-3)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            ref.gated(x, p["gate"], p["up"], p["down"], 10.0), want,
            rtol=2e-5, atol=1e-3)
    free = make().forward(p, [x], _ctx())[0]
    assert np.abs(np.asarray(free) - want).max() > 10


@pytest.mark.parametrize("path,held,lead", [
    ("dropless, every expert held", (0, 16), 1.0),
    ("bounded", (4, 4), 1.0), ("fallback", (4, 4), 30.0)])
def test_the_clamp_bites_in_the_routed_experts(path, held, lead):
    """The routed layer through ``FFModel.routed_experts`` with the limit,
    inputs scaled so that it bites, on the node's three forms: no bound
    (every expert held), the bounded path (the pairs held here fit the row
    bound) and its whole-buffer fallback (a router that sends the held
    experts nearly every pair)."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ops.moe_ops import _row_bound

    tokens, k, n, d = 256, 4, 16, 64
    config = FFConfig()
    config.parse_args(["-b", "1"])
    config.seed = 3
    ff = FFModel(config)
    x_t = ff.create_tensor((1, tokens, d), name="x")
    ff.routed_experts(x_t, n, k, 32, held=held, route_scale=2.5,
                      selection_bias=False, limit=10.0, name="l1_moe")
    ff.compile(loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE)
    params = jax.device_get(ff.params)
    router = next(v for key, v in params.items() if "router" in key)
    router["kernel"] = np.asarray(router["kernel"]).copy()
    router["kernel"][:, held[0]:held[0] + held[1]] *= lead
    experts = next(v for key, v in params.items() if "experts" in key)
    for w in experts:
        experts[w] = np.asarray(experts[w]) * 8
    ff.params = jax.device_put(params)
    x = np.random.default_rng(6).standard_normal(
        (1, tokens, d)).astype(np.float32) * 2
    got = np.asarray(ff.executor.make_forward()(ff.params, [x]))[0]
    config_ref = dict(TINY, experts_held=list(held), n_shared_experts=0,
                      routed_scaling_factor=2.5, num_experts_per_tok=k)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(
            jnp.asarray(x[0]), jnp.zeros(tokens, bool), router, experts,
            None, config_ref, 0.0)
        free, _ = ref.expert_layer(
            jnp.asarray(x[0]), jnp.zeros(tokens, bool), router, experts,
            None, dict(config_ref, swiglu_limit=None), 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert np.abs(np.asarray(free) - np.asarray(want)).max() > 1, \
        "the clamp does not bite at these inputs"
    bound = _row_bound(tokens * k, {"held": held, "num_experts": n})
    score = jax.nn.sigmoid(jnp.asarray(x[0]) @ router["kernel"])
    chosen = np.asarray(jax.lax.top_k(score, k)[1])
    here = int(((chosen >= held[0]) & (chosen < held[0] + held[1])).sum())
    if path.startswith("dropless"):
        assert bound is None
    else:
        assert (here <= bound) == (path == "bounded"), (here, bound)


def test_the_clamp_defaults_off_and_leaves_the_parents_attributes():
    from flexflow_tpu import FFConfig, FFModel

    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 8, 64), name="x")
    ff.gated_mlp(x, 32, name="mlp")
    ff.routed_experts(x, 16, 4, 32, held=(0, 4), name="l0_moe")
    ff.rms_norm(x, name="norm")
    for name in ("mlp", "l0_moeexperts", "norm"):
        attrs = layer_of(ff, name).attrs
        assert "limit" not in attrs and "gain" not in attrs


# --------------------------------------------------- the share adds up
def test_the_shares_add_up_to_the_uncut_layer():
    """At 16 experts, the routed parts that the shares (0, 4), (4, 4),
    (8, 4), (12, 4) give, plus the shared expert once, equal the uncut
    reference's layer (the router ranks all sixteen and normalises over the
    chosen in every share), the clamp in every expert."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((24, 64)).astype(np.float32)) * 4
    n = TINY["router_experts"]
    router = {"kernel": rng.standard_normal((64, n)).astype(np.float32)}
    experts = {k: rng.standard_normal(s).astype(np.float32)
               for k, s in (("gate", (n, 64, 32)), ("up", (n, 64, 32)),
                            ("down", (n, 32, 64)))}
    shared = {k: v[0] for k, v in experts.items()}
    no_flip = jnp.zeros(24, bool)
    layer = lambda held, ex, sh: ref.expert_layer(
        x, no_flip, router, ex, sh, dict(TINY, experts_held=list(held)),
        0.0)[0]
    with jax.default_matmul_precision("highest"):
        whole = layer((0, n), experts, shared)
        parts = sum(layer((e, 4), {k: v[e:e + 4] for k, v in
                                   experts.items()}, None)
                    for e in range(0, n, 4)) \
            + ref.gated(x, shared["gate"], shared["up"], shared["down"],
                        TINY["swiglu_limit"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(whole).max()) > 1


# -------------------------------------------- the reference's own pieces
def test_blocks_and_alternatives_change_nothing(system):
    """Head groups, query-row blocks, column blocks and row blocks smaller
    than the sequence give the same logits; and with nothing tied, the
    alternatives — a position's own forward from the sequence's state
    before it, its conv tail and its cached rows — are the sequence's own
    rows at their positions."""
    _, params0 = system
    seq = np.zeros(48, np.int32)
    seq[:37] = ids(8, 37)
    base = reference_logits(params0, seq)
    blocks = ("QUERY_BLOCK", "MLP_BLOCK", "LOGIT_BLOCK", "HEAD_GROUP",
              "GDN_HEAD_GROUP")
    real = [getattr(ref, b) for b in blocks]
    try:
        ref.QUERY_BLOCK, ref.MLP_BLOCK, ref.LOGIT_BLOCK = 16, 32, 16
        ref.HEAD_GROUP, ref.GDN_HEAD_GROUP = 2, 2
        r = ref.Reference(params0, TINY, route_tie=1e-30)
        with jax.default_matmul_precision("highest"):
            out, alt, pos_a, tied_at = r._rows(seq)
    finally:
        for b, v in zip(blocks, real):
            setattr(ref, b, v)
    np.testing.assert_allclose(out, base, rtol=1e-5, atol=2e-5)
    assert not tied_at.any() and len(pos_a) == ref.TIE_WINDOW * 4
    np.testing.assert_allclose(alt, out[pos_a], rtol=1e-5, atol=2e-5)


def test_a_routing_tie_returns_the_row_nearer_the_programs_token(system):
    """With every 8th/9th pair called a tie, a checked position's row is
    the base row or an alternative's — whichever puts the next id nearer
    the best — and an alternative is the full forward with that one choice
    flipped: by causality, the sequence's own row at that position when the
    flip is made in the sequence."""
    _, params0 = system
    seq = np.zeros(48, np.int32)
    seq[:37] = ids(8, 37)
    base = reference_logits(params0, seq)
    r = ref.Reference(params0, TINY, route_tie=1.0)
    got = r.logits(seq)
    assert r.tie_counts["evaluated_twice"] > 0
    moved = np.flatnonzero(np.abs(got - base).max(axis=1) > 1e-4)
    assert r.tie_counts["took_other"] >= len(moved) > 0
    assert moved.min() >= 37 - ref.TIE_WINDOW and moved.max() < 36
    for p in moved:
        nxt = seq[p + 1]
        assert got[p].max() - got[p][nxt] < base[p].max() - base[p][nxt]
    # the alternatives that moved most, one in the first expert layer (three
    # mixers of both kinds follow it) and one overall, by brute force
    with jax.default_matmul_precision("highest"):
        out, alt, pos_a, _tied = r._rows(seq)
        gap = np.abs(alt - out[pos_a]).max(axis=1)
        first = np.arange(len(pos_a)) % 4 == 0
        for k in (int(np.argmax(gap)), int(np.argmax(gap * first))):
            p, layer = int(pos_a[k]), k % 4
            brute = r._rows(seq, flip_in_sequence=(p, layer))[0][p]
            np.testing.assert_allclose(alt[k], brute, rtol=1e-5, atol=2e-5)
            assert np.abs(brute - base[p]).max() > 1e-3


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "reference",
                           "gigachat35-432b-a28b.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+flexflow_tpu", text, re.M)
    assert PUBLISHED["reference"] == TINY["reference"] \
        == "gigachat35-432b-a28b.py"


# ------------------------------ the programs this configuration shares
#: sha256 (16 hex digits) of the jaxprs of openpangu's and olmo-hybrid's
#: serving programs at lane-aligned toy widths, the kernels' gates answering
#: as on a TPU, taken on the PARENT commit (1bebd50, jax 0.9.0): this PR
#: touched ``ops/latent_attention.py``, ``ops/gated_delta.py``,
#: ``ops/linear.py``, ``ops/moe_ops.py`` and ``ops/normalization.py``, and
#: with their new attributes off those programs are the parent's
PARENT_DIGESTS = {
    "pangu_prefill": "24f4e4562c2df772", "pangu_decode": "67293262c014b153",
    "pangu_chunk": "e8cdcd389536d699", "olmo_prefill": "53c04a5d9260199e",
    "olmo_decode": "ada0379c6bef5d35"}


@pytest.fixture(scope="module")
def shared_programs():
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.kernels import _common
    from flexflow_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                 build_olmo_hybrid)
    from flexflow_tpu.models.pangu import PanguConfig, build_pangu
    from flexflow_tpu.serving import ServingEngine

    argv = ["--compute-dtype", "bf16", "--param-dtype", "bf16",
            "--only-data-parallel", "--mesh-shape", "1"]
    shape = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def digest(fn, *args):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    models = {
        "pangu": (build_pangu, PanguConfig(
            batch_size=8, seq_len=128, hidden=256, num_heads=16, q_rank=64,
            kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, num_layers=2,
            num_dense_layers=1, intermediate=256, moe_intermediate=128,
            num_experts=16, num_experts_per_tok=4, held_experts=(0, 4),
            vocab_size=512), ["--prefill-chunk-tokens", "64"]),
        "olmo": (build_olmo_hybrid, OlmoHybridConfig(
            batch_size=8, seq_len=128, hidden=512,
            layer_types=["linear_attention"] * 3 + ["full_attention"],
            num_heads=4, head_dim=128, intermediate=256,
            linear_num_key_heads=6, linear_num_value_heads=6,
            linear_key_head_dim=96, linear_value_head_dim=192,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            vocab_size=512, rms_norm_eps=1e-6), ["--prefix-cache", "off"])}
    out = {}
    real = _common.on_tpu
    for name, (builder, cfg, extra) in models.items():
        config = FFConfig()
        config.parse_args(["-b", "8", *argv, *extra])
        config.seed = 5
        ff = FFModel(config)
        builder(ff, cfg)
        ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        eng = ServingEngine(ff, n_slots=8, max_decode_len=256,
                            kv_block_size=16, kv_pool_blocks=200,
                            buckets=(128,))
        x, one = jnp.zeros((1, 128), jnp.int32), jnp.ones((1,), jnp.int32)
        _common.on_tpu = lambda: True
        try:
            eng._ensure_state(jax.eval_shape(
                eng._prefill_fn(128), ff.params, [x], one)[2])
            params = shape(ff.params)
            out[f"{name}_prefill"] = digest(
                ff.executor.make_prefill_step(128, 256), params, [shape(x)],
                shape(one))
            out[f"{name}_decode"] = digest(
                eng._decode_fn(), params,
                [shape(jnp.zeros((8, 1), jnp.int32))], shape(eng.state))
            if name == "pangu":
                i32 = shape(jnp.int32(0))
                out[f"{name}_chunk"] = digest(
                    ff.executor.make_chunk_prefill_step(64, 256, 16), params,
                    [shape(jnp.zeros((1, 64), jnp.int32))], shape(eng.state),
                    shape(jnp.zeros((eng.max_blocks_per_slot,), jnp.int32)),
                    i32, i32)
        finally:
            _common.on_tpu = real
    return out


@pytest.mark.parametrize("program", sorted(PARENT_DIGESTS))
def test_shared_programs_trace_as_on_the_parent(shared_programs, program):
    assert shared_programs[program] == PARENT_DIGESTS[program], (
        f"digest taken with jax 0.9.0, this is jax {jax.__version__}")
