"""The sliding/global grouped-query decoder with a dropless routed expert
layer (models/trinity.py) against its plain reference
(tests/reference_trinity.py): float32 on the CPU at tiny widths, so that no
routing choice can flip. Probabilities, loss and EVERY gradient, the routed
experts' and the router's included; one case per mechanism that fails if the
mechanism is left out; the shares add up to the uncut layer with the shared
expert counted once; a fully skewed routing loses no token."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_trinity as ref

HERE = os.path.dirname(os.path.abspath(__file__))

#: the tiny configuration under its published keys (what the reference and
#: a benchmark configuration's file read) and the builder's field mapping:
#: the rehearsal cell's own file
with open(os.path.join(HERE, "..", "benchmark", "tests", "cells", "configs",
                       "trinity-tiny.json")) as _f:
    TINY = json.load(_f)
FIELDS = TINY["builder"]["fields"]
BATCH, SEQ = 2, 32


def trinity_config(config=TINY, **overrides):
    from flexflow_tpu.models.trinity import TrinityConfig

    kwargs = {field: config[key] for field, key in FIELDS.items()}
    kwargs.update(batch_size=BATCH, seq_len=SEQ)
    kwargs.update(overrides)
    return TrinityConfig(**kwargs)


def build(cfg, seed=5):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.trinity import build_trinity

    config = FFConfig()
    config.batch_size = cfg.batch_size
    config.seed = seed
    ff = FFModel(config)
    build_trinity(ff, cfg)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-3),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def batch(seed=0, vocab=TINY["vocab_size"]):
    s = np.random.default_rng(seed).integers(
        0, vocab, size=(BATCH, SEQ + 1)).astype(np.int32)
    return s[:, :-1], s[:, 1:]


@pytest.fixture(scope="module")
def system():
    """(model, parameters before the step, probabilities, first-step loss,
    gradients read from Adam's first moment) on one seeded batch."""
    ff = build(trinity_config())
    # lift the router's bias off zero and its kernel off symmetry: selection
    # on score + bias, weights from the score alone, must both matter
    params0 = jax.device_get(ff.params)
    rng = np.random.default_rng(11)
    for name, group in params0.items():
        if "moerouter" in name:
            group["expert_bias"] = rng.uniform(
                -0.3, 0.3, group["expert_bias"].shape).astype(np.float32)
    ff.params = jax.device_put(params0)
    x, y = batch()
    probs = np.asarray(ff.executor.make_forward()(ff.params, [x]))
    step = ff.executor.make_train_step()
    xd = [jax.device_put(x, ff.executor.batch_sharding(2))]
    yd = jax.device_put(y, ff.executor.batch_sharding(2))
    _, opt, loss, metrics = step(ff.params, ff.opt_state, xd, yd,
                                 jax.random.PRNGKey(0))
    grads = jax.tree_util.tree_map(lambda m: np.asarray(m) / 0.1, opt["m"])
    return ff, params0, probs, float(loss), grads, jax.device_get(metrics)


@pytest.fixture(scope="module")
def reference(system):
    _, params0, *_ = system
    x, y = batch()
    with jax.default_matmul_precision("highest"):
        logits = ref.logits(params0, x, TINY)
    loss, grads = ref.loss_and_grads(params0, x, y, TINY)
    return np.asarray(jax.nn.softmax(logits, axis=-1)), float(loss), grads


def test_probabilities_and_loss_match_the_reference(system, reference):
    _, _, probs, loss, _, _ = system
    ref_probs, ref_loss, _ = reference
    np.testing.assert_allclose(probs, ref_probs, rtol=2e-4, atol=1e-7)
    assert abs(loss - ref_loss) < 2e-5 * ref_loss


def test_every_gradient_matches_the_reference(system, reference):
    _, params0, _, _, grads, _ = system
    _, _, ref_grads = reference
    checked = 0
    for name, group in params0.items():
        for weight in group:
            g, r = grads[name][weight], np.asarray(ref_grads[name][weight])
            if weight == "expert_bias":  # a buffer: no gradient reaches it
                assert not g.any() and not r.any()
                continue
            err = np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30)
            assert np.linalg.norm(r) > 0, f"{name}.{weight}: dead gradient"
            assert err < 2e-4, f"{name}.{weight}: {err}"
            checked += 1
    # the routed experts' three matrices and the router's, in both expert
    # layers, are among them
    assert checked == sum(len(g) for g in params0.values()) - 2
    assert any("moeexperts" in n for n in params0)


# ------------------------------------------------- one case per mechanism
def _full_with_rope(monkeypatch):
    plain = ref.attention
    monkeypatch.setattr(ref, "attention", lambda x, p, sliding, config: plain(
        x, p, True, config if sliding else dict(config,
                                                sliding_window=10 ** 9)))


def _bias_in_the_weights(monkeypatch):
    def routing(x, p, config):
        biased = jax.nn.sigmoid(x @ p["kernel"]) + p["expert_bias"]
        weights, chosen = jax.lax.top_k(biased,
                                        int(config["num_experts_per_tok"]))
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return weights * float(config["route_scale"]), chosen

    monkeypatch.setattr(ref, "routing", routing)


LEFT_OUT = {
    "window": lambda mp: {"sliding_window": 10 ** 9},
    "rotary_on_sliding_layers": lambda mp: mp.setattr(
        ref, "rope", lambda x, theta: x),
    "no_rotary_on_full_layers": _full_with_rope,
    "output_gate": lambda mp: mp.setattr(ref, "output_gate",
                                         lambda o, gate: o),
    "qk_norms": lambda mp: mp.setattr(ref, "head_norm",
                                      lambda x, gain, eps: x),
    "grouped_kv_heads": lambda mp: mp.setattr(
        jnp, "repeat", lambda a, n, axis: jnp.tile(a, (1, n, 1, 1))),
    "route_scale": lambda mp: {"route_scale": 1.0},
    "route_norm": lambda mp: {"route_norm": False},
    "bias_selects_only": _bias_in_the_weights,
    "shared_expert": lambda mp: {"num_shared_experts": 0},
    "mup_embedding_scale": lambda mp: {"mup_enabled": False},
    "experts_held": lambda mp: {"experts_held": [4, 4]},
}


@pytest.mark.parametrize("mechanism", sorted(LEFT_OUT))
def test_reference_without_the_mechanism_is_another_model(
        mechanism, system, reference, monkeypatch):
    """The system agrees with the reference to 2e-5 of the loss; the
    reference with one mechanism left out (or misapplied) is at least a
    hundred times further away. So a system that lacks the mechanism fails
    the two tests above."""
    _, params0, _, loss, _, _ = system
    _, ref_loss, _ = reference
    changed = LEFT_OUT[mechanism](monkeypatch) or {}
    x, y = batch()
    with jax.default_matmul_precision("highest"):
        variant = float(ref.loss(
            jax.tree_util.tree_map(jnp.asarray, params0), jnp.asarray(x),
            jnp.asarray(y), dict(TINY, **changed)))
    assert abs(variant - ref_loss) > 100 * max(abs(loss - ref_loss),
                                               2e-6 * ref_loss), \
        (mechanism, variant, ref_loss, loss)


# ------------------------------------------------------ the routed layer
def _routed_ops(held, num_experts=8, k=2, inter=12):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops import moe_ops

    ids = {"num_experts": num_experts, "held": held}
    f32 = DataType.DT_FLOAT
    return (moe_ops.MoERouterOp("r", dict(ids, k=k, route_scale=2.0), f32),
            moe_ops.MoEDispatchOp("d", ids, f32, 2),
            moe_ops.MoERoutedExpertsOp("e", dict(ids, intermediate=inter),
                                       f32, 2),
            moe_ops.MoECombineOp("c", ids, f32, 4))


def _routed_layer(x, router, experts, held):
    from flexflow_tpu.ops.base import OpContext

    r, d, e, c = _routed_ops(held)
    ctx = OpContext(stats_out={})
    weights, chosen = r.forward(router, [x], ctx)
    rows, sizes, order = d.forward({}, [x, chosen], ctx)
    (out,) = e.forward({k: v[held[0]:held[0] + held[1]]
                        for k, v in experts.items()}, [rows, sizes], ctx)
    (y,) = c.forward({}, [out, order, weights, chosen], ctx)
    return y, ctx.stats_out["d"]


def _layer_inputs(seed=0, tokens=24, d=8, n=8, inter=12):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

    router = {"kernel": normal(d, n),
              "expert_bias": jnp.asarray(rng.uniform(-0.3, 0.3, n),
                                         jnp.float32)}
    experts = {"gate": 0.3 * normal(n, d, inter), "up": 0.3 * normal(n, d, inter),
               "down": 0.3 * normal(n, inter, d)}
    shared = {"gate": normal(d, inter), "up": normal(d, inter),
              "down": normal(inter, d)}
    return normal(1, tokens, d), router, experts, shared


LAYER_CONFIG = {"score_func": "sigmoid", "num_experts_per_tok": 2,
                "route_norm": True, "route_scale": 2.0}


@pytest.mark.parametrize("shares", [[(0, 8)], [(0, 4), (4, 4)],
                                    [(0, 2), (2, 2), (4, 2), (6, 2)],
                                    [(0, 1), (1, 7)]],
                         ids=["whole", "2-shares", "4-shares", "uneven"])
def test_shares_add_up_to_the_uncut_layer(shares):
    """Routed parts of all shares + the shared expert ONCE = the uncut
    reference layer; with the shared expert on every share it is not."""
    x, router, experts, shared = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_experts(x, router, experts, LAYER_CONFIG, (0, 8)) \
            + ref.gated_mlp(x, shared["gate"], shared["up"], shared["down"])
        once = ref.gated_mlp(x, shared["gate"], shared["up"], shared["down"])
        parts = [_routed_layer(x, router, experts, held)[0]
                 for held in shares]
    np.testing.assert_allclose(sum(parts) + once, uncut, rtol=1e-5,
                               atol=1e-5)
    if len(shares) > 1:
        per_share = sum(p + once for p in parts)
        assert np.abs(per_share - uncut).max() > 1e-2


@pytest.mark.parametrize("held", [(0, 8), (2, 2), (2, 4)])
def test_a_fully_skewed_routing_drops_no_token(held):
    """Every token chooses experts 2 and 3 (a bias no score can outweigh):
    the held two receive every pair, none is dropped, and the output is the
    reference's — where the fixed-capacity path at alpha 1 keeps a quarter."""
    x, router, experts, _ = _layer_inputs(seed=1)
    router["expert_bias"] = jnp.zeros(8).at[jnp.array([2, 3])].set(10.0)
    tokens = x.shape[1]
    with jax.default_matmul_precision("highest"):
        y, stats = _routed_layer(x, router, experts, held)
        want = ref.routed_experts(
            x, router, {k: v[held[0]:held[0] + held[1]]
                        for k, v in experts.items()}, LAYER_CONFIG, held)
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.sum() == 2 * tokens == int(stats["pairs_here"])
    assert int(stats["dropped"]) == 0
    assert counts[2 - held[0]] == counts[3 - held[0]] == tokens
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    from flexflow_tpu.ops.moe_ops import dispatch_indices, moe_capacity

    _, keep = dispatch_indices(jnp.tile(jnp.array([2, 3]), tokens), 8,
                               moe_capacity(2, tokens, 1.0, 8))
    assert int(keep.sum()) < 2 * tokens  # the capacity path would drop


def test_routed_layer_gradients_match_the_dense_loop():
    x, router, experts, _ = _layer_inputs(seed=2)

    def system(x, router, experts):
        return jnp.sum(_routed_layer(x, router, experts, (2, 4))[0] ** 2)

    def plain(x, router, experts):
        held = {k: v[2:6] for k, v in experts.items()}
        return jnp.sum(ref.routed_experts(x, router, held, LAYER_CONFIG,
                                          (2, 4)) ** 2)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(system, argnums=(0, 1, 2))(x, router, experts)
        want = jax.grad(plain, argnums=(0, 1, 2))(x, router, experts)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    # the experts not held take no gradient, the bias none at all
    assert not np.asarray(got[2]["gate"][:2]).any()
    assert not np.asarray(got[1]["expert_bias"]).any()


# ---------------------------------------------------- counters and scopes
def test_routing_counters_ride_with_the_steps_metrics(system):
    ff, *_, metrics = system
    stats = metrics["op_stats"]
    assert len(stats) == 2  # one per expert layer
    for counters in stats.values():
        assert counters["tokens_per_expert"].shape == (4,)
        assert int(counters["dropped"]) == 0
        assert int(counters["pairs_here"]) == \
            int(counters["tokens_per_expert"].sum()) <= 2 * BATCH * SEQ


def test_fit_folds_the_counters_into_routing_stats():
    ff = build(trinity_config())
    x, y = batch()
    assert ff.routing_stats() == {}
    ff.fit(np.tile(x, (2, 1)), np.tile(y, (2, 1)), batch_size=BATCH,
           epochs=1, shuffle=False)
    stats = ff.routing_stats()
    assert len(stats) == 2
    for counters in stats.values():
        assert counters["steps"] == 2 and counters["dropped"] == 0
        assert counters["tokens_per_expert"].sum() == counters["pairs_here"]
    digest = ff._routing_digest()
    assert digest["moe_dropped"] == 0 and digest["moe_expert_counters"] == 8
    assert digest["moe_load_max_permille"] >= 1000
    assert len(digest["moe_tokens_per_expert"].split(",")) == 4


def test_remat_carries_the_counters_and_the_same_loss():
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.trinity import build_trinity

    losses = {}
    for level in ("none", "full"):
        config = FFConfig()
        config.parse_args(["-b", str(BATCH), "--seed", "5", "--remat", level])
        ff = FFModel(config)
        build_trinity(ff, trinity_config())
        ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-3),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        x, y = batch()
        step = ff.executor.make_train_step()
        _, _, loss, metrics = step(
            ff.params, ff.opt_state,
            [jax.device_put(x, ff.executor.batch_sharding(2))],
            jax.device_put(y, ff.executor.batch_sharding(2)),
            jax.random.PRNGKey(0))
        losses[level] = float(loss)
        assert len(metrics["op_stats"]) == 2
    assert abs(losses["none"] - losses["full"]) < 1e-5


def test_node_scopes_name_the_layers_parts():
    """The compiled step's op names carry the node scopes the benchmark's
    breakdown groups by (layer index dropped): the routed layer's four
    parts, the shared expert, and the rotary scope inside attention."""
    ff = build(trinity_config())
    x, y = batch()
    step = ff.executor.make_train_step()
    text = step.lower(
        ff.params, ff.opt_state,
        [jax.device_put(x, ff.executor.batch_sharding(2))],
        jax.device_put(y, ff.executor.batch_sharding(2)),
        jax.random.PRNGKey(0)).compile().as_text()
    spec = importlib.util.spec_from_file_location(
        "bench_xplane", os.path.join(HERE, "..", "benchmark", "reduce",
                                     "xplane.py"))
    xplane = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xplane)
    scopes = set(xplane.scope_map(text).values())
    assert {"l_moerouter", "l_moedispatch", "l_moeexperts", "l_moecombine",
            "l_moeshared", "l_attnrope", "l_attn", "l_mlp"} <= scopes, scopes


# ------------------------------------------------------------ the builder
def test_param_count_and_flops_closed_forms():
    from flexflow_tpu.models.trinity import (trinity_attention_pairs,
                                             trinity_param_count,
                                             trinity_train_flops_per_token)

    cfg = trinity_config()
    ff = build(cfg)
    held = sum(int(np.prod(v.shape)) for g in ff.params.values()
               for v in g.values())
    assert held == trinity_param_count(cfg)
    # pairs: two windowed layers (8 wide at 32 positions) and a full one
    band = 8 * 9 // 2 + (32 - 8) * 8
    assert trinity_attention_pairs(cfg) == 2 * band + 32 * 33 // 2
    assert trinity_train_flops_per_token(cfg) > 6 * 64 * 96  # the head alone


def test_serving_a_grouped_windowed_model_is_refused():
    from flexflow_tpu.serving import ServingEngine

    ff = build(trinity_config())
    with pytest.raises(NotImplementedError, match="training path only"):
        ServingEngine(ff).generate([[1, 2, 3]], max_new_tokens=2)


def test_every_new_op_prices_itself_and_says_what_shards():
    from flexflow_tpu.ffconst import OperatorType

    ff = build(trinity_config())
    new = {OperatorType.OP_GATED_MLP, OperatorType.OP_MOE_ROUTER,
           OperatorType.OP_MOE_DISPATCH, OperatorType.OP_MOE_ROUTED_EXPERTS,
           OperatorType.OP_MOE_COMBINE}
    seen = set()
    for node in ff.pcg.compute_nodes():
        if node.op.op_type not in new:
            continue
        seen.add(node.op.op_type)
        in_shapes = [ff.pcg.nodes[g].out_shapes[i] for g, i in node.inputs]
        assert node.op.flops(in_shapes, node.out_shapes) > 0
        assert node.op.memory_bytes(in_shapes, node.out_shapes) > 0
        dims = node.op.parallelizable_dims(in_shapes)
        if node.op.op_type in (OperatorType.OP_MOE_DISPATCH,
                               OperatorType.OP_MOE_ROUTED_EXPERTS,
                               OperatorType.OP_MOE_COMBINE):
            assert dims.get("expert") is True
    assert seen == new


def test_each_held_expert_is_initialised_as_a_matrix_of_its_own():
    """The default initialiser reads a leading dim as a receptive field;
    on (experts, d, i) that would scale every expert down by sqrt(experts)
    and the routed part out of the layer's output."""
    ff = build(trinity_config())
    experts = next(g for n, g in ff.params.items() if "moeexperts" in n)
    shared = next(g for n, g in ff.params.items() if "moeshared" in n)
    for w in ("gate", "up", "down"):
        ratio = float(np.std(experts[w])) / float(np.std(shared[w]))
        assert 0.9 < ratio < 1.1, (w, ratio)


def test_strict_static_analysis_admits_the_graph():
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.trinity import build_trinity

    config = FFConfig()
    config.parse_args(["-b", str(BATCH), "--static-analysis", "strict"])
    ff = FFModel(config)
    build_trinity(ff, trinity_config())
    ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-3),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    x, y = batch()
    ff.fit(x, y, batch_size=BATCH, epochs=1, shuffle=False)
    assert ff.routing_stats()


@pytest.mark.parametrize("kwargs", [dict(window=4), dict(num_kv_heads=3)])
def test_attention_attributes_that_cannot_hold_are_refused(kwargs):
    from flexflow_tpu import FFConfig, FFModel

    ff = FFModel(FFConfig())
    x = ff.create_tensor((2, 8, 16))
    with pytest.raises(ValueError):
        ff.multihead_attention(x, x, x, embed_dim=16, num_heads=4, **kwargs)


@pytest.mark.parametrize("kwargs", [dict(window=4), dict(num_kv_heads=2)],
                         ids=["window", "grouped"])
def test_sequence_parallel_grouped_or_windowed_attention_is_refused(kwargs):
    """A strategy that shards the sequence of such an attention must not be
    run with the axis silently ignored (the ring and all-to-all schedules
    hold as many K/V heads as query heads and whole-context attention)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ops.base import OpContext

    ff = FFModel(FFConfig())
    x = ff.create_tensor((2, 8, 16))
    ff.multihead_attention(x, x, x, embed_dim=16, num_heads=4, bias=False,
                           causal=True, name="attn", **kwargs)
    node = next(n for n in ff.create_pcg().compute_nodes()
                if n.name.startswith("attn"))
    node.op.attrs["sequence_parallel_axis"] = "seq"
    kv = kwargs.get("num_kv_heads", 4)
    params = {"wq": jnp.zeros((16, 4, 4)), "wk": jnp.zeros((16, kv, 4)),
              "wv": jnp.zeros((16, kv, 4)), "wo": jnp.zeros((4, 4, 16))}
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("seq",))
    h = jnp.zeros((2, 8, 16))
    with pytest.raises(NotImplementedError, match="sequence-parallel"):
        node.op.forward(params, [h, h, h], OpContext(mesh=mesh))


@pytest.mark.parametrize("score_func", ["softmax", "sigmiod"])
def test_a_score_function_other_than_sigmoid_is_refused(score_func):
    """The routed layer scores with sigmoid alone: another name (a typo
    included) must not silently select anything, in the builder or in the
    reference."""
    with pytest.raises(ValueError, match="score_func"):
        trinity_config(score_func=score_func)
    x, router, _, _ = _layer_inputs()
    with pytest.raises(ValueError, match="score_func"):
        ref.routing(x, router, dict(LAYER_CONFIG, score_func=score_func))


def test_the_search_offers_no_plan_a_grouped_windowed_attention_refuses():
    """Sequence parallelism is for plain attention (the op refuses it
    otherwise), and head parallelism needs whole K/V groups a shard."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.search.unity import node_options

    def kinds(tp, **kwargs):
        ff = FFModel(FFConfig())
        x = ff.create_tensor((2, 8, 16))
        ff.multihead_attention(x, x, x, embed_dim=16, num_heads=8,
                               bias=False, causal=True, name="attn",
                               **kwargs)
        node = next(n for n in ff.create_pcg().compute_nodes()
                    if n.name.startswith("attn"))
        return {kind for kind, _, _ in node_options(node, tp,
                                                    [(2, 8, 16)] * 3)}

    assert {"heads", "ring"} <= kinds(4)
    assert "ring" not in kinds(4, window=4)
    assert kinds(4, num_kv_heads=2) == {"none"}
    assert "heads" in kinds(2, num_kv_heads=2)
    assert "ring" not in kinds(2, num_kv_heads=2)
