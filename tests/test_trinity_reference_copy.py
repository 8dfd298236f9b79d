"""``benchmark/reference/trinity-mini.py`` is ``tests/reference_trinity.py``
made to fit beside a resident training state (blocks of query rows, blocks of
logits, layers under ``jax.checkpoint``): the two give the same logits, loss
and gradients on seeded inputs, also where the blocks are smaller than the
sequence; and the benchmark's copy names the groups the ISSUE lists."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_trinity as plain
from test_trinity import TINY

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def blocked():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_trinity", os.path.join(
            HERE, "..", "benchmark", "reference", "trinity-mini.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded_params(config, seed=0):
    """A tree with the system's names and shapes, from numpy alone."""
    rng = np.random.default_rng(seed)
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    inter, moe = config["intermediate_size"], config["moe_intermediate_size"]
    n, held = config["num_experts"], config["experts_held"][1]

    def w(*shape, scale=0.2):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    def gain(*shape):
        return (1 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    params = {"embed_0": {"weight": w(config["vocab_size"], h, scale=0.05)},
              "norm_f_90": {"scale": gain(h)},
              "lm_head_91": {"kernel": w(h, config["vocab_size"])}}
    for i in range(len(config["layer_types"])):
        for k in range(1, 5):
            params[f"l{i}_norm{k}_{10 * i + k}"] = {"scale": gain(h)}
        params[f"l{i}_attn_{10 * i + 5}"] = {
            "wq": w(h, heads, d), "wk": w(h, kv, d), "wv": w(h, kv, d),
            "wg": w(h, heads, d), "wo": w(heads, d, h),
            "q_norm": gain(d), "k_norm": gain(d)}
        if i < config["num_dense_layers"]:
            params[f"l{i}_mlp_{10 * i + 6}"] = {
                "gate": w(h, inter), "up": w(h, inter), "down": w(inter, h)}
            continue
        params[f"l{i}_moerouter_{10 * i + 6}"] = {
            "kernel": w(h, n, scale=1.0),
            "expert_bias": rng.uniform(-0.3, 0.3, n).astype(np.float32)}
        params[f"l{i}_moeexperts_{10 * i + 7}"] = {
            "gate": w(held, h, moe), "up": w(held, h, moe),
            "down": w(held, moe, h)}
        params[f"l{i}_moeshared_{10 * i + 8}"] = {
            "gate": w(h, moe), "up": w(h, moe), "down": w(moe, h)}
    return params


@pytest.mark.parametrize("query_block,loss_block", [(256, 1024), (8, 16)],
                         ids=["one-block", "many-blocks"])
def test_blocked_copy_equals_the_plain_reference(blocked, monkeypatch,
                                                 query_block, loss_block):
    monkeypatch.setattr(blocked, "QUERY_BLOCK", query_block)
    monkeypatch.setattr(blocked, "LOSS_BLOCK", loss_block)
    params = seeded_params(TINY)
    s = np.random.default_rng(3).integers(
        0, TINY["vocab_size"], size=(2, 33)).astype(np.int32)
    x, y = s[:, :-1], s[:, 1:]
    want_loss, want = plain.loss_and_grads(params, x, y, TINY)
    wanted = list(params)
    got_loss, got = blocked.loss_and_grads(params, x, y, TINY, wanted)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    for name in wanted:
        for weight, g in got[name].items():
            r = np.asarray(want[name][weight])
            if weight == "expert_bias":
                assert not np.asarray(g).any() and not r.any()
                continue
            err = np.linalg.norm(np.asarray(g) - r) / np.linalg.norm(r)
            assert err < 1e-4, (name, weight, err)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            blocked.logits(params, jnp.asarray(x), TINY),
            plain.logits(params, jnp.asarray(x), TINY), rtol=1e-4, atol=1e-5)


def test_a_subset_of_groups_gets_the_same_gradients(blocked):
    params = seeded_params(TINY)
    s = np.random.default_rng(4).integers(
        0, TINY["vocab_size"], size=(1, 33)).astype(np.int32)
    wanted = blocked.checked_params(params, TINY)
    _, some = blocked.loss_and_grads(params, s[:, :-1], s[:, 1:], TINY,
                                     wanted)
    _, every = blocked.loss_and_grads(params, s[:, :-1], s[:, 1:], TINY,
                                      list(params))
    assert sorted(some) == sorted(wanted)
    for name in wanted:
        for weight in some[name]:
            np.testing.assert_allclose(some[name][weight],
                                       every[name][weight], rtol=1e-5,
                                       atol=1e-8)


def test_checked_groups_are_the_stated_ones(blocked):
    names = blocked.checked_params(seeded_params(TINY), TINY)
    stems = [n.rsplit("_", 1)[0] for n in names]
    assert stems == ["embed", "l0_attn", "l0_mlp", "l1_attn", "l2_attn",
                     "l2_moeshared", "norm_f", "lm_head",
                     "l1_norm3", "l1_norm4", "l2_norm3", "l2_norm4"]
    assert not any("moeexperts" in n or "moerouter" in n for n in names)


def test_neither_reference_imports_the_system(blocked):
    for mod in (blocked, plain):
        with open(mod.__file__) as f:
            src = f.read()
        assert "import flexflow_tpu" not in src
        assert "from flexflow_tpu" not in src
