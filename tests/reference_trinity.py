"""Plain reference of the sliding/global grouped-query decoder with a routed
expert layer (``flexflow_tpu/models/trinity.py``): the published equations in
float32 ``jax.numpy`` at matmul precision "highest". No kernel, no mixed
precision, nothing of ``flexflow_tpu``: a dense loop over the held experts, a
mask built from positions.

    h0 = E[ids] * sqrt(hidden)                                   (mup)
    a = post_attn_norm(Attn_i(input_norm(h)));  h <- h + a
    m = post_mlp_norm(MLP_i(pre_mlp_norm(h)));  h <- h + m
    logits = final_norm(h) W_head
    loss = mean over positions of -log clip(softmax(logits), 1e-12, 1)[label]

``config`` is a dict with the published keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``sliding_window``, ``rope_theta``, ``layer_types``, ``num_dense_layers``,
``num_experts``, ``num_experts_per_tok``, ``num_shared_experts``,
``route_scale``, ``route_norm``, ``score_func``, ``rms_norm_eps``,
``mup_enabled``) and ``experts_held`` = [first, count]: the experts of
``num_experts`` whose weights ``params`` holds. The router ranks all
``num_experts`` and normalises over all the chosen; the layer adds the
chosen experts that are held. Embedding and head are whatever rows of the
vocabulary ``params`` holds.

``params`` is the system's own tree (``{"l1_moeexperts_17": {"gate": (n, d,
i), ...}, ...}``); node-number suffixes are ignored.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp


def find(params, prefix):
    keys = [k for k in params
            if re.fullmatch(re.escape(prefix) + r"(_\d+)?", k)]
    if len(keys) != 1:
        raise KeyError(f"{prefix}: {keys}")
    return keys[0]


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rope(x, theta):
    """(b, h, s, d): dim i is paired with dim i + d/2 (rotate-half)."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def head_norm(x, gain, eps):
    """The per-head RMS norm on q and on k: over head_dim, one gain vector."""
    return rms_norm(x, gain, eps)


def output_gate(o, gate):
    return o * jax.nn.sigmoid(gate)


def attention(x, p, sliding: bool, config):
    eps = float(config["rms_norm_eps"])
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    gate = jnp.einsum("bsd,dhk->bhsk", x, p["wg"])
    q, k = head_norm(q, p["q_norm"], eps), head_norm(k, p["k_norm"], eps)
    if sliding:  # full-attention layers carry no position signal
        q, k = rope(q, float(config["rope_theta"])), \
            rope(k, float(config["rope_theta"]))
    group = q.shape[1] // k.shape[1]  # query head n reads K/V head n // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = x.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    visible = j <= i
    if sliding:
        visible &= i - j < int(config["sliding_window"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    o = output_gate(jnp.einsum("bhqk,bhkd->bhqd", probs, v), gate)
    return jnp.einsum("bhsv,hvd->bsd", o, p["wo"])


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(x, p, config):
    """(weights (.., k), chosen (.., k)) over ALL num_experts: the bias
    selects, the plain scores weigh; it takes no gradient."""
    if config["score_func"] != "sigmoid":
        raise ValueError(f"score_func {config['score_func']!r}: sigmoid alone")
    scores = jax.nn.sigmoid(x @ p["kernel"])
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["expert_bias"]),
        int(config["num_experts_per_tok"]))
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * float(config["route_scale"]), chosen


def routed_experts(x, router, experts, config, held):
    """The held experts' share of sum_e w_e Expert_e(x): a dense loop."""
    weights, chosen = routing(x, router, config)
    first, count = held
    y = jnp.zeros_like(x)
    for n in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), axis=-1)
        y = y + w_e[..., None] * gated_mlp(
            x, experts["gate"][n], experts["up"][n], experts["down"][n])
    return y


def layer(h, params, i, config):
    eps = float(config["rms_norm_eps"])
    p = {part: params[find(params, f"l{i}_{part}")]
         for part in ("norm1", "attn", "norm2", "norm3", "norm4")}
    sliding = config["layer_types"][i] == "sliding_attention"
    a = attention(rms_norm(h, p["norm1"]["scale"], eps), p["attn"], sliding,
                  config)
    h = h + rms_norm(a, p["norm2"]["scale"], eps)
    x = rms_norm(h, p["norm3"]["scale"], eps)
    if i < int(config["num_dense_layers"]):
        mlp = params[find(params, f"l{i}_mlp")]
        m = gated_mlp(x, mlp["gate"], mlp["up"], mlp["down"])
    else:
        m = routed_experts(x, params[find(params, f"l{i}_moerouter")],
                           params[find(params, f"l{i}_moeexperts")], config,
                           tuple(config["experts_held"]))
        if int(config["num_shared_experts"]):  # added once, on every share
            shared = params[find(params, f"l{i}_moeshared")]
            m = m + gated_mlp(x, shared["gate"], shared["up"],
                              shared["down"])
    return h + rms_norm(m, p["norm4"]["scale"], eps)


def logits(params, ids, config):
    h = params[find(params, "embed")]["weight"][ids]
    if config["mup_enabled"]:
        h = h * jnp.sqrt(jnp.float32(int(config["hidden_size"])))
    for i in range(len(config["layer_types"])):
        h = layer(h, params, i, config)
    h = rms_norm(h, params[find(params, "norm_f")]["scale"],
                 float(config["rms_norm_eps"]))
    return h @ params[find(params, "lm_head")]["kernel"]


def loss(params, ids, labels, config):
    probs = jax.nn.softmax(logits(params, ids, config), axis=-1)
    logp = jnp.log(jnp.clip(probs, 1e-12, 1.0))
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss_and_grads(params, ids, labels, config):
    """(loss, gradients of every parameter) in float32, precision highest."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        return jax.value_and_grad(loss)(params, jnp.asarray(ids, jnp.int32),
                                        jnp.asarray(labels, jnp.int32),
                                        config)
