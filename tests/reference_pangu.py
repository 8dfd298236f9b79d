"""Plain reference of the latent-attention decoder with sandwich norms and a
routed top-k expert layer (``flexflow_tpu/models/pangu.py``;
openPangu-Ultra-MoE-718B's ``config.json``): the equations in float32
``jax.numpy`` at matmul precision "highest". Materialised attention only — no
absorption, no cache, no batching, no kernel, nothing of ``flexflow_tpu``.

    h0 = E[ids]
    a = MLA(norm1(h));  h <- h + norm2(a)          (``sandwich_norm``)
    m = MLP(norm3(h));  h <- h + norm4(m)
    logits = norm_f(h) W_head

MLA, x = norm1(h): ``c_q = RMS(x W_qa)``; ``[q_n | q_r] = c_q W_qb`` (heads x
(nope | rope)); ``[c_kv | k_r] = x W_kva``; ``c_kv <- RMS(c_kv)``; rotary
positions (rotate-half pairing) on ``q_r`` and on ``k_r``, one rotary key for
all heads; ``[k_n | v] = c_kv W_kvb`` (heads x (nope | v)); ``score = (q_n .
k_n + q_r . k_r) / sqrt(nope + rope)``, causal softmax, ``y = concat(sum p v)
W_o``. Expert layer, x = norm3(h): ``s = sigmoid(x W_g)`` in float32 over all
``n_routed_experts``, the ``num_experts_per_tok`` largest chosen, ``w_i =
routed_scaling_factor * s_i / sum_chosen s`` (``norm_topk_prob``); ``m =
SwiGLU_shared(x) + sum over the chosen AND HELD of w_i SwiGLU_i(x)``.

Departures from the published description, each also in the configuration
file: sigmoid scores with no selection bias and no expert groups (the config
has neither key); no long-context factor on the softmax scale (no
``rope_scaling`` key); the order of the four norms as above; the
multi-token-prediction module is not part of the forward pass.

``config`` is a dict with the published keys and ``experts_held`` = [first,
count]: the experts of ``n_routed_experts`` whose weights ``params`` holds.
Embedding and head are whatever rows of the vocabulary ``params`` holds.
``params`` is the system's own tree (``{"l1_mla_17": {"wq_a": ...}, ...}``);
node-number suffixes are ignored. ``leave_out`` names one piece of the
mathematics to drop — the tests' controls: the comparison must refuse each.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

LEAVE_OUT = ("rope_k", "kv_norm", "q_norm", "norm2", "norm4", "route_scale",
             "norm_topk_prob")


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
    """(..., s, d) at positions 0..s-1: dim i pairs with dim i + d/2."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def latent_attention(x, p, config, leave_out=None):
    """x (s, d) -> (s, d)."""
    heads = config["num_attention_heads"]
    nope, rdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    s = x.shape[0]
    c_q = x @ p["wq_a"]
    if leave_out != "q_norm":
        c_q = rms_norm(c_q, p["q_norm"], eps)
    q = (c_q @ p["wq_b"]).reshape(s, heads, nope + rdim)
    q_n = q[..., :nope]
    q_r = jnp.swapaxes(rope(jnp.swapaxes(q[..., nope:], 0, 1), theta), 0, 1)
    kv = x @ p["wkv_a"]
    c_kv, k_r = kv[:, :rank], kv[:, rank:]
    if leave_out != "kv_norm":
        c_kv = rms_norm(c_kv, p["kv_norm"], eps)
    if leave_out != "rope_k":
        k_r = rope(k_r, theta)
    up = (c_kv @ p["wkv_b"]).reshape(s, heads, nope + vd)
    k_n, v = up[..., :nope], up[..., nope:]
    score = (jnp.einsum("shd,thd->hst", q_n, k_n)
             + jnp.einsum("shr,tr->hst", q_r, k_r)) / jnp.sqrt(
                 jnp.float32(nope + rdim))
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    prob = jax.nn.softmax(jnp.where(causal[None], score, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thd->shd", prob, v)
    return o.reshape(s, heads * vd) @ p["wo"]


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(x, kernel, config, leave_out=None):
    """(weights (s, k), chosen (s, k)) over all ``n_routed_experts``."""
    score = jax.nn.sigmoid(x @ kernel)
    weights, chosen = jax.lax.top_k(score, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", True) and leave_out != "norm_topk_prob":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if leave_out != "route_scale":
        weights = weights * config["routed_scaling_factor"]
    return weights, chosen


def routed_experts(x, router, experts, config, held, leave_out=None):
    """The held experts' part of the layer: a dense loop over them."""
    weights, chosen = routing(x, router["kernel"], config, leave_out)
    first, count = held
    out = jnp.zeros_like(x)
    for n in range(count):
        w = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), axis=-1)
        out = out + w[:, None] * gated_mlp(
            x, experts["gate"][n], experts["up"][n], experts["down"][n])
    return out


def layer(h, params, i, config, leave_out=None):
    eps = config["rms_norm_eps"]

    def norm(k, x):
        if not config.get("sandwich_norm", True) and k in (2, 4):
            return x
        if leave_out == f"norm{k}":
            return x
        return rms_norm(x, params[find(params, f"l{i}_norm{k}")]["scale"],
                        eps)

    a = latent_attention(norm(1, h), params[find(params, f"l{i}_mla")],
                         config, leave_out)
    h = h + norm(2, a)
    x = norm(3, h)
    if i < config["first_k_dense_replace"]:
        p = params[find(params, f"l{i}_mlp")]
        m = gated_mlp(x, p["gate"], p["up"], p["down"])
    else:
        m = routed_experts(
            x, params[find(params, f"l{i}_moerouter")],
            params[find(params, f"l{i}_moeexperts")], config,
            tuple(config.get("experts_held")
                  or (0, config["n_routed_experts"])), leave_out)
        if config.get("n_shared_experts", 0):
            p = params[find(params, f"l{i}_moeshared")]
            m = m + gated_mlp(x, p["gate"], p["up"], p["down"])
    return h + norm(4, m)


def logits(params, ids, config, leave_out=None):
    """ids (s,) -> (s, vocabulary rows held), float32."""
    if leave_out is not None and leave_out not in LEAVE_OUT:
        raise KeyError(leave_out)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        h = params[find(params, "embed")]["weight"][ids]
        for i in range(config["num_hidden_layers"]):
            h = layer(h, params, i, config, leave_out)
        h = rms_norm(h, params[find(params, "norm_f")]["scale"],
                     config["rms_norm_eps"])
        return h @ params[find(params, "lm_head")]["kernel"]
