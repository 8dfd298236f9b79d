"""Plain reference of the hybrid gated-delta-rule / attention decoder
(``flexflow_tpu/models/olmo_hybrid.py``; Olmo-Hybrid-7B's ``config.json``):
the equations in float32 ``jax.numpy`` at matmul precision "highest", the
recurrence as a plain ``lax.scan`` over TOKENS (never the chunked form) — no
kernel, no cache, no batching, nothing of ``flexflow_tpu``.
``benchmark/reference/olmo-hybrid-7b.py`` is this file, byte for byte (a
tier-1 test holds the two equal).

With ``d`` the hidden size, ``H = linear_num_key_heads``, ``d_k =
linear_key_head_dim``, ``d_v = linear_value_head_dim``, ``K =
linear_conv_kernel_dim``, RMS(.; w) the RMS norm with gain ``w`` and eps
``rms_norm_eps``:

    h0 = Emb[ids]                                 (no position signal anywhere)
    layer i:  h <- h + RMS(Mix_i(h); w_1)         Mix_i = Attn where layer_types[i] is
              h <- h + RMS(W_down(silu(W_gate h) * (W_up h)); w_2)   "full_attention", else GDN
    logits = RMS(h; w_f) W_head

    GDN(u), t = 0..L-1, a head:
      q'_t = W_q u_t    k'_t = W_k u_t    v'_t = W_v u_t
      x_t  = silu( sum_j w_x[:, j] * x'_{t-K+1+j} )    x in {q, k, v};  x'_{<0} = 0;  no bias
      q^_t = q_t / sqrt(|q_t|^2 + 1e-6) * d_k^-1/2     k^_t = k_t / sqrt(|k_t|^2 + 1e-6)
      b_t  = 2 sigmoid(W_b u_t)           (the 2 is linear_allow_neg_eigval)
      g_t  = -exp(A_log) softplus(W_a u_t + dt_bias);       a_t = exp(g_t)
      S_t  = a_t S_{t-1} + k^_t ( b_t ( v_t - (a_t S_{t-1})^T k^_t ) )^T       S_{-1} = 0
      o_t  = S_t^T q^_t
      y_t  = RMS(o_t; w_n) * silu( (W_g u_t)_head )
      out_t = W_o concat_heads y_t

    Attn(u): q = W_q u, k = W_k u, v = W_v u (heads of d / heads), no bias,
             NO positions; q <- RMS over the WHOLE width (q; w_q), k likewise;
             causal softmax(q k^T / sqrt(head)) v; W_o.

Not in the config and assumed, each also in the configuration's file: where a
block's norms sit (``NORM_PLACEMENT``: the OLMo 2 / OLMo 3 family's, a norm on
each sublayer's OUTPUT), the whole-width q and k norms, no conv bias, a silu
output gate.

So that thousands of positions fit beside a resident serving engine: the
weights are upcast a layer at a time inside the jitted layer functions (they
are passed as arguments, never closed over), attention runs in blocks of
query rows, and the head is applied to blocks of rows whose logits leave the
device before the next block is made, a block of the vocabulary's columns at
a time (the published head is 1.5 GB in float32; the result is a host array).

``params`` is the system's own tree (``{"l1_gdn_17": {"w_q": ...}, ...}``);
node-number suffixes are ignored. ``fault`` names one planted fault of the
recurrence — the tests' controls, which the comparison must refuse
(``FAULTS``).
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

#: "post": ``h + RMS(sublayer(h))`` (OLMo 2 / 3); "pre": ``h + sublayer(RMS(h))``
NORM_PLACEMENT = "post"
#: planted faults of the recurrence (tests): the reference computes the
#: WRONG thing, and the comparison with the program must refuse it
FAULTS = ("stale_state", "alpha_after", "beta_no_two", "bf16_state",
          "conv_shift")
QUERY_BLOCK = 512
HEAD_ROWS = 1024
HEAD_COLUMNS = 16384


def find(params, prefix):
    keys = [k for k in params
            if re.fullmatch(re.escape(prefix) + r"(_\d+)?", k)]
    if len(keys) != 1:
        raise KeyError(f"{prefix}: {keys}")
    return keys[0]


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def delta_mixer(u, p, config, fault=None, with_state=False):
    """u (L, d) -> (L, d); ``with_state``: and the state after the last
    token, (H, d_k, d_v)."""
    heads, dk, dv, k_w = (config["linear_num_key_heads"],
                          config["linear_key_head_dim"],
                          config["linear_value_head_dim"],
                          config["linear_conv_kernel_dim"])
    eps = config["rms_norm_eps"]
    length = u.shape[0]
    xp = jnp.concatenate([u @ p["w_q"], u @ p["w_k"], u @ p["w_v"]], axis=1)
    shift = 1 if fault == "conv_shift" else 0
    padded = jnp.pad(xp, ((k_w - 1 + shift, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[j:j + length] * p["conv_w"][:, j]
                        for j in range(k_w)))
    q = l2_norm(x[:, :heads * dk].reshape(length, heads, dk)) * dk ** -0.5
    k = l2_norm(x[:, heads * dk:2 * heads * dk].reshape(length, heads, dk))
    v = x[:, 2 * heads * dk:].reshape(length, heads, dv)
    strength = 2.0 if config["linear_allow_neg_eigval"] else 1.0
    if fault == "beta_no_two":
        strength = 1.0
    beta = strength * jax.nn.sigmoid(u @ p["w_b"])              # (L, H)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(u @ p["w_a"] + p["dt_bias"])

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        decayed = jnp.exp(g_t)[:, None, None] * s
        seen = s if fault == "alpha_after" else decayed
        u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", seen, k_t))
        s_new = decayed + k_t[:, :, None] * u_t[:, None, :]
        if fault == "bf16_state":
            # not a pair of converts: the compiler may keep the excess
            # precision of those (xla_allow_excess_precision), and does
            # on the TPU
            s_new = jax.lax.reduce_precision(s_new, exponent_bits=8,
                                             mantissa_bits=7)
        read = s if fault == "stale_state" else s_new
        return s_new, jnp.einsum("hkv,hk->hv", read, q_t)

    s_last, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                             (q, k, v, g, beta))
    y = rms_norm(o, p["norm_w"], eps) * jax.nn.silu(
        (u @ p["w_g"]).reshape(length, heads, dv))
    out = y.reshape(length, heads * dv) @ p["w_o"]
    return (out, s_last) if with_state else out


def attention(u, p, config):
    """u (L, d) -> (L, d): causal multi-head attention, no positions, an RMS
    norm over the whole q and k width."""
    length = u.shape[0]
    eps = config["rms_norm_eps"]
    q = jnp.einsum("sd,dhk->shk", u, p["wq"])
    k = jnp.einsum("sd,dhk->shk", u, p["wk"])
    v = jnp.einsum("sd,dhk->hsk", u, p["wv"])
    q, k = (jnp.swapaxes(rms_norm(
        t.reshape(length, -1), gain.reshape(-1), eps).reshape(t.shape), 0, 1)
        for t, gain in ((q, p["q_norm"]), (k, p["k_norm"])))
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    out = []
    for lo in range(0, length, QUERY_BLOCK):
        rows = jnp.arange(lo, min(lo + QUERY_BLOCK, length))
        score = jnp.einsum("hsk,htk->hst", q[:, lo:lo + QUERY_BLOCK],
                           k) * scale
        seen = jnp.arange(length)[None, :] <= rows[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf),
                              axis=-1)
        out.append(jnp.einsum("hst,htk->hsk", prob, v))
    o = jnp.concatenate(out, axis=1)
    return jnp.einsum("hsv,hvd->sd", o, p["wo"])


def gated_mlp(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def sublayer(h, gain, eps, fn):
    if NORM_PLACEMENT == "post":
        return h + rms_norm(fn(h), gain, eps)
    return h + fn(rms_norm(h, gain, eps))


@functools.partial(jax.jit, static_argnames=("kind", "eps", "static"))
def _layer(h, norm1, mix, norm2, mlp, *, kind, eps, static):
    """One layer; the weights arrive as stored and are upcast here."""
    config = dict(static)
    with jax.default_matmul_precision("highest"):
        norm1, mix, norm2, mlp = f32((norm1, mix, norm2, mlp))
        if kind == "full_attention":
            mixer = lambda x: attention(x, mix, config)
        else:
            mixer = lambda x: delta_mixer(x, mix, config,
                                          config.get("fault"))
        h = sublayer(h, norm1["scale"], eps, mixer)
        return sublayer(h, norm2["scale"], eps, lambda x: gated_mlp(x, mlp))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, gain, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        gain, kernel = f32((gain, kernel))
        return rms_norm(h, gain, eps) @ kernel


def head(h, gain, kernel, eps):
    """(rows, d) -> (rows, vocabulary) on the host, a block of rows and of
    the vocabulary's columns at a time."""
    return np.concatenate([np.concatenate([
        np.asarray(_head(h[lo:lo + HEAD_ROWS], gain,
                         kernel[:, c:c + HEAD_COLUMNS], eps=eps))
        for c in range(0, kernel.shape[1], HEAD_COLUMNS)], axis=1)
        for lo in range(0, h.shape[0], HEAD_ROWS)], axis=0)


def logits(params, ids, config, fault=None):
    """ids (L,) -> (L, vocabulary) float32, a host array."""
    if fault is not None and fault not in FAULTS:
        raise KeyError(fault)
    eps = float(config["rms_norm_eps"])
    static = tuple(sorted(
        (k, config[k]) for k in (
            "linear_num_key_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_allow_neg_eigval", "rms_norm_eps"))) \
        + ((("fault", fault),) if fault else ())
    embed = params[find(params, "embed")]["weight"]
    h = jnp.asarray(embed[jnp.asarray(ids)], jnp.float32)
    for i, kind in enumerate(config["layer_types"]):
        full = kind == "full_attention"
        mix = params[find(params, f"l{i}_attn" if full else f"l{i}_gdn")]
        h = _layer(h, params[find(params, f"l{i}_norm1")], mix,
                   params[find(params, f"l{i}_norm2")],
                   params[find(params, f"l{i}_mlp")], kind=kind, eps=eps,
                   static=static)
    gain = params[find(params, "norm_f")]["scale"]
    kernel = params[find(params, "lm_head")]["kernel"]
    return head(h, gain, kernel, eps)


class Reference:
    """The benchmark driver's interface."""

    def __init__(self, params, config):
        self.params, self.config = params, config

    def logits(self, ids):
        return logits(self.params, ids, self.config)
