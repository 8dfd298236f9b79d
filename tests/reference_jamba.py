"""Plain reference of the hybrid state-space / attention decoder
(``flexflow_tpu/models/jamba.py``; AI21-Jamba2-3B's ``config.json``): the
equations in float32 ``jax.numpy`` at matmul precision "highest", the
recurrence as a plain ``lax.scan`` over tokens — no kernel, no cache, no
batching, nothing of ``flexflow_tpu``. ``benchmark/reference/
ai21-jamba2-3b.py`` is this file, byte for byte (a tier-1 test holds the two
equal).

With ``d`` the hidden size, ``E = mamba_expand * d``, ``N = mamba_d_state``,
``R = mamba_dt_rank``, ``K = mamba_d_conv``, RMS(.; g) the RMS norm with gain
``g`` and eps ``rms_norm_eps``:

    h0 = Emb[ids]                                 (no position signal anywhere)
    layer i:  u = RMS(h; g_1);  h <- h + Mix_i(u)    Mix_i = Attn if i % period == offset
              v = RMS(h; g_2);  h <- h + W_down(silu(W_gate v) * (W_up v))   else Mamba
    logits = RMS(h; g_f) W_head

    Mamba(u), t = 0..L-1:
      [x'_t ; z_t] = W_in u_t
      x_t   = silu(b_c + sum_k w_c[:, k] * x'_{t-K+1+k})         x'_{<0} = 0
      [r_t ; B_t ; C_t] = W_x x_t
      dt_t  = softplus(W_dt RMS(r_t; g_dt) + b_dt);  B_t <- RMS(B_t; g_B);  C_t <- RMS(C_t; g_C)
      S_t   = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t     A = -exp(A_log), S_{-1} = 0
      y_t   = S_t C_t + D * x_t
      out_t = W_out(y_t * silu(z_t))

    Attn(u): q = W_q u (heads of d / heads), k = W_k u, v = W_v u
             (``num_key_value_heads`` heads, each read by a group of query
             heads), no bias, no rotary; causal softmax(q k^T / sqrt(head)) v; W_o.

Not in the config and assumed, each also in the configuration's file: the
three inner norms (HF ``JambaMambaMixer``'s ``dt_layernorm``,
``b_layernorm``, ``c_layernorm``), the layer order (``i % period ==
offset``), no positions. The head is a matrix of its own where ``params``
holds ``lm_head`` (the system's stated departure) and the embedding
transposed where it does not.

So that 8,192 positions fit beside a resident serving engine: the weights
are upcast a layer at a time inside the jitted layer functions (they are
passed as arguments, never closed over), attention runs in blocks of query
rows, and the head is applied to blocks of rows whose logits leave the
device before the next block is made (the result is a host array).

``params`` is the system's own tree (``{"l1_ssm_17": {"w_in": ...}, ...}``);
node-number suffixes are ignored. ``a_log`` is stored ``(N, E)``. ``fault``
names one planted fault of the recurrence — the tests' controls, which the
comparison must refuse (``FAULTS``).
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

#: planted faults of the recurrence (tests): the reference computes the
#: WRONG thing, and the comparison with the program must refuse it
FAULTS = ("stale_state", "conv_shift", "no_dt_bias", "bf16_state")
QUERY_BLOCK = 512
HEAD_ROWS = 1024


def find(params, prefix):
    keys = [k for k in params
            if re.fullmatch(re.escape(prefix) + r"(_\d+)?", k)]
    if len(keys) != 1:
        raise KeyError(f"{prefix}: {keys}")
    return keys[0]


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def mamba(u, p, config, fault=None, with_state=False):
    """u (L, d) -> (L, d); ``with_state``: and the state after the last
    token, (N, E)."""
    n, k_w, r = (config["mamba_d_state"], config["mamba_d_conv"],
                 config["mamba_dt_rank"])
    eps = config["rms_norm_eps"]
    length = u.shape[0]
    xz = u @ p["w_in"]
    if "b_in" in p:
        xz = xz + p["b_in"]
    e = xz.shape[1] // 2
    xp, z = xz[:, :e], xz[:, e:]
    shift = 1 if fault == "conv_shift" else 0
    padded = jnp.pad(xp, ((k_w - 1 + shift, 0), (0, 0)))
    conv = sum(padded[k:k + length] * p["conv_w"][:, k] for k in range(k_w))
    if "conv_b" in p:
        conv = conv + p["conv_b"]
    x = jax.nn.silu(conv)
    rbc = x @ p["w_x"]
    dt = rms_norm(rbc[:, :r], p["dt_norm"], eps) @ p["w_dt"]
    if fault != "no_dt_bias":
        dt = dt + p["b_dt"]
    dt = jax.nn.softplus(dt)
    b = rms_norm(rbc[:, r:r + n], p["b_norm"], eps)
    c = rms_norm(rbc[:, r + n:], p["c_norm"], eps)
    a = -jnp.exp(p["a_log"])                                   # (N, E)

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s_new = jnp.exp(dt_t[None, :] * a) * s \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        if fault == "bf16_state":
            # not a pair of converts: the compiler may keep the excess
            # precision of those (xla_allow_excess_precision), and does
            # on the TPU
            s_new = jax.lax.reduce_precision(s_new, exponent_bits=8,
                                             mantissa_bits=7)
        read = s if fault == "stale_state" else s_new
        return s_new, jnp.sum(read * c_t[:, None], axis=0)

    s_last, y = jax.lax.scan(step, jnp.zeros_like(a), (x, dt, b, c))
    y = y + p["d_skip"] * x
    out = (y * jax.nn.silu(z)) @ p["w_out"]
    if "b_out" in p:
        out = out + p["b_out"]
    return (out, s_last) if with_state else out


def attention(u, p, config):
    """u (L, d) -> (L, d): grouped-query causal attention, no positions."""
    length = u.shape[0]
    q = jnp.einsum("sd,dhk->hsk", u, p["wq"])
    k = jnp.einsum("sd,dhk->hsk", u, p["wk"])
    v = jnp.einsum("sd,dhk->hsk", u, p["wv"])
    group = q.shape[0] // k.shape[0]
    k, v = (jnp.repeat(t, group, axis=0) for t in (k, v))
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
    y = jnp.einsum("hsv,hvd->sd", o, p["wo"])
    return y + p["bo"] if "bo" in p else y


def gated_mlp(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


@functools.partial(jax.jit, static_argnames=("kind", "eps", "static"))
def _layer(h, norm1, mix, norm2, mlp, *, kind, eps, static):
    """One layer; the weights arrive as stored and are upcast here."""
    config = dict(static)
    with jax.default_matmul_precision("highest"):
        norm1, mix, norm2, mlp = f32((norm1, mix, norm2, mlp))
        u = rms_norm(h, norm1["scale"], eps)
        if kind == "attention":
            h = h + attention(u, mix, config)
        else:
            h = h + mamba(u, mix, config, config.get("fault"))
        return h + gated_mlp(rms_norm(h, norm2["scale"], eps), mlp)


@functools.partial(jax.jit, static_argnames=("eps", "transposed"))
def _head(h, gain, kernel, *, eps, transposed):
    with jax.default_matmul_precision("highest"):
        gain, kernel = f32((gain, kernel))
        x = rms_norm(h, gain, eps)
        return x @ (kernel.T if transposed else kernel)


def is_attention(i, config):
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def logits(params, ids, config, fault=None):
    """ids (L,) -> (L, vocabulary) float32, a host array."""
    if fault is not None and fault not in FAULTS:
        raise KeyError(fault)
    eps = float(config["rms_norm_eps"])
    static = tuple(sorted(
        (k, config[k]) for k in ("mamba_d_state", "mamba_d_conv",
                                 "mamba_dt_rank", "rms_norm_eps"))) \
        + ((("fault", fault),) if fault else ())
    embed = params[find(params, "embed")]["weight"]
    h = jnp.asarray(embed[jnp.asarray(ids)], jnp.float32)
    for i in range(config["num_hidden_layers"]):
        attn = is_attention(i, config)
        mix = params[find(params, f"l{i}_attn" if attn else f"l{i}_ssm")]
        h = _layer(h, params[find(params, f"l{i}_norm1")], mix,
                   params[find(params, f"l{i}_norm2")],
                   params[find(params, f"l{i}_mlp")],
                   kind="attention" if attn else "mamba", eps=eps,
                   static=static)
    gain = params[find(params, "norm_f")]["scale"]
    try:
        kernel, transposed = params[find(params, "lm_head")]["kernel"], False
    except KeyError:
        kernel, transposed = embed, True
    rows = [np.asarray(_head(h[lo:lo + HEAD_ROWS], gain, kernel, eps=eps,
                             transposed=transposed))
            for lo in range(0, h.shape[0], HEAD_ROWS)]
    return np.concatenate(rows, axis=0)


class Reference:
    """The benchmark driver's interface."""

    def __init__(self, params, config):
        self.params, self.config = params, config

    def logits(self, ids):
        return logits(self.params, ids, self.config)
