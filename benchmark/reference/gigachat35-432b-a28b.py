"""Plain reference of the hybrid delta-rule / latent-attention decoder over a
routed expert layer (``flexflow_tpu/models/gigachat.py``;
GigaChat3.5-432B-A28B's ``config.json``): the equations in float32
``jax.numpy`` at matmul precision "highest" — the delta rule as a plain
``lax.scan`` over TOKENS with explicit grouped heads (never the chunked
form), latent attention materialised with explicit YaRN (no absorption), the
routed layer as a loop over the held experts — no kernel, no cache, no
batching, nothing of ``flexflow_tpu``. The tier-1 tests load this file by
its path; there is no second copy.

``config`` is the configuration's file (the published keys; ``n_routed_experts``
there counts the experts HELD, ``router_experts`` the router's width,
``experts_held`` = (first, count)). With ``d`` the hidden size, ``N(x; w) = x
/ rms(x) * norm_gain(w)`` the block norm (eps ``rms_norm_eps``):

    h0 = Emb[ids]
    layer i:  a = Mix_i(N(h; w_1));  h <- h + N(a; w_2)      Mix_i = MLA where i is in
              m = FFN_i(N(h; w_3));  h <- h + N(m; w_4)      full_attention_layers, else GDN
    logits = N(h; w_f) W_head

    GDN(u), H_k key heads, H_v value heads, r = H_v / H_k, t = 0..L-1:
      q'_t = W_q u_t (H_k d_k)   k'_t = W_k u_t (H_k d_k)   v'_t = W_v u_t (H_v d_v)
      x_t  = silu( sum_j w_x[:, j] * x'_{t-K+1+j} )      x in {q, k, v};  x'_{<0} = 0;  no bias
      q^_t = q_t / sqrt(|q_t|^2 + 1e-6) * d_k^-1/2       k^_t = k_t / sqrt(|k_t|^2 + 1e-6)   (a key head)
      value head j reads q^, k^ of key head j // r
      b_t  = sigmoid(W_b u_t)  in (0, 1)^H_v              g_t = -exp(A_log) softplus(W_a u_t + dt_bias)
      S_t  = e^g_t S_{t-1} + k^_t ( b_t ( v_t - (e^g_t S_{t-1})^T k^_t ) )^T      (d_k, d_v) a value head, S_{-1} = 0
      o_t  = S_t^T q^_t
      y_t  = o_t / rms(o_t) * (1 + w_n) * 2 sigmoid( (W_g u_t)_head )     (eps linear_attn_o_norm_eps)
      out_t = W_o concat_heads y_t

    MLA(u): c_q = RMS(u W_qa; w_q);  [q_n | q_r] = c_q W_qb  (heads x (nope | rope))
            [c_kv | k_r] = u W_kva;  c_kv <- RMS(c_kv; w_kv);  [k_n | v] = c_kv W_kvb
            q_r, k_r <- RoPE at the token's position: pairs (2j, 2j + 1) (rope_interleave),
              YaRN frequencies (yarn_inv_freq), cos and sin times mscale / mscale_all_dim's ratio (1)
            score = (q_n . k_n + q_r . k_r) * (nope + rope)^-1/2 * m^2,   m = 0.1 mscale_all_dim ln(factor) + 1
            o = causal softmax(score) v;   o <- o * sigmoid(u W_g) a head (gated_attention);   W_o

    FFN: W_down( silu(min(W_gate x, L)) * clip(W_up x, -L, L) ),  L = swiglu_limit, in the dense
         MLP (i < first_k_dense_replace), the shared expert and the routed experts. Routed: s =
         sigmoid(x W_r) over all router_experts in float32, the num_experts_per_tok largest, weights
         s_i / sum_chosen s * routed_scaling_factor, the held experts' terms alone, plus the shared
         expert ungated.

Not in the config and assumed, each also in the configuration's file: the
block norm's gain form (``norm_gain``: ONE function here, its twin
``flexflow_tpu.ops.normalization.norm_gain`` in the program, held equal by
a tier-1 test — another reading is that one line twice); the two latent
norms' plain gains; the gate's shape on the latent core; ``m^2``
(``use_mla_scaling_factor``); the delta-rule layer's form and ``b``'s range;
the clamp's reach; the router's scoring without a selection bias.

So that 8k positions fit beside a resident serving engine: weights are
passed to the jitted pieces as arguments (never closed over) and upcast
inside them; the delta-rule layer runs a group of value heads at a time,
latent attention a head group at a time and inside it in blocks of query
rows, the dense MLP a block of columns at a time, the held experts as a
``lax.scan``; the head is applied to blocks of rows whose logits leave the
device before the next is made.

**Routing ties** are handled as ``openpangu-ultra-moe-718b.py`` handles
them, on the principle written there: *the program is held to the reference
at every routing the reference itself cannot tell apart*. At each of the
last ``TIE_WINDOW`` positions before the zero padding and each expert layer
where the reference's 8th and 9th scores lie within ``ROUTE_TIE`` and one
of the two experts is held here, the reference also evaluates that position
with the other choice — that token's own forward from that layer on, the
context's rows unchanged: a later delta-rule layer reads the sequence's
state BEFORE the position and its conv tail, a later latent layer the
sequence's rows before it — and returns the row under which the token the
program chose lies nearer the best. ``ROUTE_TIE`` 0 switches it off.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

ROUTE_TIE = 2e-3
TIE_WINDOW = 16
#: planted faults (``config["fault"]``; chip_smoke.py's controls): the
#: reference computes the WRONG thing, and a comparison with the program
#: must read over its limit
FAULTS = ("bf16_state", "k_r_unrotated")
GDN_HEAD_GROUP = 16  # value heads a step of the delta-rule layer
HEAD_GROUP = 16      # heads a step of the attention loop
QUERY_BLOCK = 128    # query rows a step inside it
MLP_BLOCK = 2048     # columns of the dense MLP a step
LOGIT_BLOCK = 2048   # rows of logits that leave the device at a time


def find(params, prefix):
    keys = [k for k in params
            if re.fullmatch(re.escape(prefix) + r"(_\d+)?", k)]
    if len(keys) != 1:
        raise KeyError(f"{prefix}: {keys}")
    return keys[0]


def f32(a):
    return jnp.asarray(a, jnp.float32)


def norm_gain(w, config):
    """``ZeroCenteredGatedNorm``: ``layernorm_gating_weight * sigmoid(w)``,
    ``w`` stored about zero (gain 1 at 0 under the published weight 2)."""
    return float(config.get("layernorm_gating_weight", 2)) \
        * jax.nn.sigmoid(f32(w))


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def block_norm(x, w, config):
    return rms(x, config["rms_norm_eps"]) * norm_gain(w, config)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(b for b in range(1, min(n, cap) + 1) if n % b == 0)


def _cols(w, start, size):
    return f32(jax.lax.dynamic_slice_in_dim(w, start, size, axis=1))


def _rows(w, start, size):
    return f32(jax.lax.dynamic_slice_in_dim(w, start, size, axis=0))


# ------------------------------------------------------------------- rotary
def yarn_inv_freq(d, theta, sc):
    """The DeepSeek-V3 family's closed form: ``theta^(-2j/d)`` for the pairs
    that turn more than ``beta_fast`` times over the original context, that
    over ``factor`` for those that turn fewer than ``beta_slow`` times, a
    linear ramp over the pairs between the two correction dims."""
    orig = float(sc["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(correction_dim(float(sc["beta_fast"]))), 0)
    high = min(np.ceil(correction_dim(float(sc["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    plain = theta ** (-np.arange(0, d, 2) / d)
    return np.asarray(plain / float(sc["factor"]) * ramp + plain * (1 - ramp),
                      np.float32)


def yarn_mscale(factor, mscale):
    return 0.1 * float(mscale) * np.log(float(factor)) + 1.0 \
        if float(factor) > 1 else 1.0


def rope(x, pos, config):
    """x (n, ..., rope) at positions ``pos`` (n,)."""
    d, theta = x.shape[-1], float(config["rope_theta"])
    sc = config.get("rope_scaling")
    if sc:
        inv_freq = yarn_inv_freq(d, theta, sc)
        amp = yarn_mscale(sc["factor"], sc.get("mscale", 1)) \
            / yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0))
    else:
        inv_freq, amp = np.asarray(theta ** (-np.arange(0, d, 2) / d),
                                   np.float32), 1.0
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if config.get("rope_interleave"):
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(config):
    scale = float(config["qk_nope_head_dim"]
                  + config["qk_rope_head_dim"]) ** -0.5
    sc = config.get("rope_scaling")
    if sc and sc.get("mscale_all_dim") \
            and config.get("use_mla_scaling_factor", True):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


# --------------------------------------------------------- latent attention
def attention(u, pos_a, p, config, t: int):
    """MLA over rows ``u (t + a, d)``: the first ``t`` are the sequence,
    position = row; the last ``a`` are alternatives of single positions
    ``pos_a``, each seeing the sequence's rows BEFORE its position and
    itself. ``config["position_offset"]`` (chip_smoke.py: no alternatives
    then) shifts the sequence's positions."""
    heads = config["num_attention_heads"]
    nope, rdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]
    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    b = _divisor(t, QUERY_BLOCK)
    pos = jnp.concatenate([jnp.arange(t, dtype=jnp.int32)
                           + int(config.get("position_offset", 0)), pos_a])
    scale = softmax_scale(config)
    c_q = rms(u @ f32(p["wq_a"]), eps) * f32(p["q_norm"])
    kv = u @ f32(p["wkv_a"])
    c_kv = rms(kv[:, :rank], eps) * f32(p["kv_norm"])
    k_r = kv[:, rank:] if config.get("fault") == "k_r_unrotated" \
        else rope(kv[:, rank:], pos, config)
    kpos = jnp.arange(t)
    gated = bool(config.get("gated_attention"))

    def group(i, y):
        q = (c_q @ _cols(p["wq_b"], i * g * (nope + rdim),
                         g * (nope + rdim))).reshape(-1, g, nope + rdim)
        q_n, q_r = q[..., :nope], rope(q[..., nope:], pos, config)
        up = (c_kv @ _cols(p["wkv_b"], i * g * (nope + vd),
                           g * (nope + vd))).reshape(-1, g, nope + vd)
        k_n, v = up[..., :nope], up[..., nope:]

        def block(j):
            qn = jax.lax.dynamic_slice_in_dim(q_n, j * b, b)
            qr = jax.lax.dynamic_slice_in_dim(q_r, j * b, b)
            s = (jnp.einsum("bgd,tgd->gbt", qn, k_n[:t])
                 + jnp.einsum("bgr,tr->gbt", qr, k_r[:t])) * scale
            seen = kpos[None, :] <= (j * b + jnp.arange(b))[:, None]
            prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("gbt,tgd->bgd", prob, v[:t])

        o = jax.lax.map(block, jnp.arange(t // b)).reshape(t, g, vd)
        if pos_a.shape[0]:
            # the alternatives: the sequence's rows before, and itself
            s = (jnp.einsum("agd,tgd->gat", q_n[t:], k_n[:t])
                 + jnp.einsum("agr,tr->gat", q_r[t:], k_r[:t])) * scale
            own = (jnp.einsum("agd,agd->ga", q_n[t:], k_n[t:])
                   + jnp.einsum("agr,ar->ga", q_r[t:], k_r[t:])) * scale
            s = jnp.where((kpos[None, :] < pos_a[:, None])[None], s,
                          -jnp.inf)
            prob = jax.nn.softmax(
                jnp.concatenate([s, own[..., None]], -1), -1)
            o_a = jnp.einsum("gat,tgd->agd", prob[..., :t], v[:t]) \
                + jnp.swapaxes(prob[..., t], 0, 1)[..., None] * v[t:]
            o = jnp.concatenate([o, o_a])
        if gated:
            o = o * jax.nn.sigmoid(
                u @ _cols(p["wg"], i * g * vd, g * vd)).reshape(o.shape)
        return y + o.reshape(-1, g * vd) @ _rows(p["wo"], i * g * vd, g * vd)

    return jax.lax.fori_loop(0, heads // g, group, jnp.zeros_like(u))


# ------------------------------------------------------------- delta rule
def delta_step(s, q_t, k_t, v_t, g_t, b_t, fault=None):
    """One token: ``s`` (H, d_k, d_v) -> (the new state, o (H, d_v))."""
    decayed = jnp.exp(g_t)[:, None, None] * s
    u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
    s_new = decayed + k_t[:, :, None] * u_t[:, None, :]
    if fault == "bf16_state":
        # not a pair of converts: the compiler may keep the excess precision
        # of those, and does on the TPU
        s_new = jax.lax.reduce_precision(s_new, exponent_bits=8,
                                         mantissa_bits=7)
    return s_new, jnp.einsum("hkv,hk->hv", s_new, q_t)


def delta_mixer(u, pos_a, p, config, t: int, with_state=False):
    """GDN over rows ``u (t + a, d)``, the alternatives as
    :func:`attention` has them: an alternative at position ``p`` reads the
    sequence's state after position ``p - 1`` and its conv inputs at ``p - K
    + 1 .. p - 1``. The alternatives' positions lie in one window of
    ``TIE_WINDOW`` (``pos_a.min()`` on). ``with_state`` (no alternatives):
    and the state after the last token, (H_v, d_k, d_v)."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    k_w = config["linear_conv_kernel_dim"]
    eps = config.get("linear_attn_o_norm_eps", config["rms_norm_eps"])
    gate_scale = float(config.get("linear_sigmoid_gate_scale", 2))
    r = hv // hk
    gv = GDN_HEAD_GROUP if hv % GDN_HEAD_GROUP == 0 \
        and GDN_HEAD_GROUP % r == 0 else hv
    gk = gv // r
    a = pos_a.shape[0]
    w0 = jnp.clip(jnp.min(pos_a), 0, max(t - TIE_WINDOW, 0)) if a else 0
    conv_w = f32(p["conv_w"])
    beta_all = jax.nn.sigmoid(u @ f32(p["w_b"]))                   # (n, H_v)
    g_all = -jnp.exp(f32(p["a_log"])) * jax.nn.softplus(
        u @ f32(p["w_a"]) + f32(p["dt_bias"]))

    def conv(xp, w):
        """xp (t + a, c) before the conv, w (c, K) -> after conv and silu."""
        padded = jnp.pad(xp[:t], ((k_w - 1, 0), (0, 0)))
        seq = sum(padded[j:j + t] * w[:, j] for j in range(k_w))
        if not a:
            return jax.nn.silu(seq)
        at = pos_a[:, None] - (k_w - 1) + jnp.arange(k_w - 1)[None, :]
        hist = jnp.where((at >= 0)[..., None],
                         xp[:t][jnp.clip(at, 0, t - 1)], 0.0)     # (a, K-1, c)
        alt = sum(hist[:, j] * w[:, j] for j in range(k_w - 1)) \
            + xp[t:] * w[:, k_w - 1]
        return jax.nn.silu(jnp.concatenate([seq, alt]))

    def group(i, carry):
        y, s_out = carry
        q = conv(u @ _cols(p["w_q"], i * gk * dk, gk * dk),
                 jax.lax.dynamic_slice_in_dim(conv_w, i * gk * dk, gk * dk))
        k = conv(u @ _cols(p["w_k"], i * gk * dk, gk * dk),
                 jax.lax.dynamic_slice_in_dim(conv_w, (hk + i * gk) * dk,
                                              gk * dk))
        v = conv(u @ _cols(p["w_v"], i * gv * dv, gv * dv),
                 jax.lax.dynamic_slice_in_dim(
                     conv_w, 2 * hk * dk + i * gv * dv, gv * dv))
        # value head j reads key head j // r: explicit, after the norms
        q = jnp.repeat(l2_norm(q.reshape(-1, gk, dk)) * dk ** -0.5, r, axis=1)
        k = jnp.repeat(l2_norm(k.reshape(-1, gk, dk)), r, axis=1)
        v = v.reshape(-1, gv, dv)
        g = jax.lax.dynamic_slice_in_dim(g_all, i * gv, gv, axis=1)
        beta = jax.lax.dynamic_slice_in_dim(beta_all, i * gv, gv, axis=1)

        def step(c, row):
            s, kept = c
            j, q_t, k_t, v_t, g_t, b_t = row
            if a:
                # the state BEFORE position j, kept for the window's rows
                at = jnp.clip(j - w0, 0, TIE_WINDOW - 1)
                old = jax.lax.dynamic_index_in_dim(kept, at, keepdims=False)
                inside = (j >= w0) & (j < w0 + TIE_WINDOW)
                kept = jax.lax.dynamic_update_index_in_dim(
                    kept, jnp.where(inside, s, old), at, 0)
            s, o_t = delta_step(s, q_t, k_t, v_t, g_t, b_t,
                                config.get("fault"))
            return (s, kept), o_t

        zero = jnp.zeros((gv, dk, dv), jnp.float32)
        kept = jnp.zeros((TIE_WINDOW if a else 0, gv, dk, dv), jnp.float32)
        (s_last, kept), o = jax.lax.scan(
            step, (zero, kept),
            (jnp.arange(t), q[:t], k[:t], v[:t], g[:t], beta[:t]))
        if a:
            before = kept[jnp.clip(pos_a - w0, 0, TIE_WINDOW - 1)]
            _, o_a = jax.vmap(delta_step)(before, q[t:], k[t:], v[t:], g[t:],
                                          beta[t:])
            o = jnp.concatenate([o, o_a])
        z = (u @ _cols(p["w_g"], i * gv * dv, gv * dv)).reshape(o.shape)
        out = rms(o, eps) * (1.0 + f32(p["norm_w"])) \
            * (gate_scale * jax.nn.sigmoid(z))
        y = y + out.reshape(-1, gv * dv) @ _rows(p["w_o"], i * gv * dv,
                                                 gv * dv)
        s_out = jax.lax.dynamic_update_slice_in_dim(s_out, s_last, i * gv, 0)
        return y, s_out

    y, s_last = jax.lax.fori_loop(
        0, hv // gv, group,
        (jnp.zeros_like(u), jnp.zeros((hv, dk, dv), jnp.float32)))
    return (y, s_last) if with_state else y


# --------------------------------------------------------------------- FFN
def gated(x, gate, up, down, limit):
    g, u = x @ f32(gate), x @ f32(up)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ f32(down)


def dense_mlp(h, p, config):
    width = p["gate"].shape[1]
    c = _divisor(width, MLP_BLOCK)
    limit = config.get("swiglu_limit")

    def block(i, m):
        return m + gated(h, _cols(p["gate"], i * c, c),
                         _cols(p["up"], i * c, c), _rows(p["down"], i * c, c),
                         limit)

    return jax.lax.fori_loop(0, width // c, block, jnp.zeros_like(h))


def held_range(config):
    return tuple(config.get("experts_held")
                 or (0, config["n_routed_experts"]))


def expert_layer(h, flip, router, experts, shared, config, route_tie):
    """Shared + held routed experts over rows ``h`` and, per row, whether
    its 8th and 9th scores tie with one of the two held (``tied``). Rows
    with ``flip`` set AND tied take the 9th for the 8th."""
    k, limit = config["num_experts_per_tok"], config.get("swiglu_limit")
    first, count = held_range(config)
    score = jax.nn.sigmoid(h @ f32(router["kernel"]))
    top, idx = jax.lax.top_k(score, k + 1)

    def held(e):
        return (e >= first) & (e < first + count)

    tied = (top[:, k - 1] - top[:, k] <= route_tie) \
        & (held(idx[:, k - 1]) | held(idx[:, k]))
    other = (flip & tied)[:, None] & (jnp.arange(k) == k - 1)[None, :]
    chosen = jnp.where(other, idx[:, k:], idx[:, :k])
    weights = jnp.where(other, top[:, k:], top[:, :k])
    if config.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * config["routed_scaling_factor"]

    def expert(m, xs):
        n, gate, up, down = xs
        w = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), axis=-1)
        return m + w[:, None] * gated(h, gate, up, down, limit), None

    m, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(count), experts["gate"], experts["up"],
                         experts["down"]))
    if shared is not None:
        m = m + gated(h, shared["gate"], shared["up"], shared["down"], limit)
    return m, tied


# --------------------------------------------------------------- the layers
def mixer_layer(x, pos_a, p, gain1, gain2, config, t, latent):
    """``x + N2(Mix(N1(x)))`` over the sequence's rows and the
    alternatives'."""
    mix = attention if latent else delta_mixer
    return x + block_norm(mix(block_norm(x, gain1, config), pos_a, p, config,
                              t), gain2, config)


def dense_layer(x, p, gain3, gain4, config):
    return x + block_norm(dense_mlp(block_norm(x, gain3, config), p, config),
                          gain4, config)


def routed_layer(x, flip, router, experts, shared, gain3, gain4, config,
                 route_tie):
    m, tied = expert_layer(block_norm(x, gain3, config), flip, router,
                           experts, shared, config, route_tie)
    return x + block_norm(m, gain4, config), tied


def head_block(x, gain, kernel, config):
    return block_norm(x, gain, config) @ f32(kernel)


class Reference:
    """``Reference(params, config).logits(padded_ids)`` -> (len, vocabulary
    rows held) float32, a host array. ``params`` stays where it is (the
    engine's own tree on the device); nothing is copied at rest."""

    def __init__(self, params, config: dict, route_tie: float = ROUTE_TIE):
        self.params, self.config = params, config
        self.route_tie = float(route_tie)
        self.tie_counts = {"evaluated_twice": 0, "took_other": 0}
        self.n_layers = int(config["num_hidden_layers"])
        self.n_dense = int(config["first_k_dense_replace"])
        self.latent = set(int(i) for i in config["full_attention_layers"])
        self.n_moe = self.n_layers - self.n_dense
        self._mixer = jax.jit(
            lambda x, pos_a, p, g1, g2, t, latent: mixer_layer(
                x, pos_a, p, g1, g2, config, t, latent),
            donate_argnums=(0,), static_argnames=("t", "latent"))
        self._dense = jax.jit(
            lambda x, p, g3, g4: dense_layer(x, p, g3, g4, config),
            donate_argnums=(0,))
        self._experts = jax.jit(
            lambda x, flip, r, e, s, g3, g4, tie: routed_layer(
                x, flip, r, e, s, g3, g4, config, tie),
            donate_argnums=(0,))
        self._head = jax.jit(
            lambda x, g, k: head_block(x, g, k, config))

    def _p(self, prefix):
        return self.params[find(self.params, prefix)]

    def logits(self, padded_ids):
        ids = np.asarray(padded_ids, np.int32)
        with jax.default_matmul_precision("highest"):
            out, alt, pos_a, tied_at = self._rows(ids)
        if len(pos_a):
            self._take_nearer(out, alt, ids, pos_a, tied_at)
        return out

    def _rows(self, ids, flip_in_sequence=None):
        """(the sequence's logits, the alternatives' logits, their
        positions, which of them tied). ``flip_in_sequence`` = (position,
        expert layer): that one choice flipped IN the sequence (the tests'
        brute force: by causality that position's row is what an
        alternative must give)."""
        t = len(ids)
        n_moe = self.n_moe if self.route_tie > 0 else 0
        # the positions that stand for the checked ones: the last
        # TIE_WINDOW before the zero padding, an alternative a (position,
        # expert layer) pair
        live = int(np.flatnonzero(ids)[-1]) + 1 if ids.any() else 0
        window = np.arange(live - TIE_WINDOW, live)
        usable = np.repeat(window >= 0, n_moe)
        pos_a = np.repeat(np.clip(window, 0, None), n_moe).astype(np.int32)
        layer_a = np.tile(np.arange(n_moe), TIE_WINDOW)
        a = len(pos_a)
        rows = np.concatenate([ids, ids[pos_a]])
        x = f32(self._p("embed")["weight"][jnp.asarray(rows)])
        tied_at = np.zeros(a, bool)
        for i in range(self.n_layers):
            latent = i in self.latent
            x = self._mixer(
                x, jnp.asarray(pos_a),
                self._p(f"l{i}_mla" if latent else f"l{i}_gdn"),
                self._p(f"l{i}_norm1")["scale"],
                self._p(f"l{i}_norm2")["scale"], t=t, latent=latent)
            g3 = self._p(f"l{i}_norm3")["scale"]
            g4 = self._p(f"l{i}_norm4")["scale"]
            if i < self.n_dense:
                x = self._dense(x, self._p(f"l{i}_mlp"), g3, g4)
                continue
            flip = np.zeros(t + a, bool)
            flip[t:] = usable & (layer_a == i - self.n_dense)
            if flip_in_sequence and flip_in_sequence[1] == i - self.n_dense:
                flip[flip_in_sequence[0]] = True
            x, tied = self._experts(
                x, jnp.asarray(flip), self._p(f"l{i}_moerouter"),
                self._p(f"l{i}_moeexperts"),
                self._p(f"l{i}_moeshared")
                if self.config.get("n_shared_experts", 0) else None,
                g3, g4, jnp.float32(self.route_tie))
            tied_at |= flip[t:] & np.asarray(tied)[t:]
        gain, kernel = self._p("norm_f")["scale"], self._p("lm_head")["kernel"]
        b = _divisor(t, LOGIT_BLOCK)
        out = np.empty((t, kernel.shape[1]), np.float32)
        for j in range(t // b):
            out[j * b:(j + 1) * b] = np.asarray(
                self._head(x[j * b:(j + 1) * b], gain, kernel))
        alt = np.asarray(self._head(x[t:], gain, kernel)) if a else None
        return out, alt, pos_a, tied_at

    def _take_nearer(self, out, alt, ids, pos_a, tied_at):
        """For each tied alternative whose position has a next id: the row
        under which that id lies nearer the best stays in ``out``."""
        live = int(np.flatnonzero(ids)[-1]) + 1 if ids.any() else 0
        twice, took = set(), 0
        for k in np.flatnonzero(tied_at):
            p = int(pos_a[k])
            if p + 1 >= live:   # the program's choice there is not known
                continue
            twice.add(p)
            chosen = int(ids[p + 1])
            if alt[k].max() - alt[k][chosen] < out[p].max() - out[p][chosen]:
                out[p] = alt[k]
                took += 1
        self.tie_counts["evaluated_twice"] += len(twice)
        self.tie_counts["took_other"] += took
        print(f"[bench] reference routing ties (ROUTE_TIE {self.route_tie:g}"
              f", last {TIE_WINDOW} positions): {len(twice)} positions "
              f"evaluated twice, {took} took the other row; so far "
              f"{self.tie_counts}", flush=True)
