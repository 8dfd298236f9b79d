"""Plain reference of the latent-attention decoder with sandwich norms and a
routed top-k expert layer (``flexflow_tpu/models/pangu.py``;
openPangu-Ultra-MoE-718B): ``tests/reference_pangu.py`` made to fit beside a
resident serving engine at 13k positions. Float32 ``jax.numpy`` at matmul
precision "highest", materialised attention only (no absorption, no cache),
nothing of ``flexflow_tpu``. The equations, the meaning of ``config`` and
``params`` and the departures from the published description are that
file's; a tier-1 test holds the two equal.

What is blocked, and only that: the bf16-valued weights are upcast a layer's
matrix (an expert, a head group, a column block of the dense MLP) at a time;
attention runs a head group at a time and inside it in blocks of query rows;
the held experts are a ``lax.scan``; the head is applied to blocks of rows
and each block's logits leave the device before the next is made (the result
is a host array). Weights are passed to the jitted pieces as arguments,
never closed over.

**Routing ties.** A routed model's top-k is discontinuous: where the 8th and
9th scores of a token all but tie, a program computing in bf16 may take the
other expert, and where that expert is held here the token's logits move by
tenths — no rounding error, another forward pass. The principle this file
implements: *the program is held to the reference at every routing the
reference itself cannot tell apart.* At a checked position where, in some
expert layer, the reference's 8th and 9th scores lie within ``ROUTE_TIE`` of
each other and one of the two experts is held here, the reference also
evaluates that position with the other choice — that token's own forward from
that layer on, the context's rows unchanged — and returns for the position
the row under which the token the program chose (the next id of
``padded_ids``) lies nearer the best. Nothing is skipped and no limit is
loosened: every returned row is a full forward pass of the model under a
routing the scores do not separate, and the driver's comparison is the one
it makes for every cell. The checked positions are not handed over, so the
last ``TIE_WINDOW`` positions before the zero padding stand for them (the
driver checks the ``CHECK_TOKENS`` = 16 generated ones); the last of them has
no next id in ``padded_ids`` and keeps the reference's own routing. One
choice is flipped at a time: a position that ties in two layers gets two
alternatives, not four. Each call prints how many positions were evaluated
twice and how many took the other row; ``Reference.tie_counts`` sums them.

``ROUTE_TIE`` is absolute, on the sigmoid scores. Its reason: PR 35 measured
on the chip that 3.9-8.3% of tokens a layer choose another 8 between the bf16
program and this reference at a median 8th-9th gap of 0.0058 (PERF.md section
6): with gaps near-exponential that is a typical score error of 4e-4 to 7e-4,
growing with depth. 2e-3 is three to four of those — the scores the bf16
program cannot tell apart — and is set to 0 to switch the handling off.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

ROUTE_TIE = 2e-3
TIE_WINDOW = 16
HEAD_GROUP = 16      # heads a step of the attention scan
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


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * f32(gain)


def rope_at(x, pos, theta):
    """x (n, ..., d) at positions ``pos`` (n,): dim i pairs with i + d/2."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(b for b in range(1, min(n, cap) + 1) if n % b == 0)


def _cols(w, start, size):
    return f32(jax.lax.dynamic_slice_in_dim(w, start, size, axis=1))


def attention(x, pos_a, p, gain1, gain2, config, t: int):
    """One layer's ``x + norm2(MLA(norm1(x)))`` over rows ``x (t + a, d)``:
    the first ``t`` are the sequence, position = row; the last ``a`` are
    alternatives of single positions ``pos_a``, each seeing the sequence's
    rows BEFORE its position and itself."""
    heads = config["num_attention_heads"]
    nope, rdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    sandwich = config.get("sandwich_norm", True)
    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    b = _divisor(t, QUERY_BLOCK)
    pos = jnp.concatenate([jnp.arange(t, dtype=jnp.int32), pos_a])
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rdim))
    h = rms_norm(x, gain1, eps)
    c_q = rms_norm(h @ f32(p["wq_a"]), p["q_norm"], eps)
    kv = h @ f32(p["wkv_a"])
    c_kv = rms_norm(kv[:, :rank], p["kv_norm"], eps)
    k_r = rope_at(kv[:, rank:], pos, theta)
    kpos = jnp.arange(t)

    def group(i, y):
        q = (c_q @ _cols(p["wq_b"], i * g * (nope + rdim),
                         g * (nope + rdim))).reshape(-1, g, nope + rdim)
        q_n, q_r = q[..., :nope], rope_at(q[..., nope:], pos, theta)
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
        # the alternatives: the sequence's rows before, and itself
        s = (jnp.einsum("agd,tgd->gat", q_n[t:], k_n[:t])
             + jnp.einsum("agr,tr->gat", q_r[t:], k_r[:t])) * scale
        own = (jnp.einsum("agd,agd->ga", q_n[t:], k_n[t:])
               + jnp.einsum("agr,ar->ga", q_r[t:], k_r[t:])) * scale
        s = jnp.where((kpos[None, :] < pos_a[:, None])[None], s, -jnp.inf)
        prob = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), -1)
        o_a = jnp.einsum("gat,tgd->agd", prob[..., :t], v[:t]) \
            + jnp.swapaxes(prob[..., t], 0, 1)[..., None] * v[t:]
        o = jnp.concatenate([o, o_a]).reshape(-1, g * vd)
        w_o = f32(jax.lax.dynamic_slice_in_dim(p["wo"], i * g * vd, g * vd))
        return y + o @ w_o

    y = jax.lax.fori_loop(0, heads // g, group, jnp.zeros_like(x))
    return x + (rms_norm(y, gain2, eps) if sandwich else y)


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)


def dense_mlp(x, p, gain3, gain4, config):
    eps = config["rms_norm_eps"]
    width = p["gate"].shape[1]
    c = _divisor(width, MLP_BLOCK)
    h = rms_norm(x, gain3, eps)

    def block(i, m):
        down = jax.lax.dynamic_slice_in_dim(p["down"], i * c, c, axis=0)
        return m + gated(h, _cols(p["gate"], i * c, c),
                         _cols(p["up"], i * c, c), down)

    m = jax.lax.fori_loop(0, width // c, block, jnp.zeros_like(x))
    return x + (rms_norm(m, gain4, eps)
                if config.get("sandwich_norm", True) else m)


def expert_layer(x, flip, router, experts, shared, gain3, gain4, config,
                 route_tie):
    """``x + norm4(shared + held routed experts)`` and, per row, whether
    its 8th and 9th scores tie with one of the two held (``tied``). Rows
    with ``flip`` set AND tied take the 9th for the 8th."""
    eps, k = config["rms_norm_eps"], config["num_experts_per_tok"]
    first, count = tuple(config.get("experts_held")
                         or (0, config["n_routed_experts"]))
    h = rms_norm(x, gain3, eps)
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
        return m + w[:, None] * gated(h, gate, up, down), None

    m, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (jnp.arange(count), experts["gate"], experts["up"],
                         experts["down"]))
    if shared is not None:
        m = m + gated(h, shared["gate"], shared["up"], shared["down"])
    return x + (rms_norm(m, gain4, eps)
                if config.get("sandwich_norm", True) else m), tied


def head_block(x, gain, kernel, config):
    return rms_norm(x, gain, config["rms_norm_eps"]) @ f32(kernel)


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
        self.n_moe = self.n_layers - self.n_dense
        static = dict(static_argnames=("t",))
        self._attention = jax.jit(
            lambda x, pos_a, p, g1, g2, t: attention(x, pos_a, p, g1, g2,
                                                     config, t),
            donate_argnums=(0,), **static)
        self._dense = jax.jit(
            lambda x, p, g3, g4: dense_mlp(x, p, g3, g4, config),
            donate_argnums=(0,))
        self._experts = jax.jit(
            lambda x, flip, r, e, s, g3, g4, tie: expert_layer(
                x, flip, r, e, s, g3, g4, config, tie),
            donate_argnums=(0,))
        self._head = jax.jit(
            lambda x, g, k: head_block(x, g, k, config))

    def _p(self, prefix):
        return self.params[find(self.params, prefix)]

    def logits(self, padded_ids):
        with jax.default_matmul_precision("highest"):
            return self._logits(np.asarray(padded_ids, np.int32))

    def _logits(self, ids):
        t = len(ids)
        n_moe = self.n_moe
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
            x = self._attention(
                x, jnp.asarray(pos_a), self._p(f"l{i}_mla"),
                self._p(f"l{i}_norm1")["scale"],
                self._p(f"l{i}_norm2")["scale"]
                if self.config.get("sandwich_norm", True) else None, t=t)
            g3 = self._p(f"l{i}_norm3")["scale"]
            g4 = self._p(f"l{i}_norm4")["scale"] \
                if self.config.get("sandwich_norm", True) else None
            if i < self.n_dense:
                x = self._dense(x, self._p(f"l{i}_mlp"), g3, g4)
                continue
            flip = np.zeros(t + a, bool)
            flip[t:] = usable & (layer_a == i - self.n_dense)
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
        alt = np.asarray(self._head(x[t:], gain, kernel))
        del x
        self._take_nearer(out, alt, ids, pos_a, tied_at, live)
        return out

    def _take_nearer(self, out, alt, ids, pos_a, tied_at, live):
        """For each tied alternative whose position has a next id: the row
        under which that id lies nearer the best stays in ``out``."""
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
