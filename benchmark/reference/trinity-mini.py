"""Plain reference of the ``trinity-mini`` configuration: forward, loss and
gradients in float32 ``jax.numpy`` at matmul precision "highest". No kernel,
no mixed precision, no sharding, nothing of the program under test. A copy of
``tests/reference_trinity.py`` (``tests/test_trinity_reference_copy.py``
holds the two equal) made to fit beside a resident training state at 8192
positions: attention in blocks of query rows, the head and the loss in
blocks of rows, every layer, attention block and expert under
``jax.checkpoint`` (memory, not arithmetic: the numbers are those of the
plain formula).

    h0 = E[ids] * sqrt(hidden)                                   (mup)
    a = post_attn_norm(Attn_i(input_norm(h)));  h <- h + a
    m = post_mlp_norm(MLP_i(pre_mlp_norm(h)));  h <- h + m
    logits = final_norm(h) W_head
    loss = mean over positions of -log clip(softmax(logits), 1e-12, 1)[label]

``Attn``: q = x Wq (heads x d), k = x Wk, v = x Wv (kv heads x d), g = x Wg;
an RMS norm per head on q and on k; on ``sliding_attention`` layers rotary
positions (rotate-half) on q and k and key j visible to query i iff
i - window < j <= i, on ``full_attention`` layers no position signal and
j <= i; query head n reads K/V head n // group; o = softmax(q k^T / sqrt d) v
* sigmoid(g); o Wo. ``MLP``: W_down(silu(W_gate x) * W_up x) for the first
``num_dense_layers`` layers; after them Shared(x) + sum over the chosen
experts HELD HERE of w_e Expert_e(x), with s = sigmoid(x W_r),
sel = top-k(s + b), w = s[sel] / (sum s[sel] + 1e-20) * route_scale over ALL
``num_experts`` (a dense loop over the held experts; b selects only and takes
no gradient). ``experts_held`` = [first, count] names the held experts;
embedding and head are the vocabulary rows the parameters hold.

Parameters are the system's own tree (``{"l1_moeexperts_17": {"gate": (16,
2048, 1024), ...}, ...}``); node-number suffixes are ignored.

Which gradients are compared, and why not all (``checked_params``). Top-8 of
128 is discontinuous: the system's residual stream carries bf16 rounding, so
in a share of (token, layer) pairs it and this float32 reference choose
another 8th expert. A routed expert's weight gradient is a sum over the SET
of tokens routed to it, and the router's over the chosen scores: both change
by whole terms with each flipped pair, which no tolerance on rounding
covers, and the driver hands a reference only parameters, ids and labels,
never the system's choices. So the routed experts' three matrices and the
router's kernel are left out here and held where nothing can flip:
``tests/test_trinity.py`` (float32, every gradient), on the chip at these
widths ``chip_smoke.py``'s ``check_routed_layer`` (bf16 against float32 at
the PROGRAM'S routing, every gradient of the layer), and the controls of
``benchmark/tests/test_trinity_controls.py``. Measured on the v5e (PERF.md
§6, PR 35): the share of flipped pairs and the routed weights' error are
written there. Every group that IS compared receives its cotangent through
all four routed layers, and carries the flipped pairs' error with it (3 to
6% of the 8%): the comparison refuses the whole model in three mantissa bits
at 3.4 to 21 times the sound reading, the grouped products alone in three
mantissa bits at 1.4 to 1.5 times, whatever the weight.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128   # rows of scores at once: 32 heads x 128 x 8192 x 4 B
LOSS_BLOCK = 1024   # rows of logits at once: 1024 x 25,024 x 4 B


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


def _blocks(n: int, block: int) -> int:
    return block if n % block == 0 and n > block else n


def attention(x, p, sliding: bool, config):
    eps = float(config["rms_norm_eps"])
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    gate = jnp.einsum("bsd,dhk->bhsk", x, p["wg"])
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if sliding:  # full-attention layers carry no position signal
        q, k = rope(q, float(config["rope_theta"])), \
            rope(k, float(config["rope_theta"]))
    b, heads, s, d = q.shape
    kv = k.shape[1]  # query head n reads K/V head n // (heads // kv)
    q = q.reshape(b, kv, heads // kv, s, d)
    blk = _blocks(s, QUERY_BLOCK)
    window = int(config["sliding_window"])
    j = jnp.arange(s)[None, :]

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=3)
        i = start + jnp.arange(blk)[:, None]
        visible = j <= i
        if sliding:
            visible &= i - j < window
        scores = jnp.einsum("bngqd,bnkd->bngqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))     # (nb, b, kv, g, blk, d)
    o = jnp.moveaxis(o, 0, 3).reshape(b, heads, s, d) * jax.nn.sigmoid(gate)
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
    """The held experts' share of sum_e w_e Expert_e(x): a dense loop over
    the held experts, every token through each."""
    weights, chosen = routing(x, router, config)
    first, count = held

    @jax.checkpoint
    def share(n, gate, up, down):
        w_e = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), axis=-1)
        return w_e[..., None] * gated_mlp(x, gate, up, down)

    y, _ = jax.lax.scan(
        lambda y, expert: (y + share(*expert), None), jnp.zeros_like(x),
        (jnp.arange(count), experts["gate"], experts["up"], experts["down"]))
    return y


def layer_params(params, i, config):
    parts = ["norm1", "attn", "norm2", "norm3", "norm4"]
    if i < int(config["num_dense_layers"]):
        parts.append("mlp")
    else:
        parts += ["moerouter", "moeexperts"]
        if int(config["num_shared_experts"]):
            parts.append("moeshared")
    return {part: params[find(params, f"l{i}_{part}")] for part in parts}


def layer(h, p, i, config):
    eps = float(config["rms_norm_eps"])
    sliding = config["layer_types"][i] == "sliding_attention"
    a = attention(rms_norm(h, p["norm1"]["scale"], eps), p["attn"], sliding,
                  config)
    h = h + rms_norm(a, p["norm2"]["scale"], eps)
    x = rms_norm(h, p["norm3"]["scale"], eps)
    if "mlp" in p:
        m = gated_mlp(x, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    else:
        m = routed_experts(x, p["moerouter"], p["moeexperts"], config,
                           tuple(config["experts_held"]))
        if "moeshared" in p:  # added once, on every share
            m = m + gated_mlp(x, p["moeshared"]["gate"], p["moeshared"]["up"],
                              p["moeshared"]["down"])
    return h + rms_norm(m, p["norm4"]["scale"], eps)


def hidden(params, ids, config):
    """The last layer's residual stream, before the final norm."""
    h = params[find(params, "embed")]["weight"][ids]
    if config["mup_enabled"]:
        h = h * jnp.sqrt(jnp.float32(int(config["hidden_size"])))
    for i in range(len(config["layer_types"])):
        h = jax.checkpoint(
            lambda h, p, i=i: layer(h, p, i, config))(
                h, layer_params(params, i, config))
    return h


def logits(params, ids, config):
    """The forward: (b, s, held vocabulary rows)."""
    h = rms_norm(hidden(params, ids, config),
                 params[find(params, "norm_f")]["scale"],
                 float(config["rms_norm_eps"]))
    return h @ params[find(params, "lm_head")]["kernel"]


def loss(params, ids, labels, config):
    h = rms_norm(hidden(params, ids, config),
                 params[find(params, "norm_f")]["scale"],
                 float(config["rms_norm_eps"]))
    head = params[find(params, "lm_head")]["kernel"]
    rows, targets = h.reshape(-1, h.shape[-1]), labels.reshape(-1)
    blk = _blocks(rows.shape[0], LOSS_BLOCK)

    @jax.checkpoint
    def block_nll(start):
        r = jax.lax.dynamic_slice_in_dim(rows, start, blk, axis=0)
        t = jax.lax.dynamic_slice_in_dim(targets, start, blk, axis=0)
        logp = jnp.log(jnp.clip(jax.nn.softmax(r @ head, axis=-1), 1e-12,
                                1.0))
        return -jnp.sum(jnp.take_along_axis(logp, t[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block_nll, jnp.arange(0, rows.shape[0], blk))
                   ) / rows.shape[0]


# --------------------------------------------- what the train driver calls
def checked_params(params, config):
    """The parameter groups whose gradients are compared (every weight in
    them): the embedding rows, the dense layer's attention (q, k, v, o, gate,
    both head norms) and MLP, the first expert layer's attention, the
    full-attention layer's attention, the last layer's shared expert, the
    final norm and the head; and of EVERY expert layer the two gains next to
    the routed part — ``norm3``, whose cotangent is the sum of what the
    router, the grouped products and the shared expert hand back to their
    common input, and ``norm4``, which multiplies the routed + shared output
    itself. Not the routed experts' matrices nor the router's: see the
    module's docstring."""
    layers = len(config["layer_types"])
    dense = int(config["num_dense_layers"])
    full = [i for i, kind in enumerate(config["layer_types"])
            if kind == "full_attention"]
    names = ["embed", "l0_attn", "l0_mlp" if dense else "l0_moeshared",
             f"l{dense}_attn", f"l{full[-1] if full else layers - 1}_attn",
             f"l{layers - 1}_moeshared", "norm_f", "lm_head"]
    names += [f"l{i}_norm{n}" for i in range(dense, layers) for n in (3, 4)]
    return list(dict.fromkeys(find(params, n) for n in names))


def loss_and_grads(params, x, y, config, wanted):
    """(loss, {name: {weight: gradient}}) for the parameter groups named in
    ``wanted`` (names of the system's tree); the gradient is taken with
    respect to those groups alone."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    rest = {k: v for k, v in params.items() if k not in wanted}

    def f(chosen, rest, ids, labels):
        # ``rest`` is an argument, not a closure: 2.5 GB of closed-over
        # arrays become constants of the program, and its compile then
        # takes the host's memory
        return loss({**rest, **chosen}, ids, labels, config)

    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(f))(
            {k: params[k] for k in wanted}, rest, jnp.asarray(x, jnp.int32),
            jnp.asarray(y, jnp.int32))
    return value, grads


# ------------------------------------------------ the yardstick's closed forms
def param_count(config) -> int:
    """Parameters held here (the held experts and vocabulary rows alone). A
    copy of ``models/trinity.trinity_param_count``."""
    h, d = int(config["hidden_size"]), int(config["head_dim"])
    heads, kv = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    layers, dense = len(config["layer_types"]), int(config["num_dense_layers"])
    expert = 3 * h * int(config["moe_intermediate_size"])
    attn = 3 * h * heads * d + 2 * h * kv * d + 2 * d
    moe = (int(config["num_shared_experts"]) + int(config["experts_held"][1])
           ) * expert + (h + 1) * int(config["num_experts"])
    return layers * (attn + 4 * h) + dense * 3 * h * int(
        config["intermediate_size"]) + (layers - dense) * moe \
        + 2 * int(config["vocab_size"]) * h + h


def attention_pairs(config, seq: int):
    """Per layer the unmasked (query, key) pairs of one sequence."""
    w = int(config["sliding_window"])
    full = seq * (seq + 1) // 2
    band = full if w >= seq else w * (w + 1) // 2 + (seq - w) * w
    return [band if kind == "sliding_attention" else full
            for kind in config["layer_types"]]


def train_flops_per_token(config, seq: int) -> float:
    """Forward + backward (3x forward) model FLOPs of one trained token on
    this chip: 6 x the matrix parameters a token meets here — attention's
    five projections, the dense MLP, the shared expert, the router, in
    expectation k * held / num_experts routed experts, the held head — plus
    12 * heads * head_dim per unmasked (query, key) pair; recomputation is
    not counted. A copy of ``models/trinity.trinity_train_flops_per_token``
    (``benchmark/tests/test_flops_trinity.py`` holds the two equal)."""
    h, d = int(config["hidden_size"]), int(config["head_dim"])
    heads, kv = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    layers, dense = len(config["layer_types"]), int(config["num_dense_layers"])
    expert = 3 * h * int(config["moe_intermediate_size"])
    routed = int(config["num_experts_per_tok"]) * int(
        config["experts_held"][1]) / int(config["num_experts"])
    moe = (int(config["num_shared_experts"]) + routed) * expert \
        + h * int(config["num_experts"])
    matmul = layers * (3 * h * heads * d + 2 * h * kv * d) \
        + dense * 3 * h * int(config["intermediate_size"]) \
        + (layers - dense) * moe + h * int(config["vocab_size"])
    return 6.0 * matmul + 12.0 * heads * d * sum(
        attention_pairs(config, seq)) / seq


def attention_calls(config, batch: int, seq: int):
    """The attention kernels' calls in one step on one chip, as
    ``(count, b, h, sq, sk, d, causal)``, counting exactly the unmasked
    (query, key) pairs: a full-attention layer is one causal call over
    (seq, seq) — the closed forms halve it: seq^2 / 2 against the exact
    seq (seq + 1) / 2, 0.01% under at 8192 — and a windowed layer's band is
    expressed as the key length ``pairs / seq`` of a call with no mask. So no
    roofline share built on these can count a masked pair as work done."""
    heads, d = int(config["num_attention_heads"]), int(config["head_dim"])
    calls = []
    for kind, pairs in zip(config["layer_types"],
                           attention_pairs(config, seq)):
        if kind == "sliding_attention" and int(config["sliding_window"]) < seq:
            calls.append((1, batch, heads, seq, pairs / seq, d, False))
        else:
            calls.append((1, batch, heads, seq, seq, d, True))
    return calls
