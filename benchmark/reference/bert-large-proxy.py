"""Plain reference of the BERT proxy: forward, loss and gradients in float32
``jax.numpy`` at matmul precision "highest". No kernel, no mixed precision,
no sharding. Attention is computed in blocks of query rows so that s4096
fits; layers run under ``lax.scan`` with the block recomputed in the
backward pass (memory, not arithmetic: the numbers are those of the plain
formula).

The block, as BERT publishes it (post-LN):

    a = MHA(x);  x1 = LN(a + x);  f = W2 gelu(W1 x1 + b1) + b2;  y = LN(f + x1)

then mean-pool over positions, a ``num_classes`` head, softmax, and the mean
negative log-likelihood of the labels. Departures of the repo's builder from
the published model, followed here because they define what is run: float
activations as input (no embeddings), layer-norm eps 1e-5, tanh-GELU,
no bias on q/k/v, probabilities clipped to [1e-12, 1] before the log.

Parameters are the system's own tree (``{"l0_attn_0": {"wq": (H, n, d), ...},
"l0_fc1_3": {"kernel", "bias"}, "l0_ln1_2": {"scale", "bias"}, ...,
"cls_<k>": {...}}``); node-number suffixes are ignored.

Everything the training driver has to know about this configuration and no
other is here too, as functions of the configuration's file (``config``, the
JSON object): which parameter groups the gradients are compared on
(``checked_params``), the model FLOPs of a trained token
(``train_flops_per_token``) and the attention kernel's calls in one step
(``attention_calls``). A new configuration brings its own file with the
same five functions; the driver names none of them.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
QUERY_BLOCK = 512


def _find(params, prefix):
    keys = [k for k in params if re.fullmatch(re.escape(prefix) + r"(_\d+)?", k)]
    if len(keys) != 1:
        raise KeyError(f"{prefix}: {keys}")
    return keys[0]


def layer_keys(params, i):
    return {part: _find(params, f"l{i}_{part}")
            for part in ("attn", "ln1", "fc1", "fc2", "ln2")}


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def attention(x, p, causal=False):
    """(b, s, H) -> (b, s, H); scores in blocks of QUERY_BLOCK query rows."""
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    b, h, s, d = q.shape
    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 and s > QUERY_BLOCK else s
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    kpos = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=2)
        sc = jnp.einsum("bhqk,bhsk->bhqs", qb, k) * scale
        if causal:
            qpos = start + jnp.arange(blk)
            sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqs,bhsk->bhqk", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(0, s, blk))       # (nb, b, h, blk, d)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, d)
    y = jnp.einsum("bhsv,hvd->bsd", out, p["wo"])
    return y + p["bo"] if "bo" in p else y


def dense(x, p, act=False):
    y = x @ p["kernel"] + p["bias"]
    return jax.nn.gelu(y, approximate=True) if act else y


def block(x, lp):
    x1 = layer_norm(attention(x, lp["attn"]) + x, lp["ln1"])
    f = dense(dense(x1, lp["fc1"], act=True), lp["fc2"])
    return layer_norm(f + x1, lp["ln2"])


def _stack(params, num_layers):
    per = [{part: params[k] for part, k in layer_keys(params, i).items()}
           for i in range(num_layers)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)


def forward(stacked, head, x):
    def body(h, lp):
        return jax.checkpoint(block)(h, lp), None

    h, _ = jax.lax.scan(body, x, stacked)
    logits = jnp.mean(h, axis=1) @ head["kernel"] + head["bias"]
    return jax.nn.softmax(logits, axis=-1)


def loss_fn(stacked, head, x, y):
    probs = forward(stacked, head, x)
    logp = jnp.log(jnp.clip(probs, 1e-12, 1.0))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def checked_params(params, config):
    """The parameter groups whose gradients are compared: the first layer's
    attention and first dense layer (every later layer's cotangent has
    passed through them) and the last layer's second dense layer."""
    first, last = layer_keys(params, 0), layer_keys(
        params, int(config["num_hidden_layers"]) - 1)
    return [first["attn"], first["fc1"], last["fc2"]]


def loss_and_grads(params, x, y, config, wanted):
    """(loss, {name: {weight: gradient}}) for the parameter groups named in
    ``wanted`` (names of the system's tree)."""
    with jax.default_matmul_precision("highest"):
        stacked = _stack(params, int(config["num_hidden_layers"]))
        head = params[_find(params, "cls")]
        loss, (g_stack, _) = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1))
                                     )(stacked, head, jnp.asarray(x, jnp.float32),
                                       jnp.asarray(y, jnp.int32))
    out = {}
    for name in wanted:
        m = re.fullmatch(r"l(\d+)_([a-z0-9]+?)(_\d+)?", name)
        i, part = int(m.group(1)), m.group(2)
        out[name] = jax.tree_util.tree_map(lambda g: g[i], g_stack[part])
    return loss, out


# ------------------------------------------------ the yardstick's closed forms
def param_count(config) -> int:
    """Per layer q/k/v/o (no q/k/v bias, one output bias), two dense layers
    with biases, two layer norms; a ``num_classes`` head. A copy of
    ``models/bert.bert_param_count``."""
    h, i = int(config["hidden_size"]), int(config["intermediate_size"])
    c = int(config["num_classes"])
    per_layer = 4 * h * h + h + 2 * h * i + i + h + 4 * h
    return int(config["num_hidden_layers"]) * per_layer + h * c + c


def train_flops_per_token(config, seq: int) -> float:
    """Forward + backward (3x forward) model FLOPs of one trained token:
    6 * P for the matmuls plus 12 * L * S * H for attention's scores and
    values; recomputation is not counted. A copy of
    ``models/bert.bert_train_flops_per_step`` over batch * seq
    (``tests/test_flops.py`` holds the two equal today)."""
    attn = 12 * int(config["num_hidden_layers"]) * seq \
        * int(config["hidden_size"])
    return 6.0 * param_count(config) + attn


def attention_calls(config, batch: int, seq: int):
    """The attention kernel's calls in one step on one chip, as
    ``(count, b, h, sq, sk, d, causal)``: one per layer, forward and
    backward each."""
    return [(int(config["num_hidden_layers"]), batch,
             int(config["num_attention_heads"]), seq, seq,
             int(config["head_dim"]), False)]
