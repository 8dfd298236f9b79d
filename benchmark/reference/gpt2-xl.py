"""Plain reference of GPT-2 (Radford et al. 2019): the full forward pass over
a whole sequence in float32 ``jax.numpy`` at matmul precision "highest". No
kernel, no KV cache, no batching, no buckets.

    x = wte[ids] + wpe[pos]
    per block:  x = x + MHA_causal(LN(x));  x = x + W2 gelu_new(W1 LN(x) + b1) + b2
    logits = LN_f(x) @ head

Departures of the repo's builder from the published model, followed here
because they define what is run: the head is its own matrix (not ``wte``
transposed), q/k/v carry no bias. Layer-norm eps 1e-5 and tanh-GELU are as
published.

Parameters are the system's own tree (``wte_<k>``/``wpe_<k>``: {"weight"} or
{"embedding"}; ``h<i>_ln1``, ``h<i>_attn``, ``h<i>_ln2``, ``h<i>_fc1``,
``h<i>_fc2``; ``ln_f``; ``lm_head``); node-number suffixes are ignored.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _find(params, prefix):
    keys = [k for k in params if re.fullmatch(re.escape(prefix) + r"(_\d+)?", k)]
    if len(keys) != 1:
        raise KeyError(f"{prefix}: {keys}")
    return keys[0]


def _table(p):
    (leaf,) = jax.tree_util.tree_leaves(p)
    return leaf


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def causal_attention(x, p):
    q = jnp.einsum("sd,dhk->hsk", x, p["wq"])
    k = jnp.einsum("sd,dhk->hsk", x, p["wk"])
    v = jnp.einsum("sd,dhk->hsk", x, p["wv"])
    s, d = q.shape[1], q.shape[2]
    sc = jnp.einsum("hqk,hsk->hqs", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(mask[None], sc, -jnp.inf)
    out = jnp.einsum("hqs,hsk->hqk", jax.nn.softmax(sc, axis=-1), v)
    y = jnp.einsum("hsv,hvd->sd", out, p["wo"])
    return y + p["bo"] if "bo" in p else y


def block(x, lp):
    x = x + causal_attention(layer_norm(x, lp["ln1"]), lp["attn"])
    h = layer_norm(x, lp["ln2"])
    h = jax.nn.gelu(h @ lp["fc1"]["kernel"] + lp["fc1"]["bias"],
                    approximate=True)
    return x + h @ lp["fc2"]["kernel"] + lp["fc2"]["bias"]


def _embed(wte, wpe, ids):
    return wte[ids] + wpe[jnp.arange(ids.shape[0])]


def _head(x, ln_f, head):
    return layer_norm(x, ln_f) @ head


class Reference:
    """The full forward over one sequence of token ids, layer by layer on the
    system's own float32 master weights (no stacked copy: 6.5 GB of them sit
    beside a full KV pool on the chip)."""

    def __init__(self, params, config: dict):
        self.layers = [{part: params[_find(params, f"h{i}_{part}")]
                        for part in ("ln1", "attn", "ln2", "fc1", "fc2")}
                       for i in range(int(config["n_layer"]))]
        self.wte = _table(params[_find(params, "wte")])
        self.wpe = _table(params[_find(params, "wpe")])
        self.ln_f = params[_find(params, "ln_f")]
        self.head = params[_find(params, "lm_head")]["kernel"]
        self._embed, self._block, self._head = (jax.jit(_embed),
                                                jax.jit(block), jax.jit(_head))

    def logits(self, ids):
        """(len(ids), vocab) float32 logits of the whole sequence."""
        with jax.default_matmul_precision("highest"):
            x = self._embed(self.wte, self.wpe, jnp.asarray(ids, jnp.int32))
            for lp in self.layers:
                x = self._block(x, lp)
            return self._head(x, self.ln_f, self.head)
