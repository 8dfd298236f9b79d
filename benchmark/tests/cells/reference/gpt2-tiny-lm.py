"""What a language-model training configuration brings beside its sizes: the
plain loss and gradients (mean token-level negative log-likelihood over the
forward of ``reference/gpt2-xl.py``), the parameter groups they are compared
on, the FLOPs of a trained token and the attention calls of a step. At the
rehearsal's size the gradients of the whole tree are taken at once."""
import importlib.util
import os

import jax
import jax.numpy as jnp

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_reference_gpt2", os.path.join(
        _HERE, "..", "..", "..", "reference", "gpt2-xl.py"))
gpt2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gpt2)


def _loss(params, config, x, y):
    ref = gpt2.Reference(params, config)

    def one(ids, labels):
        logp = jax.nn.log_softmax(ref.logits(ids), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    return jnp.mean(jnp.stack([one(x[i], y[i]) for i in range(x.shape[0])]))


def checked_params(params, config):
    last = int(config["n_layer"]) - 1
    return [gpt2._find(params, name)
            for name in ("h0_attn", "h0_fc1", f"h{last}_fc2")]


def loss_and_grads(params, x, y, config, wanted):
    loss, grads = jax.value_and_grad(_loss)(
        params, config, jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32))
    return loss, {name: grads[name] for name in wanted}


def train_flops_per_token(config, seq: int) -> float:
    h, i, layers = (int(config[k]) for k in ("n_embd", "n_inner", "n_layer"))
    matmul = layers * (4 * h * h + 2 * h * i) + h * int(config["vocab_size"])
    return 6.0 * matmul + 6.0 * layers * seq * h      # causal: half of 12LSH


def attention_calls(config, batch: int, seq: int):
    return [(int(config["n_layer"]), batch, int(config["n_head"]), seq, seq,
             int(config["head_dim"]), True)]
