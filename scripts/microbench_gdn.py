"""Microbench: the gated delta rule alone at the ``olmo-hybrid-7b-assist``
cell's shapes (30 heads, d_k 96, d_v 192) or, named as argument, the
``gigachat35-reasoning-2k`` cell's (64 value heads of 128 x 128, 128 slots,
4 layers: a head a row of the state at rest, so ``packed`` and ``padded`` are
one shape there), on the host's clock.

* the decode step's one-token update over ``SLOTS`` slots, 12 layers' worth
  inside one jit with the states donated, in three forms: the
  ``gated_delta_update`` kernel on the state as it rests, two heads a row
  (``packed``: ``(slots, H / 2, d_k, 2 d_v)``, whole lane tiles); the same
  kernel handed a head a row (``padded``: ``(slots, H, d_k, d_v)``, the shape
  PR 46 rested, whose 192 lanes the chip pads to 256); and the fused XLA
  expression (``kernels.gated_delta_rule.one_token_update``, the path off the
  chip) on the packed state. Each prints the bytes its states rest in on the
  chip (``kvcache.tiled_bytes``) and the GB/s it moved, counted on the
  matrices' own bytes (``logical``) and on the bytes at rest;
* the prefill kernel (``gated_delta_rule``, the chunked form) over one
  sequence of 256 / 512 / 1,024 rows against the ``lax.scan`` form it
  replaces.

Run manually on the chip; not part of the test suite:

    chiprun --chips 1 -- python scripts/microbench_gdn.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from microbench_ssm import timed  # scripts/ is sys.path[0]

#: cell: slots, (value) heads, d_k, d_v, delta-rule layers, prompt rows; a
#: cell's name as argument runs that shape (the first by default). The
#: kernels see value heads alone: grouped key heads are repeated before them
SHAPES = {
    "olmo-hybrid-7b-assist": (64, 30, 96, 192, 12, (256, 512, 1024)),
    "gigachat35-reasoning-2k": (128, 64, 128, 128, 4, (512, 1024, 2048)),
}
SLOTS, HEADS, DK, DV, LAYERS, PROMPTS = SHAPES[
    sys.argv[1] if sys.argv[1:] else "olmo-hybrid-7b-assist"]


def rule_inputs(key, rows, length):
    keys = jax.random.split(key, 5)
    f32 = jnp.float32
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (rows, length, HEADS, DK), f32)) \
        * DK ** -0.5
    k = unit(jax.random.normal(keys[1], (rows, length, HEADS, DK), f32))
    v = jax.random.normal(keys[2], (rows, length, HEADS, DV), f32)
    g = -jnp.abs(jax.random.normal(keys[3], (rows, length, HEADS), f32)) * 0.1
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(keys[4], (rows, length, HEADS), f32))
    return q, k, v, g, beta


def main() -> None:
    from flexflow_tpu.kernels.gated_delta_rule import (
        gated_delta_rule, gated_delta_rule_reference, gated_delta_update,
        one_token_update, pack_state)
    from flexflow_tpu.serving.kvcache import tiled_bytes

    if jax.devices()[0].platform != "tpu":
        sys.exit("microbench_gdn: no TPU; a time from another backend says "
                 "nothing about the chip")
    f32 = jnp.float32
    padded = jax.ShapeDtypeStruct((SLOTS, HEADS, DK, DV), f32)
    packed = jax.eval_shape(pack_state, padded)
    q, k, v, g, beta = (t[:, 0] for t in rule_inputs(
        jax.random.PRNGKey(0), SLOTS, 1))

    def stack(update):
        def run(states, q, k, v, g, beta):
            vs, out = v, []
            for s in states:   # each layer's input depends on the one before
                o, s = update(s, q, k, vs, g, beta)
                vs = v + 1e-3 * o
                out.append(s)
            return out, vs

        return run

    result = {"device": jax.devices()[0].device_kind, "slots": SLOTS,
              "layers": LAYERS, "state_packed": list(packed.shape)}
    logical = LAYERS * SLOTS * HEADS * DK * DV * 4
    for name, update, rest in (("kernel_packed", gated_delta_update, packed),
                               ("kernel_padded", gated_delta_update, padded),
                               ("fused_xla", one_token_update, packed)):
        states = [jnp.zeros(rest.shape, f32) for _ in range(LAYERS)]
        wall = timed(jax.jit(stack(update), donate_argnums=(0,)), states,
                     q, k, v, g, beta, donate_first=True)
        at_rest = LAYERS * tiled_bytes(rest.shape, 4)
        result[f"decode_update_{name}_ms"] = round(wall * 1e3, 3)
        result[f"decode_update_{name}_at_rest_gb"] = round(at_rest / 1e9, 4)
        result[f"decode_update_{name}_logical_gb_per_s"] = round(
            2 * logical / wall / 1e9, 1)
        result[f"decode_update_{name}_at_rest_gb_per_s"] = round(
            2 * at_rest / wall / 1e9, 1)

    for rows in PROMPTS:
        args = rule_inputs(jax.random.PRNGKey(rows), 1, rows)
        for name, fn in (("kernel", gated_delta_rule),
                         ("lax_scan", gated_delta_rule_reference)):
            wall = timed(jax.jit(fn), *args)
            result[f"prefill_{rows}_{name}_ms"] = round(wall * 1e3, 3)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
