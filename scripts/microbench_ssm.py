"""Microbench: the selective state-space recurrence alone at the
``jamba2-3b-reasoning`` cell's shapes (E 5,120, N 16), on the host's clock.

* the decode step's one-token update over ``SLOTS`` slots, 26 layers' worth
  inside one jit with the state donated, in both forms PR 44 timed: the fused
  XLA expression (``ops.ssm.one_token_update``, the form the op keeps) and the
  ``selective_scan`` kernel at one row a slot with the slot's state as its
  initial state (the form it dropped; the row rides a 64-token tile whose
  other rows have ``dt`` 0: the kernel's SMEM blocks rest in rows of 1,024
  words);
* the prefill kernel over one 2,048-row sequence against the ``lax.scan``
  form it replaces.

Run manually on the chip; not part of the test suite:

    chiprun --chips 1 -- python scripts/microbench_ssm.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

SLOTS, INNER, STATE, LAYERS, PROMPT = 192, 5120, 16, 26, 2048
REPEATS = 10


def timed(fn, *args, donate_first=False):
    """Median-of-REPEATS wall of ``fn(*args)``; with ``donate_first`` the
    first result replaces the first argument (a donated state)."""
    out = fn(*args)
    jax.block_until_ready(out)
    walls = []
    for _ in range(REPEATS):
        if donate_first:
            args = (out[0],) + args[1:]
        t = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        walls.append(time.perf_counter() - t)
    return sorted(walls)[len(walls) // 2]


def main() -> None:
    from flexflow_tpu.kernels.selective_scan import (
        selective_scan, selective_scan_reference)
    from flexflow_tpu.ops.ssm import one_token_update

    if jax.devices()[0].platform != "tpu":
        sys.exit("microbench_ssm: no TPU; a time from another backend says "
                 "nothing about the chip")
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    f32 = jnp.float32
    a = -jnp.exp(jax.random.normal(keys[0], (STATE, INNER), f32))
    states = [jnp.zeros((SLOTS, STATE, INNER), f32) for _ in range(LAYERS)]
    x = jax.random.normal(keys[1], (SLOTS, INNER), f32)
    dt = jnp.abs(jax.random.normal(keys[2], (SLOTS, INNER), f32)) * 0.05
    b = jax.random.normal(keys[3], (SLOTS, STATE), f32)
    c = jax.random.normal(keys[4], (SLOTS, STATE), f32)

    def fused(states, x, dt, b, c):
        ys, out = x, []
        for s in states:   # each layer's input depends on the one before
            y, s = one_token_update(s, ys, dt, b, c, a)
            ys = x + 1e-3 * y
            out.append(s)
        return out, ys

    def kernel(states, x, dt, b, c):
        ys, out = x, []
        for s in states:
            y, s = selective_scan(ys[:, None], dt[:, None], b[:, None],
                                  c[:, None], a, s0=s)
            ys = x + 1e-3 * y[:, 0]
            out.append(s)
        return out, ys

    result = {"device": jax.devices()[0].device_kind, "slots": SLOTS,
              "layers": LAYERS}
    moved = 2 * LAYERS * SLOTS * STATE * INNER * 4
    for name, fn in (("fused_xla", fused), ("kernel_one_row", kernel)):
        wall = timed(jax.jit(fn, donate_argnums=(0,)), states, x, dt, b, c,
                     donate_first=True)
        states = [jnp.zeros((SLOTS, STATE, INNER), f32)
                  for _ in range(LAYERS)]
        result[f"decode_update_{name}_ms"] = round(wall * 1e3, 3)
        result[f"decode_update_{name}_gb_per_s"] = round(
            moved / wall / 1e9, 1)

    xs = jax.random.normal(keys[5], (1, PROMPT, INNER), f32)
    dts = jnp.abs(jax.random.normal(keys[6], (1, PROMPT, INNER), f32)) * 0.05
    bs = jax.random.normal(keys[7], (1, PROMPT, STATE), f32)
    for name, fn in (("kernel", selective_scan),
                     ("lax_scan", selective_scan_reference)):
        wall = timed(jax.jit(lambda x, dt, b, c, fn=fn: fn(x, dt, b, c, a)),
                     xs, dts, bs, bs)
        result[f"prefill_{PROMPT}_{name}_ms"] = round(wall * 1e3, 3)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
