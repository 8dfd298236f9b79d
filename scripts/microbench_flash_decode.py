"""Microbench: the ``flash_decode`` kernel alone at the three serving
cells' shapes, a decode step's worth of calls (one a layer that reads the
pool) back to back inside one jit, on the host's clock:

* ``gpt2-xl-chat``: 64 slots x 25 heads x 128 lanes, a bf16 pool of 1,400
  blocks of 16, 64 table entries a slot, 48 calls; a live slot holds 240
  keys (the cell's median context).
* ``jamba2-3b-reasoning``: 192 slots x 20 query heads on ONE K/V head of
  256 lanes (the grouped read), 512 table entries, 2 calls; 1,200 keys.
* ``openpangu-ultra-docqa-8k``: 64 slots x 128 heads against one 640-lane
  latent row a key, 824 table entries (no multiple of the tile), 5 calls;
  9,000 keys.

Each state is a number of live slots — none, a few, half, all — with the
free slots handed ``n_keys`` 0, as the decode step hands them. A decode
step's calls run ``INNER`` times inside the one jit (a ``fori_loop``: the
host's dispatch and the wait for the result, half a millisecond a jit call,
would otherwise be most of what two or five calls take), and the same loop
with the kernel left out is timed beside it and taken off. From the rows:
what a slot costs that folds nothing (the time with none live over calls x
slots) and what a live key tile costs (the time a state adds to that over
its live tiles); the few-live row against the half-live one says what the
un-overlapped first gather of a slot costs. Run manually on the chip; not
part of the test suite:

    chiprun --chips 1 -- python scripts/microbench_flash_decode.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16
REPEATS = 10
INNER = 32           # decode steps' worth of calls inside one jit
# cell: slots, query heads, query width, pool (heads, lanes), v_lanes (None:
# the plain pool), table entries, pool blocks, calls a decode step, keys of a
# live slot, the live-slot counts timed
SHAPES = {
    "gpt2-xl-chat": dict(
        slots=64, q_heads=25, kd=64, pool_heads=25, lanes=128, v_lanes=None,
        table=64, pool_blocks=1400, calls=48, live_keys=240,
        live=(0, 6, 32, 38, 64)),
    "jamba2-3b-reasoning": dict(
        slots=192, q_heads=20, kd=256, pool_heads=1, lanes=256, v_lanes=128,
        table=512, pool_blocks=49153, calls=2, live_keys=1200,
        live=(0, 12, 96, 192)),
    "openpangu-ultra-docqa-8k": dict(
        slots=64, q_heads=128, kd=576, pool_heads=1, lanes=640, v_lanes=512,
        table=824, pool_blocks=28000, calls=5, live_keys=9000,
        live=(0, 4, 10, 32, 64)),
    # the latent read at 64 heads a row: one latent layer, a slot some
    # 650 prompt tokens and half its 2,400-token answer deep
    "gigachat35-reasoning-2k": dict(
        slots=128, q_heads=64, kd=576, pool_heads=1, lanes=640, v_lanes=512,
        table=512, pool_blocks=32769, calls=1, live_keys=1900,
        live=(0, 16, 64, 85, 128)),
}


def state_arrays(shape: dict, n_live: int):
    """Tables and key counts: a live slot holds its own run of pool
    blocks (wrapping over the pool where all slots live need more than it
    has), a free one an all-garbage row (block 0) and ``n_keys`` 0."""
    per = -(-shape["live_keys"] // BLOCK)
    tables = np.zeros((shape["slots"], shape["table"]), np.int32)
    blocks = 1 + np.random.default_rng(0).permutation(
        shape["pool_blocks"] - 1)
    for s in range(n_live):
        tables[s, :per] = np.take(blocks, np.arange(s * per, (s + 1) * per),
                                  mode="wrap")
    n_keys = np.zeros((shape["slots"],), np.int32)
    n_keys[:n_live] = shape["live_keys"]
    return jnp.asarray(tables), jnp.asarray(n_keys)


def bench(name: str, shape: dict) -> list:
    from flexflow_tpu.kernels.flash_decode import (flash_decode_pool,
                                                   tile_blocks)

    kq, kp = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(
        kq, (shape["slots"], shape["q_heads"], shape["kd"]), jnp.bfloat16)
    pool = jax.random.normal(
        kp, (shape["pool_blocks"], shape["pool_heads"], BLOCK,
             shape["lanes"]), jnp.bfloat16)
    calls = shape["calls"]
    v_lanes = shape["v_lanes"]
    vd = shape["lanes"] - shape["kd"] if v_lanes is None else v_lanes

    def step_fn(read):
        @jax.jit
        def step(q, pool, tables, n_keys):
            def one_step(k, total):
                # another query a call and a step, so that no two calls
                # are one to XLA and none leaves the loop
                outs = [read(q * (1.0 + i + 0.01 * k), pool, tables, n_keys)
                        for i in range(calls)]
                return total + sum(o.astype(jnp.float32) for o in outs)

            return jax.lax.fori_loop(
                0, INNER, one_step, jnp.zeros(q.shape[:2] + (vd,)))
        return step

    def timed(step, tables, n_keys):
        jax.block_until_ready(step(q, pool, tables, n_keys))
        walls = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(step(q, pool, tables, n_keys))
            walls.append((time.perf_counter() - t0) * 1e3 / INNER)
        return float(np.median(walls)), float(np.min(walls))

    kernel = step_fn(lambda q, pool, tables, n_keys: flash_decode_pool(
        q, pool, tables, n_keys, v_lanes=v_lanes))
    no_kernel = step_fn(lambda q, pool, tables, n_keys: q[..., :vd])
    tile = BLOCK * tile_blocks(pool.shape, pool.dtype.itemsize,
                               shape["table"])
    tiles_live_slot = -(-shape["live_keys"] // tile)
    without, _ = timed(no_kernel, *state_arrays(shape, 0))
    rows, empty_ms = [], None
    for n_live in shape["live"]:
        ms, ms_min = timed(kernel, *state_arrays(shape, n_live))
        row = {"cell": name, "live_slots": n_live, "calls": calls,
               "ms_per_step_median": ms, "ms_min": ms_min,
               "ms_without_kernel": without, "kernel_ms": ms - without}
        if n_live == 0:
            empty_ms = ms
            row["us_per_empty_slot"] = 1e3 * (ms - without) / (
                calls * shape["slots"])
        else:
            row["us_per_live_tile"] = 1e3 * (ms - empty_ms) / (
                calls * n_live * tiles_live_slot)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("microbench_flash_decode: no TPU; a time from another "
                 "backend says nothing about the kernel")
    names = sys.argv[1:] or list(SHAPES)
    rows = [row for name in names for row in bench(name, SHAPES[name])]
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, "microbench_flash_decode.json"),
                  "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
