"""Microbench: the ``flash_decode`` kernel alone at the ``gpt2-xl-chat``
cell's shapes — 64 slots x 25 heads x 128 lanes, a bf16 pool of 1,400
blocks of 16, a table of 64 entries a slot, 48 calls back to back (one a
layer of the decode step) inside one jit, on the host's clock.

Each state is a number of live slots of 240 keys (the cell's median
context) and what the free slots are handed: ``n_keys`` 0 (what the decode
step hands a free slot since PR 36), 1, or 1,024 over an all-garbage table
row (a free slot whose cursor has grown, as before PR 36). The difference
between the states separates the empty grid, the live slots and the free
slots' live steps. Run manually on the chip; not part of the test suite:

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

SLOTS, HEADS, HEAD_DIM, BLOCK, TABLE, POOL_BLOCKS = 64, 25, 64, 16, 64, 1400
LAYERS = 48          # calls back to back: one decode step's worth
LIVE_KEYS = 240      # the cell's median context (192 + 48)
# (live slots, n_keys handed to a free slot)
STATES = ((0, 0), (6, 0), (6, 1), (6, 1024), (38, 0), (38, 1), (64, 0))
REPEATS = 10


def state_arrays(n_live: int, free_keys: int):
    """Tables and key counts: the live slots hold their own run of pool
    blocks, the free ones an all-garbage row (block 0)."""
    per = -(-LIVE_KEYS // BLOCK)
    tables = np.zeros((SLOTS, TABLE), np.int32)
    blocks = 1 + np.random.default_rng(0).permutation(POOL_BLOCKS - 1)
    for s in range(n_live):
        tables[s, :per] = blocks[s * per:(s + 1) * per]
    n_keys = np.full((SLOTS,), free_keys, np.int32)
    n_keys[:n_live] = LIVE_KEYS
    return jnp.asarray(tables), jnp.asarray(n_keys)


def main() -> None:
    from flexflow_tpu.kernels.flash_decode import flash_decode_pool

    if jax.devices()[0].platform != "tpu":
        sys.exit("microbench_flash_decode: no TPU; a time from another "
                 "backend says nothing about the kernel")
    kq, kp = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (SLOTS, HEADS, HEAD_DIM), jnp.bfloat16)
    pool = jax.random.normal(
        kp, (POOL_BLOCKS, HEADS, BLOCK, 2 * HEAD_DIM), jnp.bfloat16)

    @jax.jit
    def step(q, pool, tables, n_keys):
        # another query a call, so that no two calls are one to XLA
        outs = [flash_decode_pool(q * (1.0 + i), pool, tables, n_keys)
                for i in range(LAYERS)]
        return sum(o.astype(jnp.float32) for o in outs)

    rows = []
    for n_live, free_keys in STATES:
        tables, n_keys = state_arrays(n_live, free_keys)
        jax.block_until_ready(step(q, pool, tables, n_keys))
        walls = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(step(q, pool, tables, n_keys))
            walls.append((time.perf_counter() - t0) * 1e3)
        rows.append({"live_slots": n_live, "free_slot_n_keys": free_keys,
                     "ms_per_48_calls_median": float(np.median(walls)),
                     "ms_min": float(np.min(walls))})
        print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, "microbench_flash_decode.json"),
                  "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
