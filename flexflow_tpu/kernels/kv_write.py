"""Pallas in-place write of rows into the paged KV pool (``kv_write``).

XLA's one-row scatter does not write a row of a packed bf16 pool in
place: it moves the whole pool to a layout with the heads under the
tile, scatters there, and moves it back — two passes over every pool
leaf for each token (PERF.md §7, "the decode step's copies"). This call
is aliased onto the pool instead (``input_output_aliases``), so the
compiled step holds no instruction of the pool's shape but the kernels
that read and write it.

* grid = (groups,), sequential. Grid step ``g`` rewrites ONE pool block,
  ``block_ids[g]`` (scalar-prefetched, resolved by the BlockSpec index
  map): rows ``lo[g] <= t < hi[g]`` take the new values, every other
  row keeps its stored ones — a whole-block read-modify-write, because
  Mosaic refuses a store at a dynamic row of a packed tile.
* the new values ride as ``(groups, heads, R, lanes)``: ``R == 1`` is one
  row a group (the decode step: one token a slot), broadcast over the
  block; ``R == block_size`` carries a block's worth, row ``t`` landing
  at offset ``t`` (a prefill chunk, staged a block at a time).
* the hazard: Pallas fetches step ``g + 1``'s input block before step
  ``g``'s output is written back, so two steps that rewrite the SAME
  block lose the first one's rows. Callers therefore never give one
  block to two steps unless both writes may be lost — the garbage block
  (serving/kvcache.py), where free slots and pad rows meet and any
  finite row will do.

A move, not arithmetic: stored values pass through bit for bit.
"""
from __future__ import annotations

from typing import Optional


def use_kv_write(pool) -> bool:
    """Routing gate: a TPU, and a pool whose blocks fill whole tiles —
    ``kd + vd`` a multiple of 128 lanes, ``block_size`` as many sublanes
    as the dtype packs into a tile (f32 8, bf16 16, int8 32). Only such
    a pool rests in the layout the kernels compute in, so only there is
    a whole-block rewrite in place (serving/kvcache.py's docstring has
    what the compiler does to any other). The reader, ``flash_decode``,
    needs less and has its own gate. Elsewhere the pool is written by
    the ``.at[].set`` this kernel replaces (the CPU path and the tests'
    oracle)."""
    import numpy as np

    from ._common import on_tpu

    sublanes = 32 // np.dtype(pool.dtype).itemsize
    return (pool.shape[-1] % 128 == 0 and pool.shape[2] % sublanes == 0
            and on_tpu())


def kv_write(pool, rows, block_ids, lo, hi, *,
             interpret: Optional[bool] = None):
    """pool (n_blocks, heads, block_size, lanes); rows (groups, heads,
    R, lanes) with R 1 or block_size, in the pool's dtype; block_ids,
    lo, hi (groups,) int32. Returns the pool, written in place when the
    caller donates it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ._common import resolve_interpret

    groups, heads, r, lanes = rows.shape
    block = (1,) + pool.shape[1:]
    if r not in (1, pool.shape[2]) or rows.dtype != pool.dtype \
            or (heads, lanes) != (pool.shape[1], pool.shape[3]):
        raise ValueError(
            f"kv_write: rows {rows.shape} {rows.dtype} do not fit pool "
            f"blocks {block} {pool.dtype}")

    def kernel(bid_ref, lo_ref, hi_ref, rows_ref, blk_ref, out_ref):
        g = pl.program_id(0)
        t = jax.lax.broadcasted_iota(jnp.int32, block[1:], 1)
        new = jnp.broadcast_to(rows_ref[0], block[1:])
        out_ref[0] = jnp.where((t >= lo_ref[g]) & (t < hi_ref[g]),
                               new, blk_ref[0])

    def pool_block(g, bid_ref, lo_ref, hi_ref):
        return (bid_ref[g], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(groups,),
        in_specs=[
            pl.BlockSpec((1, heads, r, lanes),
                         lambda g, b, lo_, hi_: (g, 0, 0, 0)),
            pl.BlockSpec(block, pool_block),
        ],
        out_specs=pl.BlockSpec(block, pool_block),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 4 of (block_ids, lo, hi, rows, pool) is output 0
        input_output_aliases={4: 0},
        interpret=resolve_interpret(interpret),
        name="kv_write",
    )(block_ids.astype(jnp.int32), lo.astype(jnp.int32),
      hi.astype(jnp.int32), rows, pool)
