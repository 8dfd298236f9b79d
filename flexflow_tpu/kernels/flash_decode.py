"""Pallas split-K flash-decode kernel: single-token paged attention.

The serving decode step advances every slot one token; its attention read
is the decode hot loop's HBM bill. Over the paged pool
(serving/kvcache.py) this kernel does work in proportion to the keys that
are LIVE — grid steps, bytes and arithmetic:

* grid = (n_slots,): a grid step is one SLOT. It reads the slot's live
  key count from the scalar prefetch and folds the slot's live KEY TILES,
  first to last, in a loop whose trip count is that many — a tile is
  ``P = tile_blocks(...)`` consecutive entries of the slot's block table,
  ``P * block_size`` keys (256 where the row is that long and VMEM
  allows) — into an online-softmax accumulator (m, l, acc scratch),
  exactly the FlashAttention recurrence restricted to a 1-row q. The
  tile loop is the **split-K** dimension. A table whose width is no
  multiple of ``P`` is padded with the garbage block.
* the pool stays in HBM and the kernel gathers a tile's blocks itself:
  ``P`` async copies, one a table entry, resolved through the
  scalar-prefetched table into a double-buffered VMEM tile — the next
  tile of the slot is in flight while this one is folded. No
  ``BlockSpec`` names the pool and no step exists for a tile past the
  slot's last key: what the kernel steps over is the slots and the live
  tiles, nothing else (PERF.md section 6, PR 45, has what each costs).
  Entries of a live tile past the slot's last block point at blocks the
  slot does not read — the row's garbage padding — and are copied like
  the others: their keys are masked out by global key position, and the
  masked probability, exactly 0, meets finite stored values.
* a slot of ``n_keys`` 0 — what the decode step hands a free slot
  (kvcache.flash_decode_kv) — folds nothing and is written as exact
  zeros without touching the accumulators (a NaN in its place would
  reach the garbage block through that slot's K/V write and break the
  invariant below).
* ONE pool: a pool row holds a head's K and V side by side on the lanes
  (kvcache.py), so the kernel never slices lanes. The query rides
  zero-padded over V's lanes — ``0 * finite`` is exactly 0, and every
  stored value is finite (the garbage block's invariant) — so
  ``q . row`` is the K score; ``p . tile`` is accumulated at full width
  and V's lanes are taken once, from the output.
* int8 KV (``scales``): a tile is dequantized in-VMEM from the
  block-paged per-(token, head) scales — HBM moves ~1/el of the fp bytes
  plus the f32 scale vectors (the bandwidth the serving search's
  ``kv_dtype`` axis prices). The scales' rows are narrower than a lane
  tile, which Mosaic does not let a hand-written copy slice (a block's
  whole ``(2, heads, block_size)`` rows included: "slice shape along
  dimension 3 must be aligned to tiling (128)"), so they come through
  ``P`` ``BlockSpec``s, and a ``BlockSpec`` is indexed by the grid
  alone: an int8 pool therefore keeps the tile axis on the grid —
  ``(n_slots, tiles of a table row)``, the same fold one (slot, key
  tile) grid step, a dead step (a tile past the slot's last key)
  costing the grid's own step and nothing else, each scale index
  clamped to the slot's last occupied block so that a dead step repeats
  it and moves nothing. Which grid is read off the pool's dtype.

Off-TPU the op layer never routes here (the masked gather path keeps
tier-1 CPU-green); tests run the kernel in interpret mode.

Mosaic wants a free (row) dimension on the left operand of a matmul, so
the one-token query rides as a unit row: q is ``(heads, 1, lanes)`` and
the two products are ordinary head-batched matmuls ``hqd,hkd->hqk`` and
``hqk,hkd->hqd`` — the (m, l, acc) state and the output block carry the
same unit row.
"""
from __future__ import annotations

import functools
from typing import Optional

NEG_INF = -1e30
# keys a tile holds, where the table row is that long and VMEM
# allows: on the v5e at GPT-2 XL's widths 256 beat 64, 128 and 512 at 6,
# 38 and 64 live slots of 64 (PERF.md section 6, PR 36)
TILE_KEYS = 256
# what a tile may take of the 16 MiB of scoped VMEM: the two halves of
# the gather buffer in the pool's dtype and two f32 tiles of the body
TILE_VMEM_BYTES = 12 * 2 ** 20


def use_flash_decode(lanes: int, block_size: int) -> bool:
    """Routing gate for the serving attention op, from what it can see
    of the pool: a TPU, ``kd + vd`` a multiple of 128 lanes and
    ``block_size`` of 8 sublanes — what the READER needs, whatever the
    pool's dtype (an int8 pool at block 16 and a bf16 pool at block 8
    read through the kernel, and are written by scatter: ``kv_write``
    asks for whole tiles, kernels/kv_write.py). The CPU path (gather +
    masked einsum) is the correctness path — this kernel is the
    bandwidth path."""
    from ._common import on_tpu

    return lanes % 128 == 0 and block_size % 8 == 0 and on_tpu()


def tile_blocks(pool_shape, itemsize: int, table_width: int) -> int:
    """``P``: the table entries of one key tile, from the pool's
    shape ``(n_blocks, heads, block_size, lanes)`` and element size —
    ``TILE_KEYS`` keys' worth of blocks or what fits ``TILE_VMEM_BYTES``,
    at least one block, at most the row."""
    _n_blocks, heads, block_size, lanes = pool_shape
    fits = TILE_VMEM_BYTES // (heads * lanes * (2 * itemsize + 8))
    return max(1, min(min(TILE_KEYS, fits) // block_size, table_width))


def tiles_on_grid(pool_dtype) -> bool:
    """Whether the kernel's grid keeps the key-tile axis — ``(n_slots,
    tiles of a table row)`` — and does not loop over a slot's live tiles
    inside a ``(n_slots,)`` grid: so for an int8 pool, whose scales come
    through ``BlockSpec``s that only a grid axis can index."""
    import jax.numpy as jnp

    return pool_dtype == jnp.int8


def _decode_kernel(tab_ref, len_ref, q_ref, pool_ref, *rest, block_size,
                   tile_blocks, n_tiles_grid, kd, int8, latent=False,
                   tokens=1, shared_table=False):
    """One SLOT of the split-K recurrence: a grid step folds the slot's
    live key tiles, first to last, in a loop whose trip count is read
    from ``len_ref[s]`` (``n_tiles_grid`` None). An int8 pool's scales
    come through ``BlockSpec``s, which only a grid axis can index, so
    there the same fold is one (slot, key tile) grid step of
    ``n_tiles_grid`` a slot. ``latent``: the query block is a group's
    heads against one stored row a key, and the two products take their
    operands as stored (the pool's dtype) and accumulate in float32.
    ``tokens`` > 1 (latent): the block's rows are that many successive
    positions of one sequence, heads innermost, and ``len_ref[s]`` counts
    the keys of the FIRST of them — token ``t`` sees ``t`` more (a
    prefill chunk's causal mask); ``shared_table``: every slot reads the
    table's one row."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P = tile_blocks
    sc_refs = ()
    if int8:
        sc_refs, rest = rest[:P], rest[P:]
    o_ref, kv_buf, sems, m_ref, l_ref, acc_ref = rest
    tile_keys = P * block_size
    s = pl.program_id(0)
    n_keys = len_ref[s]
    row = 0 if shared_table else s
    if tokens > 1:   # the last token's keys decide the slot's live tiles
        n_tiles = jnp.where(n_keys > 0, (n_keys + tokens - 1 + tile_keys
                                         - 1) // tile_keys, 0)
    else:
        n_tiles = (n_keys + tile_keys - 1) // tile_keys   # the live ones

    def gather(tile, buf):
        """The copies of one tile's blocks into half ``buf`` of the
        buffer: started once, waited once."""
        return [pltpu.make_async_copy(
            pool_ref.at[tab_ref[row, tile * P + i]], kv_buf.at[buf, i],
            sems.at[buf, i]) for i in range(P)]

    def init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def first():
        for copy in gather(0, 0):
            copy.start()

    def fold(j):
        buf = j % 2

        @pl.when(j + 1 < n_tiles)
        def _next():
            for copy in gather(j + 1, 1 - buf):
                copy.start()

        for copy in gather(j, buf):
            copy.wait()
        # (h, 1, lanes): pre-scaled, zero over V's lanes
        q = q_ref[0] if latent else q_ref[0].astype(jnp.float32)

        def block(i):                             # (h, bs, lanes): K | V
            kv = kv_buf[buf, i]
            if not latent:
                kv = kv.astype(jnp.float32)
            if int8:
                lane = jax.lax.broadcasted_iota(jnp.int32, kv.shape, 2)
                sc = sc_refs[i][0]
                kv = kv * jnp.where(lane < kd, sc[0][..., None],
                                    sc[1][..., None])
            return kv

        kv = jnp.concatenate([block(i) for i in range(P)],
                             axis=1)              # (h, tile, lanes)
        # (h, 1, tile) score tile: per-head q row against the tile's keys
        s_tile = jnp.einsum("hqd,hkd->hqk", q, kv,
                            preferred_element_type=jnp.float32)
        kpos = j * tile_keys + jax.lax.broadcasted_iota(
            jnp.int32, s_tile.shape, 2)
        seen = n_keys
        if tokens > 1:   # row r is token r // heads-of-the-group
            seen = n_keys + jax.lax.broadcasted_iota(
                jnp.int32, s_tile.shape, 1) // (s_tile.shape[1] // tokens)
        s_tile = jnp.where(kpos < seen, s_tile, NEG_INF)
        m_prev = m_ref[:, :, :1]                  # (h, 1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_tile, axis=-1,
                                            keepdims=True))
        p = jnp.exp(s_tile - m_new)               # (h, 1, tile)
        corr = jnp.exp(m_prev - m_new)            # (h, 1, 1)
        # (h, 1, lanes): V's lanes are the output, K's are never read
        pv = jnp.einsum("hqk,hkd->hqd", p.astype(kv.dtype), kv,
                        preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        l_new = l_ref[:, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def finish():
        # a slot of no keys folded nothing: l and acc are 0, and 0 / 1
        # is the exact zero that 0 / 0 is not
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l > 0.0, l, 1.0)
                    ).astype(o_ref.dtype)

    if n_tiles_grid is None:
        @pl.when(n_tiles == 0)
        def _free():
            # exact zeros, and no accumulator touched: a free slot's
            # step then costs half of what init and finish make it cost
            o_ref[0] = jnp.zeros_like(o_ref[0])

        @pl.when(n_tiles > 0)
        def _live():
            init()
            first()

            def step(j, carry):
                fold(j)
                return carry

            jax.lax.fori_loop(0, n_tiles, step, 0)
            finish()
    else:
        j = pl.program_id(1)
        pl.when(j == 0)(init)
        pl.when(jnp.logical_and(j == 0, n_tiles > 0))(first)
        pl.when(j < n_tiles)(lambda: fold(j))
        pl.when(j == n_tiles_grid - 1)(finish)


def flash_decode_pool(q, pool, block_tables, n_keys, *,
                      sm_scale: Optional[float] = None, scales=None,
                      interpret: bool = False,
                      v_lanes: Optional[int] = None, tokens: int = 1):
    """Single-token paged attention over one KV block pool.

    q            (n_slots, heads, kd) — this step's query rows
    pool         (n_blocks, heads, block_size, kd + vd) — a row is one
                 head's K then V (serving/kvcache.py); model dtype, or
                 int8 with ``scales`` (n_blocks, 2, heads, block_size)
                 f32, K's per-(token, head) scales then V's
    block_tables (n_slots, max_blocks_per_slot) int32
    n_keys       (n_slots,) int32 — keys each slot attends: position + 1
                 for a live slot, 0 for one that attends nothing (a free
                 slot: no live step, no bytes, an output of exact zeros)

    ``v_lanes`` names the LATENT layout (kvcache.py): the pool holds one
    row a key whatever the query heads (``heads`` 1, or the K/V heads of
    a grouped read), ``q`` is ``(n_slots, heads * group, kd)`` and a
    slot's step scores a group's query rows — a ``(group, lanes)`` block
    — against each tile's rows in one product; the value is the row's
    first ``v_lanes`` lanes, so the output is ``(n_slots, heads * group,
    v_lanes)``. The products take the pool's dtype (bf16 on the chip)
    and accumulate in float32. Same grid, same gather, same name.

    ``tokens`` > 1 (latent only) is a prefill CHUNK's read through the
    same kernel: a slot is ``tokens`` successive positions of ONE
    sequence — ``q`` is ``(n_slots, tokens * heads * group, kd)``, token
    major — ``block_tables`` is that sequence's one row ``(1, mb)``
    shared by every slot, and ``n_keys[s]`` counts the keys of the
    slot's FIRST token (0: a slot of pad rows, which costs nothing);
    token ``t`` of the slot sees ``t`` more. The call is named
    ``latent_chunk_attention`` in the compiled program, so that a trace
    tells the chunk's reads from the decode step's.

    The grid is ``(n_slots,)``: a step a slot, the slot's live tiles a
    loop inside it, ``P = tile_blocks(pool.shape, itemsize,
    max_blocks_per_slot)`` table entries a tile (an int8 pool:
    ``(n_slots, ceil(max_blocks_per_slot / P))``, a step a tile); the
    table is padded to whole tiles with the garbage block. Returns
    (n_slots, heads, vd) in q's dtype. ``interpret=True``
    runs the Mosaic interpreter (the CPU test path; refused on a TPU).

    The call is a ``jax.jit`` of its own: a decode step calls it once a
    layer with the same shapes, and the kernel is then traced and
    lowered once a program and not once a layer (48 times at GPT-2 XL,
    seconds of every set-up)."""
    return _jitted_pool()(q, pool, block_tables, n_keys, sm_scale=sm_scale,
                          scales=scales, interpret=interpret,
                          v_lanes=v_lanes, tokens=int(tokens))


@functools.lru_cache(maxsize=1)
def _jitted_pool():
    import jax

    return jax.jit(_flash_decode_pool,
                   static_argnames=("sm_scale", "interpret", "v_lanes",
                                    "tokens"))


def _flash_decode_pool(q, pool, block_tables, n_keys, *, sm_scale, scales,
                       interpret, v_lanes=None, tokens=1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..serving.kvcache import GARBAGE_BLOCK
    from ._common import resolve_interpret

    n_slots, heads, kd = q.shape
    _n_blocks, _h, block_size, lanes = pool.shape
    mb = block_tables.shape[1]
    int8 = pool.dtype == jnp.int8
    if int8 and scales is None:
        raise ValueError("flash_decode: an int8 pool needs its scales")
    latent = v_lanes is not None
    shared_table = tokens > 1
    if shared_table and not (latent and block_tables.shape[0] == 1
                             and heads % tokens == 0):
        raise ValueError(
            f"flash_decode: tokens={tokens} is the latent chunk read: one "
            f"shared table row and q rows a multiple of it; got q "
            f"{q.shape}, tables {block_tables.shape}, v_lanes {v_lanes}")
    if latent and (int8 or heads % _h or not 0 < v_lanes <= kd <= lanes):
        raise ValueError(
            f"flash_decode: the latent read takes an fp pool whose heads "
            f"divide the query's and a value that is a lane prefix of the "
            f"key; got q {q.shape}, pool {pool.shape} {pool.dtype}, "
            f"v_lanes {v_lanes}")
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(kd)
    out_dtype = q.dtype
    q = q.astype(jnp.float32) * jnp.float32(scale)
    rows = 1  # query rows a pool head: the group's heads in the latent form
    if latent:
        rows, heads = heads // _h, _h
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - kd))).reshape(
            n_slots, heads, rows, lanes).astype(pool.dtype)
    else:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - kd)))[:, :, None, :]
    P = tile_blocks(pool.shape, pool.dtype.itemsize, mb)
    n_tiles = -(-mb // P)
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, n_tiles * P - mb)),
                     constant_values=GARBAGE_BLOCK)
    n_keys = n_keys.astype(jnp.int32)

    def slot_row(s, *_):
        return (s, 0, 0, 0)

    def scale_block(i):
        def index(s, j, tab_ref, len_ref):
            # clamp entries past the slot's last occupied block to the
            # last occupied one: the resolved index repeats, Pallas
            # skips the DMA, and the position mask ignores the data
            used = (len_ref[s] + block_size - 1) // block_size
            jj = jnp.minimum(j * P + i, jnp.maximum(used - 1, 0))
            return (tab_ref[s, jj], 0, 0, 0)
        return index

    in_specs = [pl.BlockSpec((1, heads, rows, lanes), slot_row),
                pl.BlockSpec(memory_space=pltpu.HBM)]
    args = [q, pool]
    if int8:
        in_specs += [pl.BlockSpec((1, 2, heads, block_size), scale_block(i))
                     for i in range(P)]
        args += [scales] * P
    tiled_grid = tiles_on_grid(pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots, n_tiles) if tiled_grid else (n_slots,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, rows, lanes), slot_row),
        scratch_shapes=[
            # two tiles of P blocks: one folded, the next in flight
            pltpu.VMEM((2, P, heads, block_size, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2, P)),
            pltpu.VMEM((heads, rows, 128), jnp.float32),    # m
            pltpu.VMEM((heads, rows, 128), jnp.float32),    # l
            pltpu.VMEM((heads, rows, lanes), jnp.float32),  # acc
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          tile_blocks=P,
                          n_tiles_grid=n_tiles if tiled_grid else None,
                          kd=kd,
                          int8=int8, latent=latent, tokens=tokens,
                          shared_table=shared_table),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, rows, lanes),
                                       out_dtype),
        interpret=resolve_interpret(interpret),
        name="latent_chunk_attention" if shared_table else "flash_decode",
    )
    out = fn(tables, n_keys, *args)
    if latent:
        return out[..., :v_lanes].reshape(n_slots, heads * rows, v_lanes)
    return out[:, :, 0, kd:]


def flash_decode(q, kpool, vpool, block_tables, n_keys, *,
                 sm_scale: Optional[float] = None, kscale=None,
                 vscale=None, interpret: bool = False):
    """:func:`flash_decode_pool` for a caller that holds K and V apart
    (``(n_blocks, heads, block_size, kd | vd)``, int8 with ``kscale`` /
    ``vscale`` ``(n_blocks, heads, block_size)``): packs them into the
    one pool, a copy of both. The serving path stores the pool packed
    and never comes through here; the benchmark's ahead-of-time check
    of the kernel at the cell's widths does."""
    import jax.numpy as jnp

    scales = None
    if kscale is not None and vscale is not None:
        scales = jnp.stack([kscale, vscale], axis=1)
    return flash_decode_pool(
        q, jnp.concatenate([kpool, vpool], axis=-1), block_tables,
        n_keys, sm_scale=sm_scale, scales=scales, interpret=interpret)


@functools.lru_cache(maxsize=1)
def _reference_decode():
    """Masked-gather reference (the op layer's CPU path restated) for the
    kernel parity tests."""
    import jax
    import jax.numpy as jnp

    from ..serving.kvcache import read_kv

    def ref(q, pool, tables, n_keys, sm_scale, scales=None):
        entry = pool if scales is None else (pool, scales)
        kc, vc = read_kv(entry, tables, q.shape[-1], jnp.float32)
        logits = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kc,
                            preferred_element_type=jnp.float32) * sm_scale
        kpos = jnp.arange(kc.shape[2])
        logits = jnp.where(kpos[None, None, :] < n_keys[:, None, None],
                           logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhk,bhkd->bhd", probs, vc,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    return ref
