"""The oracle of the flash-decode parity tests: the kernel as it was
before the tile axis left the grid (commit 320ad91, PR 36's grid of
``(n_slots, tiles of a table row)``: a grid step a (slot, key tile),
``_init`` / ``_finish`` under ``pl.when``), kept word for word and run in
interpret mode. The kernel of ``flexflow_tpu/kernels/flash_decode.py`` folds
the same tiles in the same order with the same arithmetic, so on the same
inputs its outputs are these to the bit."""
import functools

NEG_INF = -1e30


def _decode_kernel(tab_ref, len_ref, q_ref, pool_ref, *rest, block_size,
                   tile_blocks, n_tiles_grid, kd, int8, latent=False,
                   tokens=1, shared_table=False):
    """One (slot, key tile) grid step of the split-K recurrence. ``latent``:
    the query block is a group's heads against one stored row a key, and the
    two products take their operands as stored (the pool's dtype) and
    accumulate in float32. ``tokens`` > 1 (latent): the block's rows are
    that many successive positions of one sequence, heads innermost, and
    ``len_ref[s]`` counts the keys of the FIRST of them — token ``t``
    sees ``t`` more (a prefill chunk's causal mask); ``shared_table``:
    every slot reads the table's one row."""
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
    j = pl.program_id(1)
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

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(j == 0, n_tiles > 0))
    def _first():
        for copy in gather(0, 0):
            copy.start()

    @pl.when(j < n_tiles)
    def _step():
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

    @pl.when(j == n_tiles_grid - 1)
    def _finish():
        # a slot of no keys folded nothing: l and acc are 0, and 0 / 1
        # is the exact zero that 0 / 0 is not
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l > 0.0, l, 1.0)
                    ).astype(o_ref.dtype)


def tiled_flash_decode_pool(q, pool, block_tables, n_keys, *, sm_scale=None,
                            scales=None, v_lanes=None, tokens=1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flexflow_tpu.kernels.flash_decode import tile_blocks
    from flexflow_tpu.serving.kvcache import GARBAGE_BLOCK

    n_slots, heads, kd = q.shape
    _n_blocks, _h, block_size, lanes = pool.shape
    mb = block_tables.shape[1]
    int8 = pool.dtype == jnp.int8
    latent = v_lanes is not None
    shared_table = tokens > 1
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

    def slot_row(s, j, tab_ref, len_ref):
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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots, n_tiles),
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
                          tile_blocks=P, n_tiles_grid=n_tiles, kd=kd,
                          int8=int8, latent=latent, tokens=tokens,
                          shared_table=shared_table),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, rows, lanes),
                                       out_dtype),
        interpret=True,
        name="latent_chunk_attention" if shared_table else "flash_decode",
    )
    out = fn(tables, n_keys, *args)
    if latent:
        return out[..., :v_lanes].reshape(n_slots, heads * rows, v_lanes)
    return out[:, :, 0, kd:]
