"""Pallas split-K flash-decode kernel: single-token paged attention.

The serving decode step advances every slot one token; its attention read
is the decode hot loop's HBM bill. Over the paged pool
(serving/kvcache.py) this kernel reads ONLY the blocks a slot actually
occupies, so per-token traffic is O(true_length):

* grid = (n_slots, max_blocks_per_slot): the KV-block axis is the
  **split-K** dimension — each grid step folds one (heads, block_size)
  score tile into an online-softmax accumulator (m, l, acc scratch),
  exactly the FlashAttention recurrence restricted to a 1-row q.
* the pool block each step reads is resolved through the slot's block
  table by the BlockSpec index map (``PrefetchScalarGridSpec`` — the
  tables and per-slot key counts are scalar-prefetched, available before
  the kernel body). Steps past a slot's last occupied block CLAMP to the
  last occupied block: Pallas skips the DMA when the resolved index is
  unchanged, so dead steps move no HBM bytes, and the body masks them
  out by global key position anyway (the loaded data is never used).
* ONE block a step: a pool row holds a head's K and V side by side on
  the lanes (kvcache.py), so the kernel never slices lanes. The query
  rides zero-padded over V's lanes — ``0 * finite`` is exactly 0, and
  every stored value is finite (the garbage block's invariant) — so
  ``q . row`` is the K score; ``p . block`` is accumulated at full
  width and V's lanes are taken once, from the output.
* int8 KV (``scales``): blocks are dequantized in-VMEM from the
  block-paged per-(token, head) scales — HBM moves ~1/el of the fp
  bytes plus the f32 scale vectors (the bandwidth the serving search's
  ``kv_dtype`` axis prices).

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


def _decode_kernel(tab_ref, len_ref, q_ref, kv_ref, *rest, block_size,
                   n_blocks_grid, kd, int8):
    """One (slot, kv-block) grid step of the split-K recurrence."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if int8:
        sc_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    n_keys = len_ref[s]

    @pl.when(j * block_size < n_keys)
    def _step():
        # (h, 1, lanes): pre-scaled, zero over V's lanes
        q = q_ref[0].astype(jnp.float32)
        kv = kv_ref[0].astype(jnp.float32)        # (h, bs, lanes): K | V
        if int8:
            lane = jax.lax.broadcasted_iota(jnp.int32, kv.shape, 2)
            kv = kv * jnp.where(lane < kd, sc_ref[0, 0][..., None],
                                sc_ref[0, 1][..., None])
        # (h, 1, bs) score tile: per-head q row against the block's keys
        s_tile = jnp.einsum("hqd,hkd->hqk", q, kv,
                            preferred_element_type=jnp.float32)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s_tile.shape, 2)
        s_tile = jnp.where(kpos < n_keys, s_tile, NEG_INF)
        m_prev = m_ref[:, :, :1]                  # (h, 1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_tile, axis=-1,
                                            keepdims=True))
        p = jnp.exp(s_tile - m_new)               # (h, 1, bs)
        corr = jnp.exp(m_prev - m_new)            # (h, 1, 1)
        # (h, 1, lanes): V's lanes are the output, K's are never read
        pv = jnp.einsum("hqk,hkd->hqd", p, kv,
                        preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        l_new = l_ref[:, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blocks_grid - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / l_ref[:, :, :1]).astype(o_ref.dtype)


def flash_decode_pool(q, pool, block_tables, n_keys, *,
                      sm_scale: Optional[float] = None, scales=None,
                      interpret: bool = False):
    """Single-token paged attention over one KV block pool.

    q            (n_slots, heads, kd) — this step's query rows
    pool         (n_blocks, heads, block_size, kd + vd) — a row is one
                 head's K then V (serving/kvcache.py); model dtype, or
                 int8 with ``scales`` (n_blocks, 2, heads, block_size)
                 f32, K's per-(token, head) scales then V's
    block_tables (n_slots, max_blocks_per_slot) int32
    n_keys       (n_slots,) int32 — keys each slot attends (position + 1)

    Returns (n_slots, heads, vd) in q's dtype. ``interpret=True`` runs
    the Mosaic interpreter (the CPU test path; refused on a TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ._common import resolve_interpret

    n_slots, heads, kd = q.shape
    _n_blocks, _h, block_size, lanes = pool.shape
    mb = block_tables.shape[1]
    int8 = pool.dtype == jnp.int8
    if int8 and scales is None:
        raise ValueError("flash_decode: an int8 pool needs its scales")
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(kd)
    out_dtype = q.dtype
    q = q.astype(jnp.float32) * jnp.float32(scale)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - kd)))[:, :, None, :]
    tables = block_tables.astype(jnp.int32)
    n_keys = n_keys.astype(jnp.int32)

    def block_index(s, j, tab_ref, len_ref):
        # clamp steps past the slot's last occupied block to the last
        # occupied one: the resolved index repeats, Pallas skips the DMA,
        # and the body's position mask ignores the data
        used = (len_ref[s] + block_size - 1) // block_size
        jj = jnp.minimum(j, jnp.maximum(used - 1, 0))
        return (tab_ref[s, jj], 0, 0, 0)

    def slot_row(s, j, tab_ref, len_ref):
        return (s, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, heads, 1, lanes), slot_row),
                pl.BlockSpec((1, heads, block_size, lanes), block_index)]
    args = [q, pool]
    if int8:
        in_specs.append(
            pl.BlockSpec((1, 2, heads, block_size), block_index))
        args.append(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, 1, lanes), slot_row),
        scratch_shapes=[
            pltpu.VMEM((heads, 1, 128), jnp.float32),    # m
            pltpu.VMEM((heads, 1, 128), jnp.float32),    # l
            pltpu.VMEM((heads, 1, lanes), jnp.float32),  # acc
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          n_blocks_grid=mb, kd=kd, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, 1, lanes),
                                       out_dtype),
        interpret=resolve_interpret(interpret),
        name="flash_decode",
    )
    return fn(tables, n_keys, *args)[:, :, 0, kd:]


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
