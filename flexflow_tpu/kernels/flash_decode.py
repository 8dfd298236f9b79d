"""Pallas split-K flash-decode kernel: single-token paged attention.

The serving decode step advances every slot one token; its attention read
is the decode hot loop's HBM bill. The ring layout paid O(max_len) per
slot per token (position-masked attention over the full ring); with the
paged layout (serving/kvcache.py) this kernel gathers ONLY the blocks a
slot actually occupies, so per-token traffic is O(true_length):

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
* int8 KV (``kscale``/``vscale``): blocks are dequantized in-VMEM from
  the block-paged per-(token, head) scales — HBM moves ~1/el of the fp
  bytes plus the f32 scale vectors (the bandwidth the serving search's
  ``kv_dtype`` axis prices).

Off-TPU the op layer never routes here (the masked gather path keeps
tier-1 CPU-green); tests run the kernel in interpret mode.

Mosaic wants a free (row) dimension on the left operand of a matmul, so
the one-token query rides as a unit row: q is ``(heads, 1, head_dim)`` and
the two products are ordinary head-batched matmuls ``hqd,hkd->hqk`` and
``hqk,hkd->hqd`` — the (m, l, acc) state and the output block carry the
same unit row.
"""
from __future__ import annotations

import functools
from typing import Optional

NEG_INF = -1e30


def use_flash_decode(head_dim: int, block_size: int) -> bool:
    """Routing gate for the serving attention op: real-TPU platform and
    MXU/VPU-friendly dims (lane-padded head_dim, whole-sublane blocks).
    The CPU path (gather + masked einsum) is the correctness path —
    this kernel is the bandwidth path."""
    from ._common import on_tpu

    if block_size < 8 or block_size % 8 != 0 or head_dim % 64 != 0:
        return False
    return on_tpu()


def _decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_size, n_blocks_grid,
                   kv_dtype, ks_ref=None, vs_ref=None):
    """One (slot, kv-block) grid step of the split-K recurrence."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

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
        q = q_ref[0].astype(jnp.float32)          # (h, 1, hd), pre-scaled
        k = k_ref[0]                              # (h, bs, kd)
        v = v_ref[0]                              # (h, bs, vd)
        if kv_dtype == "int8":
            k = k.astype(jnp.float32) * ks_ref[0][..., None]
            v = v.astype(jnp.float32) * vs_ref[0][..., None]
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        # (h, 1, bs) score tile: per-head q row against the block's keys
        s_tile = jnp.einsum("hqd,hkd->hqk", q, k,
                            preferred_element_type=jnp.float32)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s_tile.shape, 2)
        s_tile = jnp.where(kpos < n_keys, s_tile, NEG_INF)
        m_prev = m_ref[:, :, :1]                  # (h, 1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_tile, axis=-1,
                                            keepdims=True))
        p = jnp.exp(s_tile - m_new)               # (h, 1, bs)
        corr = jnp.exp(m_prev - m_new)            # (h, 1, 1)
        pv = jnp.einsum("hqk,hkd->hqd", p, v,
                        preferred_element_type=jnp.float32)  # (h, 1, vd)
        acc_ref[:] = acc_ref[:] * corr + pv
        l_new = l_ref[:, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blocks_grid - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / l_ref[:, :, :1]).astype(o_ref.dtype)


def flash_decode(q, kpool, vpool, block_tables, n_keys, *,
                 sm_scale: Optional[float] = None, kscale=None,
                 vscale=None, interpret: bool = False):
    """Single-token paged attention over a KV block pool.

    q            (n_slots, heads, head_dim) — this step's query rows
    kpool/vpool  (n_blocks, heads, block_size, kd|vd) — model dtype, or
                 int8 with ``kscale``/``vscale`` (n_blocks, heads,
                 block_size) f32 per-(token, head) scales
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

    n_slots, heads, head_dim = q.shape
    n_blocks, _h, block_size, kd = kpool.shape
    vd = vpool.shape[-1]
    mb = block_tables.shape[1]
    kv_dtype = "int8" if kpool.dtype == jnp.int8 else "native"
    if kv_dtype == "int8" and (kscale is None or vscale is None):
        raise ValueError("flash_decode: int8 pools need kscale/vscale")
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(head_dim)
    out_dtype = q.dtype
    q = (q.astype(jnp.float32) * jnp.float32(scale))[:, :, None, :]
    tables = block_tables.astype(jnp.int32)
    n_keys = n_keys.astype(jnp.int32)

    def block_index(s, j, tab_ref, len_ref):
        # clamp steps past the slot's last occupied block to the last
        # occupied one: the resolved index repeats, Pallas skips the DMA,
        # and the body's position mask ignores the data
        used = (len_ref[s] + block_size - 1) // block_size
        jj = jnp.minimum(j, jnp.maximum(used - 1, 0))
        return (tab_ref[s, jj], 0, 0, 0)

    def scale_index(s, j, tab_ref, len_ref):
        return block_index(s, j, tab_ref, len_ref)[:3]

    in_specs = [
        pl.BlockSpec((1, heads, 1, head_dim),
                     lambda s, j, t, n: (s, 0, 0, 0)),
        pl.BlockSpec((1, heads, block_size, kd), block_index),
        pl.BlockSpec((1, heads, block_size, vd), block_index),
    ]
    args = [q, kpool, vpool]
    ks_vs = None
    if kv_dtype == "int8":
        in_specs += [pl.BlockSpec((1, heads, block_size), scale_index),
                     pl.BlockSpec((1, heads, block_size), scale_index)]
        args += [kscale, vscale]
        ks_vs = True

    def kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, *rest):
        if ks_vs:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            (o_ref, m_ref, l_ref, acc_ref) = rest
            ks_ref = vs_ref = None
        _decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, block_size=block_size,
                       n_blocks_grid=mb, kv_dtype=kv_dtype,
                       ks_ref=ks_ref, vs_ref=vs_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, 1, vd),
                               lambda s, j, t, n: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, 1, 128), jnp.float32),  # m
            pltpu.VMEM((heads, 1, 128), jnp.float32),  # l
            pltpu.VMEM((heads, 1, vd), jnp.float32),   # acc
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, 1, vd), out_dtype),
        interpret=resolve_interpret(interpret),
        name="flash_decode",
    )
    return fn(tables, n_keys, *args)[:, :, 0, :]


@functools.lru_cache(maxsize=1)
def _reference_decode():
    """Masked-gather reference (the op layer's CPU path restated) for the
    kernel parity tests."""
    import jax.numpy as jnp

    from ..serving.kvcache import (dequantize_kv, gather_paged_kv,
                                   gather_paged_scales)

    def ref(q, kpool, vpool, tables, n_keys, sm_scale,
            kscale=None, vscale=None):
        if kscale is not None:
            kc = dequantize_kv(gather_paged_kv(kpool, tables),
                               gather_paged_scales(kscale, tables),
                               jnp.float32)
            vc = dequantize_kv(gather_paged_kv(vpool, tables),
                               gather_paged_scales(vscale, tables),
                               jnp.float32)
        else:
            kc = gather_paged_kv(kpool, tables).astype(jnp.float32)
            vc = gather_paged_kv(vpool, tables).astype(jnp.float32)
        logits = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kc,
                            preferred_element_type=jnp.float32) * sm_scale
        kpos = jnp.arange(kc.shape[2])
        logits = jnp.where(kpos[None, None, :] < n_keys[:, None, None],
                           logits, NEG_INF)
        import jax

        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhk,bhkd->bhd", probs, vc,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    return ref
