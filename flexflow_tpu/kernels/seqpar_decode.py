"""Sequence-parallel decode: shard-local flash partials + priced combine.

ISSUE 18 (ROADMAP item 3, the capacity half of the long-context story):
a context whose paged KV exceeds one chip's HBM cannot decode on a
single chip no matter how fast the kernel is. Ring Attention (Liu et
al.) and DeepSpeed-Ulysses shard the sequence axis; for *decode* the
paged block tables (ISSUE 12) make that a block-table partition, not a
new runtime — each of ``seq_shards`` chips owns a CONTIGUOUS run of a
slot's KV blocks in its local pool, the single query token of a decode
step is allgathered to every shard, each shard runs the flash-decode
split-K recurrence over its own blocks producing a partial online-
softmax state ``(m, l, acc)``, and one priced combine merges the
partials:

    m*   = max_s m_s
    l*   = sum_s l_s * exp(m_s - m*)
    out  = sum_s acc_s * exp(m_s - m*) / l*

— exactly the flash-attention segment-merge identity, so the combined
result equals the unsharded online softmax up to fp reassociation
(~1 ulp, inside the band tier-1 holds decode logits to against the
whole-sequence forward: 64 ulp of the largest logit, measured 3-8).
A shard whose entire segment is masked (the slot's write cursor has not
reached its block range) contributes ``m_s = -1e30``; its combine
weight ``exp(m_s - m*)`` underflows to exactly 0.0, so never-written
shards add exact zeros — the garbage-block safety argument, lifted to
whole shards.

On the CPU tier (and on a single chip) the shards are emulated locally:
the decomposition is a compute-path reshape of the one gathered extent,
and tier-1 pins the sharded token stream equal to the single-shard
stream (shards 2 and 4, solo, co-batched, through prefix hits and
chunked prefill). On a real mesh the per-shard partials are chip-local
and only ``(m, l, acc)`` crosses ICI; ``combine_bytes_per_step`` below
is the closed form ``serving_search`` prices that traffic with, next to
kv_fill/prefill_reuse.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

#: additive mask value — must match ops/attention.py's decode mask so a
#: fully-masked shard's combine weight underflows to exactly 0.0
MASK_NEG = -1e30


def shard_segment(extent: int, seq_shards: int) -> int:
    """Tokens per shard of a gathered KV extent partitioned into
    ``seq_shards`` contiguous runs. The extent (``max_blocks_per_slot *
    block_size``) must split evenly — FF006's seq-shard law validates
    ``max_blocks_per_slot % seq_shards == 0`` at engine construction,
    so by the time a decode step runs this cannot raise."""
    if seq_shards < 1:
        raise ValueError(f"seq_shards must be >= 1, got {seq_shards}")
    if extent % seq_shards:
        raise ValueError(
            f"KV extent {extent} does not split into {seq_shards} "
            "contiguous sequence shards (FF006: max_blocks_per_slot "
            "must be divisible by seq_shards)")
    return extent // seq_shards


def decode_shard_partial(q, k_seg, v_seg, mask_seg, sm_scale: float):
    """One shard's online-softmax partial over its contiguous key
    segment: ``q`` (b, h, 1, d), ``k_seg``/``v_seg`` (b, h, seg, d),
    ``mask_seg`` (b, 1, 1, seg) bool. Returns f32 ``(m, l, acc)`` with
    shapes (b, h, 1), (b, h, 1), (b, h, 1, vd) — the same state triple
    the flash-decode kernel's VMEM scratch carries per grid step."""
    import jax.numpy as jnp

    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_seg,
                        preferred_element_type=jnp.float32) * sm_scale
    logits = jnp.where(mask_seg, logits, MASK_NEG)
    m = jnp.max(logits, axis=-1)                      # (b, h, 1)
    p = jnp.exp(logits - m[..., None])                # (b, h, 1, seg)
    l = jnp.sum(p, axis=-1)                           # noqa: E741
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_seg.dtype), v_seg,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def combine_partials(partials: Sequence[Tuple]):
    """The priced combine: merge per-shard ``(m, l, acc)`` into the
    decoded attention output (b, h, 1, vd) f32 — the flash segment-merge
    identity. On a real mesh this is the one cross-shard collective of
    a decode step (an allgather of the partial triples); here it is the
    arithmetic both the emulated path and the pricing agree on."""
    import jax.numpy as jnp

    ms: List = [m for m, _l, _a in partials]
    m_star = ms[0]
    for m in ms[1:]:
        m_star = jnp.maximum(m_star, m)
    l_star = None
    out = None
    for m, l, acc in partials:
        w = jnp.exp(m - m_star)                       # 0.0 exactly for
        lw = l * w                                    # never-written shards
        aw = acc * w[..., None]
        l_star = lw if l_star is None else l_star + lw
        out = aw if out is None else out + aw
    return out / l_star[..., None]


def combine_bytes_per_step(heads: int, vdim: int, slots: int,
                           seq_shards: int, el: int = 4) -> int:
    """Per-chip allgather payload bytes of ONE decode step's partial
    combine for one attention node: each shard contributes, per slot
    per head, the f32 triple ``m`` + ``l`` (2 scalars) and the f32
    ``acc`` row (vdim). This is what ``serving_search`` feeds the ICI
    allgather closed form — per STEP, so it is priced next to the
    per-step KV stream it buys down."""
    if seq_shards <= 1:
        return 0
    return slots * heads * (2 + vdim) * el


def query_bytes_per_step(heads: int, kdim: int, slots: int,
                         el: int) -> int:
    """Per-chip bytes of the single-query-token allgather that starts a
    sequence-parallel decode step: every shard needs the step's q rows
    (slots x heads x kdim at the model element size) before it can score
    its local blocks."""
    return slots * heads * kdim * el
