"""First-class KV-cache / decode-state pytrees for the serving engine.

The reference snapshot's only inference artifact is an incomplete Triton
prototype (triton/README.md); its training-side ``CacheOp`` (src/ops/
cache.cc) threads one cached tensor per op through the step. This module
generalizes that pattern into the serving engine's decode state (ISSUE 6):

* ``ServingState`` — the per-forward context ops see (``OpContext.serving``):
  mode ("prefill" | "decode" | "chunk"), the static per-slot capacity,
  per-slot write positions, the block tables, and the
  cache_in/cache_out dicts keyed by op name. Stateful ops (causal
  ``MultiHeadAttentionOp``, ``LSTMOp``) read and extend it; everything
  else is oblivious.

* ``DecodeState`` — the jit-carried pytree between decode steps: one cache
  entry per stateful node, the per-slot ``lengths`` cursor and the block
  tables. Registered as a pytree node so it flows through ``jax.jit``
  donation like any other train-state argument.

Static shapes are the design rule (no per-token recompiles). The KV
layout is paged (ISSUE 12, vLLM-style PagedAttention adapted to
JAX/TPU): ONE pool of fixed-size KV blocks per stateful node —
``(n_blocks, heads, block_size, head_dim)`` — plus a per-slot **block
table** ``(n_slots, max_blocks_per_slot)`` int32 mapping each slot's
logical positions onto pool blocks. Prefill computes one request's
contiguous ``(1, heads, max_len, head_dim)`` cache and the slot writer
scatters it into the request's blocks; each decode step writes ONE token
at ``lengths[slot]``, and attention masks key positions ``> position``.
Pad garbage beyond a prompt's true length is never read: the write
cursor overwrites it before the mask ever exposes it.
Slot recycling and prefix sharing are pointer bookkeeping in the
host-side :class:`~flexflow_tpu.serving.scheduler.BlockAllocator`
(prefix sharing delivered by ISSUE 14's radix-tree cache,
serving/prefix.py: shared blocks are refcounted, divergent writes clone
first — copy-on-write); pool occupancy decouples from ``max_len`` (a
short request holds few blocks); and the single-compile decode contract
holds — block tables are just another int32 array in the jitted
signature. Block index 0 is the reserved GARBAGE block: every unused
table entry points at it, free slots write their (discarded) tokens into
it, and the attention mask guarantees it is never read — so its contents
only ever need to stay FINITE (``0 * garbage`` must be exactly ``0.0``;
the chaos poisoner deliberately never NaNs it).

Quantized layout (``kv_dtype="int8"``): pool blocks store symmetric
per-(token, head) int8 rows with float32 scales in block-paged scale
arrays ``(n_blocks, heads, block_size)`` — scale = amax/127 over the
head_dim row, written once with the row and folded back on read. fp
layouts are held to the whole-sequence forward within the stated
tolerance (tests/serving_oracle.py); int8 is judged against a pinned
tolerance band (tests/test_decode_paged.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

#: reserved pool block every unused block-table entry points at — written
#: by free slots, never read (masked), must stay finite
GARBAGE_BLOCK = 0

#: supported KV-cache storage dtypes (the searched serving axis)
KV_DTYPES = ("native", "int8")

INT8_QMAX = 127.0


class SeqShardsError(ValueError):
    """A configuration asked for sequence-parallel decode
    (``--seq-shards`` > 1) in a mode that cannot honor it — speculative
    decoding (the greedy verify contract assumes the single-shard score
    path). Raised
    loudly at plan/engine construction instead of decoding garbage."""


@dataclasses.dataclass
class ServingState:
    """Per-forward serving context threaded as ``OpContext.serving``.

    mode:      "prefill" (whole padded prompt), "decode" (one token/slot)
               or "chunk" (ISSUE 14: one fixed-width prefill chunk for a
               SINGLE slot — batch 1 — writing its k/v rows into the
               slot's pool blocks and attending over the slot's gathered
               extent; the chunked-prefill and prefix-suffix program)
    max_len:   per-slot capacity — the static sequence axis of the
               prefill's cache entry (``--max-decode-len``)
    positions: (batch,) int32 — the first position this call writes
               (zeros for prefill; ``DecodeState.lengths`` for decode;
               the chunk's start position for chunk mode)
    lengths:   (batch,) int32 true prompt lengths (prefill only — the LSTM
               carry must be read at position length-1, not at the padded
               tail; attention needs no lengths, its causal mask + the
               decode-side position mask cover padding). Chunk mode reuses
               it for the chunk's REAL token count (rows beyond are pad).
    cache_in:  {node_name: state pytree} consumed by decode
    cache_out: {node_name: state pytree} every stateful op fills
    block_tables: (n_slots, max_blocks_per_slot) int32 — the paged-KV
               block tables (decode and chunk; prefill has none: it
               hands one request's contiguous cache to the slot writer)
    block_size: tokens per KV block
    kv_dtype:  "native" (store k/v at the model dtype) or "int8"
               (symmetric per-(token, head) quantization with f32 scales)
    seq_shards: sequence-parallel decode width (ISSUE 18) — the gathered
               KV extent is partitioned into this many contiguous key
               segments, each scored independently (on a mesh: one chip
               per shard owning that run of pool blocks; on one device:
               an emulated compute-path decomposition of the same
               arrays) and merged by the flash segment combine. 1 is
               the unsharded reference path. Decode only; chunk
               prefill writes are layout-identical at any width.
    """

    mode: str
    max_len: int
    positions: Any
    lengths: Any = None
    cache_in: Optional[Dict[str, Any]] = None
    cache_out: Dict[str, Any] = dataclasses.field(default_factory=dict)
    block_tables: Any = None
    block_size: int = 0
    kv_dtype: str = "native"
    seq_shards: int = 1


@dataclasses.dataclass
class DecodeState:
    """The decode loop's carried state: {node_name: cache pytree} plus the
    per-slot length cursor. A pytree node — ``jax.jit`` donates and returns
    it whole, so the pool updates in place on device (the decode loop
    never copies the cache host-side).

    ``block_tables`` is the (n_slots, max_blocks_per_slot) int32 table
    mapping each slot's positions onto pool blocks — it only changes at
    admission (the slot writer sets the row), so decode steps carry it
    through untouched."""

    caches: Dict[str, Any]
    lengths: Any  # (n_slots,) int32
    block_tables: Any  # (n_slots, max_blocks_per_slot) int32

    @property
    def n_slots(self) -> int:
        return int(self.lengths.shape[0])


def _decode_state_flatten(s: "DecodeState"):
    names = tuple(sorted(s.caches))
    return ([s.caches[k] for k in names]
            + [s.lengths, s.block_tables]), names


def _decode_state_unflatten(names, children):
    return DecodeState(caches=dict(zip(names, children[:-2])),
                       lengths=children[-2], block_tables=children[-1])


def _register_pytree() -> None:
    import jax

    jax.tree_util.register_pytree_node(
        DecodeState, _decode_state_flatten, _decode_state_unflatten)


_register_pytree()


# ---------------------------------------------------------------- helpers
def parse_context_buckets(spec) -> Tuple[int, ...]:
    """Normalize a ``--context-buckets`` spec — the comma-separated flag
    string ("1024,4096,16384") or an already-parsed int sequence — into
    a validated ascending tuple of context lengths. Each bucket is the
    max context a request routed to it may hold; ``serving_search``
    picks seq_shards per bucket and admission routes a request to the
    smallest bucket covering its context. Empty spec → no bucketing."""
    if not spec:
        return ()
    if isinstance(spec, str):
        vals = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                vals.append(int(part))
            except ValueError:
                raise ValueError(
                    f"--context-buckets: {part!r} is not an integer "
                    "(expected a comma-separated list like "
                    "'1024,4096,16384')")
    else:
        vals = [int(v) for v in spec]
    if any(v < 1 for v in vals):
        raise ValueError(
            f"--context-buckets entries must be >= 1, got {vals}")
    if vals != sorted(set(vals)):
        raise ValueError(
            "--context-buckets must be strictly ascending context "
            f"lengths, got {vals}")
    return tuple(vals)


def is_position_constant(value) -> bool:
    """Detect the position-id constant pattern the autoregressive builders
    bake in (models/gpt2.py: ``broadcast(arange(seq_len), (b, s))``): an
    integer 2-D constant whose every row is ``arange(seq)``. Serving must
    regenerate it per phase — prefill gets ``arange(bucket_len)``, decode
    gets each slot's current position — because the baked value is shaped
    for the training batch/sequence."""
    v = np.asarray(value)
    if v.ndim != 2 or not np.issubdtype(v.dtype, np.integer):
        return False
    if v.shape[1] < 1:
        return False
    return bool(np.all(v == np.arange(v.shape[1], dtype=v.dtype)[None, :]))


def update_slot_entry(cache_entry, prefill_entry, slot):
    """Insert one prefilled request's cache rows (leading dim 1) into the
    decode batch's entry (leading dim n_slots) at ``slot`` — a traced
    index, so slot choice never recompiles."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    def ins(dst, src):
        start = (slot,) + (0,) * (dst.ndim - 1)
        return lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                        tuple(jnp.asarray(s) for s in start))

    return jax.tree.map(ins, cache_entry, prefill_entry)


# ----------------------------------------------------------- paged layout
def blocks_per_slot(max_len: int, block_size: int) -> int:
    """Block-table width: blocks covering ``max_len`` tokens."""
    return -(-int(max_len) // int(block_size))


def kv_token_bytes(heads: int, kdim: int, vdim: int, el: int,
                   kv_dtype: str = "native") -> int:
    """KV bytes ONE token costs across one attention node's heads — THE
    shared pricing formula behind the engine's measured
    ``kv_bytes_read`` accounting AND the serving search's explicit
    KV-stream term (``_attention_state_bytes``): int8 stores 1-byte
    rows plus the two f32 per-(token, head) scales; native stores the
    model dtype. One implementation, two consumers — the bench's
    measured fill ratio is fed back into ``serving_search(kv_fill=)``,
    so the two sides must never price from drifting copies."""
    if kv_dtype == "int8":
        return heads * ((kdim + vdim) * 1 + 8)
    return heads * (kdim + vdim) * el


def quantize_kv(x) -> Tuple[Any, Any]:
    """Symmetric per-(..., token, head)-row int8 quantization over the
    trailing head_dim axis: ``q = round(x / scale)`` with
    ``scale = amax(|x|) / 127`` (scale 1 for all-zero rows — dequant of a
    zero row stays exactly zero). Returns ``(q int8, scale f32)`` with
    ``scale`` shaped like ``x`` minus its last axis."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / INT8_QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale[..., None]),
                 -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    """Fold the per-row scale back: ``q * scale`` in f32, cast to the
    compute dtype — the read half of :func:`quantize_kv`."""
    import jax.numpy as jnp

    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def write_token_kv_paged(pool, new, positions, block_tables, block_size):
    """Scatter one token's k or v (n_slots, h, 1, hd) into the block pool
    (n_blocks, h, block_size, hd) at each slot's current position: block
    ``tables[slot, pos // bs]``, offset ``pos % bs``. Free slots (their
    table rows all GARBAGE_BLOCK, position 0) collide harmlessly in the
    garbage block — it is never read. No arithmetic on stored values."""
    import jax.numpy as jnp

    bi = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size
    return pool.at[bi, :, off].set(new[:, :, 0, :].astype(pool.dtype))


def write_token_scale_paged(scales, scale_new, positions, block_tables,
                            block_size):
    """Scale-array twin of :func:`write_token_kv_paged`:
    ``scales (n_blocks, h, block_size)``, ``scale_new (n_slots, h, 1)``."""
    import jax.numpy as jnp

    bi = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size
    return scales.at[bi, :, off].set(scale_new[:, :, 0])


def write_chunk_kv_paged(pool, new, positions, valid, table_row,
                         block_size):
    """Scatter one prefill CHUNK's k or v rows ``(1, h, C, hd)`` into
    the block pool at ``positions`` (C,) of the single slot owning
    ``table_row`` (mb,) — the chunked-prefill / prefix-suffix write
    (ISSUE 14). Invalid (pad) rows beyond the chunk's real token count
    are routed to the GARBAGE block (finite garbage, never read); valid
    rows land at (table[pos // bs], pos % bs) like the decode-step
    write. No arithmetic on stored values."""
    import jax.numpy as jnp

    mb = table_row.shape[0]
    blk = jnp.clip(positions // block_size, 0, mb - 1)
    bi = jnp.where(valid, table_row[blk], GARBAGE_BLOCK)
    off = positions % block_size
    rows = jnp.swapaxes(new[0], 0, 1)  # (h, C, hd) -> (C, h, hd)
    return pool.at[bi, :, off].set(rows.astype(pool.dtype))


def write_chunk_scale_paged(scales, scale_new, positions, valid,
                            table_row, block_size):
    """Scale-array twin of :func:`write_chunk_kv_paged`:
    ``scales (n_blocks, h, bs)``, ``scale_new (1, h, C)``."""
    import jax.numpy as jnp

    mb = table_row.shape[0]
    blk = jnp.clip(positions // block_size, 0, mb - 1)
    bi = jnp.where(valid, table_row[blk], GARBAGE_BLOCK)
    off = positions % block_size
    return scales.at[bi, :, off].set(jnp.swapaxes(scale_new[0], 0, 1))


def gather_paged_kv(pool, block_tables):
    """Materialize each slot's logical KV extent from the pool:
    ``(n_blocks, h, bs, hd)`` gathered through ``(n_slots, mb)`` tables →
    ``(n_slots, h, mb * bs, hd)`` in position order. This is the
    CPU fallback read (O(mb * bs) rows — the
    Pallas flash-decode kernel is the O(true_length) path); a pure
    gather, so the materialized rows are bitwise the stored rows."""
    import jax.numpy as jnp

    g = pool[block_tables]                 # (S, mb, h, bs, hd)
    g = jnp.swapaxes(g, 1, 2)              # (S, h, mb, bs, hd)
    return g.reshape(g.shape[0], g.shape[1], -1, g.shape[-1])


def gather_paged_scales(scales, block_tables):
    """(n_blocks, h, bs) through (n_slots, mb) → (n_slots, h, mb * bs)."""
    import jax.numpy as jnp

    g = scales[block_tables]               # (S, mb, h, bs)
    g = jnp.swapaxes(g, 1, 2)              # (S, h, mb, bs)
    return g.reshape(g.shape[0], g.shape[1], -1)


def paged_pool_entry(prefill_leaf, n_blocks: int, block_size: int,
                     kv_dtype: str):
    """Zeros-initialized pool (+ scales for int8) for one KV leaf whose
    per-request prefill shape is ``(1, h, max_len, hd)``. Returns the pool
    array for "native", ``(pool int8, scales f32)`` for "int8"."""
    import jax.numpy as jnp

    _, h, _L, hd = prefill_leaf.shape
    if kv_dtype == "int8":
        return (jnp.zeros((n_blocks, h, block_size, hd), jnp.int8),
                jnp.zeros((n_blocks, h, block_size), jnp.float32))
    return jnp.zeros((n_blocks, h, block_size, hd), prefill_leaf.dtype)


def scatter_prefill_paged(pool, prefill_leaf, table_row, block_size: int,
                          scales=None):
    """Insert one prefilled request's contiguous cache ``(1, h, max_len,
    hd)`` into its table row's pool blocks: it is padded to whole blocks,
    reshaped block-major and scattered at ``table_row`` (mb,) int32.
    Unused table entries point at GARBAGE_BLOCK and receive the cache's
    zero pad — harmless, never read. For int8 pools the rows are
    quantized here (``scales`` must be the matching scale array); fp
    pools store the rows bit-unchanged."""
    import jax.numpy as jnp

    x = prefill_leaf[0]                       # (h, L, hd)
    h, L, hd = x.shape
    mb = int(table_row.shape[0])
    pad = mb * block_size - L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    if scales is not None:
        q, s = quantize_kv(x)              # (h, P, hd), (h, P)
        qb = q.reshape(h, mb, block_size, hd).transpose(1, 0, 2, 3)
        sb = s.reshape(h, mb, block_size).transpose(1, 0, 2)
        return (pool.at[table_row].set(qb),
                scales.at[table_row].set(sb))
    xb = x.reshape(h, mb, block_size, hd).transpose(1, 0, 2, 3)
    return pool.at[table_row].set(xb.astype(pool.dtype)), None
