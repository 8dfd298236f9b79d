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
JAX/TPU), and what an entry is — its shape, where K and V sit, how a
prefill's buffers become blocks, how rows are written and read back —
is known in this module alone:

* ONE pool of fixed-size KV blocks per attention node, ``(n_blocks,
  heads, block_size, kd + vd)``: a row is one head's K then V, side by
  side on the lanes. The width is the point. A TPU array rests in tiles
  of 128 lanes; a last dimension of 64 (GPT-2's head) is half a tile,
  so the chip's at-rest layout for a separate ``(.., 64)`` K pool puts
  the BLOCK axis minor-most, which no program computes in — every step
  copied the whole pool to the kernel's layout and back. Packed to 128
  lanes with ``block_size`` a whole sublane tile of the dtype, the
  at-rest layout IS the kernels' (``{3,2,1,0}``, no padding, the same
  bytes), and a program that donates the pool holds no copy of it
  (tests/test_kv_pool_in_place.py reads that from the compiled text).
  One layout for every head width and dtype; where the blocks do not
  fill whole tiles (tiny test widths) or off the chip, the same arrays
  are read by gather and written by scatter. Between the two, a pool of
  128 lanes whose ``block_size`` is 8 rows but not the dtype's whole
  tile (int8 at the default block 16, bf16 at block 8) is read by the
  kernel and written by scatter: the reader's gate asks for less than
  the writer's (kernels/flash_decode.py, kernels/kv_write.py).
* a per-slot **block table** ``(n_slots, max_blocks_per_slot)`` int32
  maps each slot's logical positions onto pool blocks. Prefill computes
  one request's contiguous ``(1, heads, max_len, kd | vd)`` buffers
  (:func:`prefill_kv_entry`) and the slot writer scatters them, whole
  blocks, into the request's blocks (:func:`scatter_prefill_kv`); each
  decode step writes ONE token at ``lengths[slot]`` and a prefill chunk
  its C rows, both through :func:`write_kv_rows` — on the chip the
  aliased Pallas call ``kv_write``, in place — and attention masks key
  positions ``> position``. Reads are the ``flash_decode`` kernel
  (:func:`flash_decode_kv`) or the gather :func:`read_kv`.

* the LATENT layout (a latent-attention node, ops/latent_attention.py):
  what is cached per token is ONE row for all heads — the compressed
  key/value ``c_kv`` and the shared rotary key ``k_r`` side by side —
  so the pool is ``(n_blocks, 1, block_size, lanes)`` with ``lanes``
  the row's width padded with zeros to whole 128-lane tiles
  (:func:`latent_lanes`; 576 -> 640, a ninth of the pool). It is the
  layout above with one head and no V: the value the decode read
  accumulates is the row's first lanes (``flash_decode``'s ``v_lanes``),
  so every function here takes ``v=None`` for it and the block tables,
  the allocator, the trie and the clone see blocks as before. int8 is
  not defined for it.

* the GROUPED layout (multi-head attention with fewer K/V heads than
  query heads): the pool is ``(n_blocks, kv_heads, block_size, vd +
  kd)`` and a row rests a K/V head's **V then K**. The decode read is
  then the latent read as it stands: a K/V head's whole group of query
  rows scores the stored row in one grid step under a query that is
  zero over V's lanes, and the value is the row's first ``vd`` lanes
  (``flash_decode``'s ``v_lanes``). Every function that packs or
  splits a row takes ``v_first`` for it; the block tables, the
  allocator, the trie and the clone see blocks as before. int8 is not
  defined for it.

* the SLOT-MAJOR kind (a recurrent node: the LSTM carry, a state-space
  mixer's state and conv tail, a gated delta-rule mixer's matrix state a
  head and conv tails): a fixed size a REQUEST whatever its
  context, one row a slot (``(n_slots, ...)`` leaves), overwritten
  whole at admission (:func:`update_slot_entry`), priced a slot by
  :func:`node_slot_bytes` as the pool is priced a token by
  :func:`node_token_bytes`.

Pad garbage beyond a prompt's true length is never read: the write
cursor overwrites it before the mask ever exposes it.
Slot recycling and prefix sharing are pointer bookkeeping in the
host-side :class:`~flexflow_tpu.serving.scheduler.BlockAllocator`
(prefix sharing delivered by ISSUE 14's radix-tree cache,
serving/prefix.py: shared blocks are refcounted, divergent writes clone
first — copy-on-write, :func:`clone_kv_block`); pool occupancy decouples
from ``max_len`` (a short request holds few blocks); and the
single-compile decode contract holds — block tables are just another
int32 array in the jitted signature. Block index 0 is the reserved
GARBAGE block: every unused table entry points at it, free slots and a
chunk's pad rows write their (discarded) tokens into it, and the
attention mask guarantees it is never read — so its contents only ever
need to stay FINITE (``0 * garbage`` must be exactly ``0.0``: the
kernel's zero-padded query leans on the same; the chaos poisoner
deliberately never NaNs it).

Quantized layout (``kv_dtype="int8"``): the pool stores symmetric
per-(token, head) int8 rows, K's and V's quantized apart, and the entry
is ``(pool, scales)`` with f32 ``scales (n_blocks, 2, heads,
block_size)``, K's then V's — scale = amax/127 over the head_dim row,
written once with the row and folded back on read. fp layouts are held
to the whole-sequence forward within the stated tolerance
(tests/serving_oracle.py); int8 is judged against a pinned tolerance
band (tests/test_decode_paged.py).
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

    def advanced_lengths(self):
        """The cursors after one decode step: a live slot's grows by
        one, a free slot's stays 0 (:func:`live_slots`) — so a free
        slot never indexes the position table past its end, and its
        discarded token lands at offset 0 of the garbage block however
        long the slot stays free."""
        import jax.numpy as jnp

        return jnp.where(live_slots(self.block_tables), self.lengths + 1, 0)


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


def latent_lanes(width: int) -> int:
    """Lanes of a latent pool row: ``width`` padded to whole 128-lane
    tiles, so that the pool rests in the kernels' layout."""
    return -(-int(width) // 128) * 128


def latent_token_bytes(width: int, el: int) -> int:
    """Bytes ONE token costs in one latent node's pool: its stored row,
    padding included — once a token and layer, whatever the head count
    (:func:`kv_token_bytes` of one head whose K is the row and whose V
    is inside it)."""
    return kv_token_bytes(1, latent_lanes(width), 0, el)


def node_token_bytes(op, kv_dtype: str = "native", el: int = 0) -> int:
    """What ONE cached token costs in the pool of the attention node
    ``op``, or 0 for an op that caches none: K/V heads x (K + V) for
    multi-head attention, the one stored row for latent attention. The
    engine's ``kv_bytes_read`` and the serving search price from here.
    ``el``: bytes of an element as the pool rests it — the compute
    dtype's where the graph computes in a reduced one (the engine says);
    the node's own dtype otherwise."""
    from ..ffconst import OperatorType, size_of_datatype

    a, el = op.attrs, el or size_of_datatype(op.data_type)
    if op.op_type == OperatorType.OP_LATENT_ATTENTION:
        return latent_token_bytes(int(a["kv_rank"]) + int(a["rope_dim"]),
                                  el)
    if op.op_type != OperatorType.OP_MULTIHEAD_ATTENTION:
        return 0
    heads = int(a.get("num_heads", 1))
    return kv_token_bytes(int(a.get("num_kv_heads") or heads),
                          int(a.get("kdim") or a["embed_dim"] // heads),
                          int(a.get("vdim") or a["embed_dim"] // heads),
                          el, kv_dtype)


def tiled_bytes(shape, itemsize: int) -> int:
    """Bytes an array of ``shape`` rests in on the chip: its last dimension
    in whole 128-lane tiles and the one before in whole sublane tiles (8
    rows of 4 bytes; 16 of 2; 32 of 1), the padding counted."""
    dims = [int(d) for d in shape]
    if dims:
        dims[-1] = latent_lanes(dims[-1])
    if len(dims) > 1:
        sub = 8 * max(1, 4 // int(itemsize))
        dims[-2] = -(-dims[-2] // sub) * sub
    return int(np.prod(dims, dtype=np.int64)) * int(itemsize)


def is_recurrent(op) -> bool:
    """Does ``op`` carry a recurrent state — a summary of the whole
    prefix, one row a slot — rather than per-token pool rows? Such a
    state has no block to share and no position to resume from: the
    engine serves a graph that holds one without chunked prefill and
    without the prefix cache (ROADMAP.md, Reach R8). The op says
    (``Op.slot_state_bytes``)."""
    return op.slot_state_bytes() > 0


def node_slot_bytes(op, el: int = 0) -> int:
    """What ONE slot costs in the slot-major state of the recurrent node
    ``op``, or 0 for an op that holds none: the op's own answer
    (``Op.slot_state_bytes``: the LSTM's ``[h, c]``; a state-space mixer's
    float32 state and conv tail; a gated delta-rule mixer's matrix state a
    head and three conv tails), ``el`` bytes an element as
    :func:`node_token_bytes`. The engine's ``recurrent_state_bytes`` and
    the serving search price from here."""
    return op.slot_state_bytes(el)


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


def new_kv_pool(prefill_entry, n_blocks: int, block_size: int,
                kv_dtype: str):
    """Zeros-initialized pool entry for one attention node, from the
    structure of its prefill entry (:func:`prefill_kv_entry`): the one
    pool ``(n_blocks, h, block_size, kd + vd)`` for "native"; for "int8"
    ``(pool int8, scales f32 (n_blocks, 2, h, block_size))``."""
    import jax.numpy as jnp

    if len(prefill_entry) == 1:  # the latent layout: one row, no V
        if kv_dtype == "int8":
            raise NotImplementedError(
                "kv_dtype='int8' is not defined for a latent KV pool")
        (buf,) = prefill_entry
        return jnp.zeros((n_blocks, buf.shape[1], block_size,
                          latent_lanes(buf.shape[-1])), buf.dtype)
    kbuf, vbuf = prefill_entry
    h = kbuf.shape[1]
    shape = (n_blocks, h, block_size, kbuf.shape[-1] + vbuf.shape[-1])
    if kv_dtype == "int8":
        return (jnp.zeros(shape, jnp.int8),
                jnp.zeros((n_blocks, 2, h, block_size), jnp.float32))
    return jnp.zeros(shape, kbuf.dtype)


def _stored_order(k, v, v_first: bool):
    """The two halves of a row in the order they rest on the lanes: K
    then V, or V then K in the grouped layout."""
    return (v, k) if v_first else (k, v)


def prefill_kv_entry(k, v, max_len: int, v_first: bool = False):
    """What a prefill hands the slot writer for one attention node: the
    request's k ``(1, h, L, kd)`` and v ``(1, h, L, vd)`` at position 0
    of contiguous zeroed ``max_len`` buffers, unquantized and in stored
    order — :func:`scatter_prefill_kv` turns them into pool blocks."""
    import jax.numpy as jnp

    pad = ((0, 0), (0, 0), (0, max_len - k.shape[2]), (0, 0))
    if v is None:  # the latent layout: k is the row ``(1, 1, L, width)``
        return (jnp.pad(k, pad),)
    first, second = _stored_order(k, v, v_first)
    return jnp.pad(first, pad), jnp.pad(second, pad)


def is_prefill_kv_entry(entry) -> bool:
    """Is a prefill's cache entry attention K and V — the pageable kind,
    4-D per-request buffers ``(1, h, max_len, kd | vd)``? Everything
    else (the LSTM carry ``(1, 2h)``) stays slot-major."""
    import jax

    leaves = jax.tree_util.tree_leaves(entry)
    return bool(leaves) and all(
        getattr(leaf, "ndim", 0) == 4 for leaf in leaves)


def _pool_scales(entry):
    """``(pool, scales)`` of a pool entry; ``scales`` is None for a
    "native" entry, which is the bare pool."""
    return entry if isinstance(entry, tuple) else (entry, None)


def _pack_rows(entry, k, v):
    """k ``(..., kd)`` and v ``(..., vd)`` as stored rows ``(..., kd +
    vd)`` in the pool's dtype, and for an int8 entry their f32 scales
    ``(..., 2)``; fp rows are stored bit-unchanged."""
    import jax.numpy as jnp

    pool, scales = _pool_scales(entry)
    if v is None:  # a latent row: zeros over the pool's padding lanes
        pad = [(0, 0)] * (k.ndim - 1) + [(0, pool.shape[-1] - k.shape[-1])]
        return jnp.pad(k, pad).astype(pool.dtype), None
    if scales is None:
        return jnp.concatenate([k, v], axis=-1).astype(pool.dtype), None
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return (jnp.concatenate([kq, vq], axis=-1),
            jnp.stack([ks, vs], axis=-1))


def write_kv_rows(pool, rows, block_ids, offsets, *,
                  consecutive: bool = False, interpret: bool = False):
    """THE pool write: ``pool[block_ids[n], :, offsets[n]] = rows[n]``
    for ``rows (N, h, lanes)`` in the pool's dtype. No arithmetic on
    stored values, and every other byte of the pool is left as it was.

    On the chip (and under ``interpret``, the tests' way in) it is the
    aliased Pallas call ``kv_write`` (kernels/kv_write.py), which
    rewrites whole blocks, one a grid step, and loses the first of two
    steps' rows when both name the same block. So:

    * ``consecutive=False`` (the decode step: one row a slot) takes one
      step a row. Live slots never share a writable block (copy-on-
      write); free slots meet in the garbage block, where any finite
      row will do. Only the chip shows the loss — the interpreter and
      the scatter below write both rows — so a caller that could name a
      real block twice in one call (several tokens of one slot) must
      take ``consecutive=True`` or its own grouping;
      tests/test_serving_paths.py reads the engine's calls for it and
      ``chip_smoke.py::check_kv_write`` holds the call on the chip.
    * ``consecutive=True`` (a prefill chunk) promises ``offsets[n] ==
      (offsets[0] + n) % block_size`` — the rows are successive
      positions of one slot — and takes one step a BLOCK: the rows are
      staged ``block_size`` at a time and each step merges every row
      that names its block. Steps left without a row are sent to the
      garbage block, never to a block another step writes.

    Elsewhere it is the scatter the kernel replaces, which is also the
    tests' oracle."""
    import jax.numpy as jnp

    from ..kernels.kv_write import kv_write, use_kv_write

    if not (interpret or use_kv_write(pool)):
        return pool.at[block_ids, :, offsets].set(rows)
    if not consecutive:
        return kv_write(pool, rows[:, :, None, :], block_ids, offsets,
                        offsets + 1, interpret=interpret)
    n, bs = rows.shape[0], pool.shape[2]
    steps = (n + bs - 2) // bs + 1  # blocks n successive rows can touch
    # r[j, t]: which of the n rows sits at offset t of the j-th block
    r = (jnp.arange(steps)[:, None] * bs + jnp.arange(bs)[None, :]
         - offsets[0])
    rc = jnp.clip(r, 0, n - 1)
    bid = block_ids[rc[:, 0]]  # the block of a step's first row
    mine = (r >= 0) & (r < n) & (block_ids[rc] == bid[:, None])
    lo = jnp.argmax(mine, axis=1)
    count = jnp.sum(mine, axis=1)
    staged = jnp.swapaxes(rows[rc], 1, 2)  # (steps, h, bs, lanes)
    return kv_write(pool, staged,
                    jnp.where(count > 0, bid, GARBAGE_BLOCK), lo,
                    lo + count, interpret=interpret)


def _write_entry(entry, k, v, block_ids, offsets, consecutive):
    """Rows k ``(N, h, kd)`` / v ``(N, h, vd)`` into a pool entry at
    ``(block_ids, offsets)``; int8 entries quantize per (token, head)
    and write the two scales beside the row."""
    pool, scales = _pool_scales(entry)
    rows, srows = _pack_rows(entry, k, v)
    pool = write_kv_rows(pool, rows, block_ids, offsets,
                         consecutive=consecutive)
    if scales is None:
        return pool
    # (N, h, 2) -> (N, 2, h) at scales[block, :, :, offset]
    return pool, scales.at[block_ids, :, :, offsets].set(
        srows.swapaxes(1, 2))


def write_token_kv(entry, k, v, positions, block_tables, block_size,
                   v_first: bool = False):
    """Write one token's k ``(n_slots, h, 1, kd)`` and v ``(n_slots, h,
    1, vd)`` into the pool at each slot's current position: block
    ``tables[slot, pos // bs]``, offset ``pos % bs``. Free slots (their
    table rows all GARBAGE_BLOCK, position 0) collide harmlessly in the
    garbage block — it is never read."""
    import jax.numpy as jnp

    if v is not None:
        k, v = _stored_order(k, v, v_first)
    bi = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    return _write_entry(entry, k[:, :, 0, :],
                        None if v is None else v[:, :, 0, :], bi,
                        positions % block_size, consecutive=False)


def write_chunk_kv(entry, k, v, start, n_new, table_row, block_size):
    """Write one prefill CHUNK's k ``(1, h, C, kd)`` and v ``(1, h, C,
    vd)`` at positions ``start + arange(C)`` of the single slot owning
    ``table_row`` (mb,) — the chunked-prefill / prefix-suffix write
    (ISSUE 14). Pad rows beyond the chunk's ``n_new`` real tokens are
    routed to the GARBAGE block (finite garbage, never read); real rows
    land at (table[pos // bs], pos % bs) like the decode-step write."""
    import jax.numpy as jnp

    chunk_len = k.shape[2]
    pos = start + jnp.arange(chunk_len, dtype=jnp.int32)
    blk = jnp.clip(pos // block_size, 0, table_row.shape[0] - 1)
    bi = jnp.where(jnp.arange(chunk_len) < n_new, table_row[blk],
                   GARBAGE_BLOCK)
    return _write_entry(entry, jnp.swapaxes(k[0], 0, 1),
                        None if v is None else jnp.swapaxes(v[0], 0, 1),
                        bi, pos % block_size, consecutive=True)


def read_kv(entry, block_tables, kdim: int, dtype, v_first: bool = False):
    """Materialize each slot's logical KV extent from the pool entry
    through ``(n_slots, mb)`` tables: ``(k, v)`` as ``(n_slots, h, mb *
    bs, kd | vd)`` in position order and in ``dtype`` (``v_first``: the
    grouped layout, whose rows rest V then K). This is the
    gather read (O(mb * bs) rows — the Pallas flash-decode kernel is
    the O(true_length) path); fp rows come back bitwise the stored rows,
    int8 rows with their per-(token, head) scale folded back."""
    import jax.numpy as jnp

    pool, scales = _pool_scales(entry)
    g = jnp.swapaxes(pool[block_tables], 1, 2)   # (S, h, mb, bs, lanes)
    g = g.reshape(g.shape[0], g.shape[1], -1, g.shape[-1])
    if v_first:
        vdim = g.shape[-1] - kdim
        return g[..., vdim:].astype(dtype), g[..., :vdim].astype(dtype)
    kc, vc = g[..., :kdim], g[..., kdim:]
    if scales is None:
        return kc.astype(dtype), vc.astype(dtype)
    s = jnp.moveaxis(scales[block_tables], 1, 3)  # (S, 2, h, mb, bs)
    s = s.reshape(s.shape[:3] + (-1,))
    return (dequantize_kv(kc, s[:, 0], dtype),
            dequantize_kv(vc, s[:, 1], dtype))


def live_slots(block_tables):
    """``(n_slots,)`` bool: the slots whose table row maps a pool block.
    A row that is all GARBAGE_BLOCK is a free slot — freed, never
    admitted, or still being chunk-prefilled (its row is set when the
    last chunk lands). Read from the row, which every slot-freeing path
    clears at once, and not from the cursor, which the one-deep
    pipeline may have advanced past the clear."""
    import jax.numpy as jnp

    return jnp.any(block_tables != GARBAGE_BLOCK, axis=1)


def flash_decode_kv(q, entry, block_tables, n_keys, sm_scale,
                    v_lanes=None, tokens: int = 1, v_first: bool = False):
    """The kernel read of a pool entry (kernels/flash_decode.py): q
    ``(n_slots, h, kd)`` against each slot's ``n_keys`` first keys →
    ``(n_slots, h, vd)``; None where the gate says the gather read
    (:func:`read_kv`) is the path — off the chip, or a pool that is not
    whole lanes (128) and whole sublanes (8 rows a block). ``v_lanes`` names the
    latent layout: q is every head's row against the one stored row a
    key, the output its first ``v_lanes`` lanes; ``tokens`` > 1 is a
    latent prefill chunk's read (``tokens`` positions a slot, one shared
    table row, the caller's ``n_keys`` as they are). ``v_first`` names
    the grouped layout: the query rides zero-padded over V's lanes in
    front of it and the read is the latent one, a K/V head's group of
    query rows a grid step. A free slot
    (:func:`live_slots`) is handed ``n_keys`` 0: the kernel runs no live
    step for it, moves no bytes and writes exact zeros."""
    import jax.numpy as jnp

    from ..kernels.flash_decode import flash_decode_pool, use_flash_decode

    pool, scales = _pool_scales(entry)
    if not use_flash_decode(pool.shape[-1], pool.shape[2]):
        return None
    if tokens == 1:
        n_keys = jnp.where(live_slots(block_tables), n_keys, 0)
    if v_first:
        v_lanes = pool.shape[-1] - q.shape[-1]
        q = jnp.pad(q, ((0, 0), (0, 0), (v_lanes, 0)))
    return flash_decode_pool(q, pool, block_tables, n_keys,
                             sm_scale=sm_scale, scales=scales,
                             v_lanes=v_lanes, tokens=tokens)


def scatter_prefill_kv(entry, prefill_entry, table_row, block_size: int):
    """Insert one prefilled request's contiguous cache
    (:func:`prefill_kv_entry`) into its table row's pool blocks: it is
    padded to whole blocks, packed, reshaped block-major and scattered
    at ``table_row`` (mb,) int32 — whole blocks, so the scatter is in
    place whatever the layout. Unused table entries point at
    GARBAGE_BLOCK and receive the cache's zero pad — harmless, never
    read. int8 entries quantize the rows here; fp pools store them
    bit-unchanged."""
    import jax.numpy as jnp

    pool, scales = _pool_scales(entry)
    kbuf, vbuf = prefill_entry if len(prefill_entry) == 2 \
        else (prefill_entry[0], None)
    mb = int(table_row.shape[0])
    pad = ((0, 0), (0, mb * block_size - kbuf.shape[2]), (0, 0))
    rows, srows = _pack_rows(
        entry, jnp.pad(kbuf[0], pad),
        None if vbuf is None else jnp.pad(vbuf[0], pad))  # (h, P, lanes)
    h, _p, lanes = rows.shape
    pool = pool.at[table_row].set(
        rows.reshape(h, mb, block_size, lanes).transpose(1, 0, 2, 3))
    if scales is None:
        return pool
    # (h, P, 2) -> (mb, 2, h, bs)
    return pool, scales.at[table_row].set(
        srows.reshape(h, mb, block_size, 2).transpose(1, 3, 0, 2))


def clone_kv_block(entry, src, dst):
    """Copy pool block ``src`` onto ``dst`` (traced ids) in every array
    of the entry — the copy-on-write clone; ``src`` is read only."""
    import jax

    return jax.tree.map(lambda a: a.at[dst].set(a[src]), entry)
