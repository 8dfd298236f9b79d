"""Pallas flash attention for TPU.

Replaces the reference's cuDNN ``cudnnMultiHeadAttnForward`` core
(src/ops/attention.cu:35-128) with a blockwise online-softmax kernel that never
materializes the (seq_q, seq_k) score matrix in HBM — the standard
FlashAttention recipe tiled for the MXU (128-aligned blocks) with VMEM
accumulators. Backward uses the recompute trick via ``jax.custom_vjp``: the
residuals are only (out, logsumexp), so long sequences fit in HBM.

Streaming grids (round 5): every kernel walks K/V (or Q) tiles through a
Pallas grid dimension instead of holding the full sequence resident in VMEM,
so per-program VMEM is O(block) — Pallas double-buffers the tile DMAs against
compute automatically and max sequence length is bounded by HBM, not VMEM.
Backward has two schedules:

- **fused one-pass** (``seq_q * d * 10 ≤ FUSED_BWD_RESIDENT_BUDGET``): grid
  over K/V tiles,
  Q/dO resident, dq accumulated in a (seq_q, d) f32 scratch. Computes the
  probabilities ONCE per (q, k) tile and reuses them for dq, dk and dv —
  vs. the two-pass schedule this halves the exp/VPU work and drops two of
  the six MXU passes (score + dO·Vᵀ recomputation).
- **two-pass streaming** (arbitrary seq): FlashAttention-2-style separate
  dkv and dq kernels, each O(block) VMEM, for sequences whose Q residency
  would not fit VMEM.

Numerics note: q is PRE-SCALED by 1/sqrt(d) outside the kernels (XLA fuses
the multiply into the producing projection). The fold is bit-exact in bf16
only when the scale is a power of two (d = 4^k, e.g. d=64/256); at d=128 and
d=192 — both admitted by the d % 64 == 0 flash gate — each q element takes
one extra bf16 rounding versus scaling the f32 score tile in-kernel. The
error is bounded by one bf16 ulp per element ahead of the f32 accumulation
and sits inside the parity tests' bf16 tolerances; see _flash_forward.

Off-TPU the attention op routes to the einsum core instead
(ops/attention._should_use_flash); tests run these kernels in interpret
mode."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from ._common import resolve_interpret as _resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# Fused-backward residency budget: Q/dO/O/dq-out (bf16) + dq scratch (f32)
# come to ~10*seq_q*d bytes; past this the schedule no longer fits the 16 MB
# VMEM scope next to the in-flight score tiles -> two-pass streaming.
# (5 MB == seq_q 8192 at d=64, 4096 at d=128.)
FUSED_BWD_RESIDENT_BUDGET = 5 * 2 ** 20
# Unroll the fused backward's q loop with STATIC slices up to this many
# tiles (dynamic-slice reads defeat the Mosaic vectorizer, ~10% on v5e).
MAX_UNROLL_QB = 16
# Widest k tile of the fused one-pass backward. Measured on the v5e inside
# the full train step (2026-09-26, jax 0.9.0 / libtpu 0.0.34): at s4096 d64 a
# (512, 1024) tile runs out of VMEM — the resident Q/dO/O/dq and the
# (seq_q, 1) lse block are lane-padded to 128 and double-buffered, which a
# byte count of the unpadded arrays (the r18 estimate this replaces) missed.
FUSED_BWD_MAX_BLOCK_K = 512
NEG_INF = -1e30


def dropout_keep_scale(seed, bh, q_start, k_start, block_q, block_k,
                       rate: float):
    """Counter-based dropout mask for one (block_q, block_k) score tile:
    {0, 1/(1-rate)} as f32, a pure function of the GLOBAL (seed, batch*head,
    q_pos, k_pos) coordinates — so the forward kernel and both backward
    kernels regenerate the SAME mask regardless of block decomposition
    (reference analog: cuDNN's dropout descriptor inside the fused MHA,
    src/ops/attention.cu:225). One murmur3-finalizer round per element over
    a linear counter; plain uint32 ops, so it runs identically compiled on
    TPU and in interpret mode."""
    import jax
    import jax.numpy as jnp

    qpos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return dropout_keep_scale_nd(seed, jnp.asarray(bh, jnp.uint32),
                                 qpos, kpos, rate)


def dropout_keep_scale_nd(seed, bh, q_pos, k_pos, rate: float):
    """Vectorized twin of ``dropout_keep_scale`` for the non-Pallas paths
    (ring/Ulysses sequence parallelism): ``bh``/``q_pos``/``k_pos`` are
    broadcastable uint32 arrays of GLOBAL coordinates, so every chip of an
    SP group draws decorrelated masks from the same counter stream."""
    import jax.numpy as jnp

    x = (q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         + bh.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
         + jnp.asarray(seed, jnp.uint32))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    threshold = jnp.uint32(min(int(rate * 2 ** 32), 2 ** 32 - 1))
    return (x >= threshold).astype(jnp.float32) / (1.0 - rate)


def coerce_dropout_seed(name: str, dropout: float, seed):
    """Shared validation + uint32 coercion for every dropout entry point
    (flash / ring / Ulysses) so the contract cannot drift."""
    import jax.numpy as jnp

    if not 0.0 <= float(dropout) < 1.0:
        raise ValueError(f"{name} dropout must be in [0, 1), got {dropout}")
    if dropout > 0.0 and seed is None:
        raise ValueError(f"{name} dropout requires a seed")
    return jnp.asarray(seed if seed is not None else 0, jnp.uint32)


def global_bh_indices(b_local: int, total_heads: int, h_local: int,
                      b_base, h_base):
    """(b_local, h_local) uint32 grid of GLOBAL batch*head indices for the
    dropout counter stream — one implementation shared by ring and Ulysses
    so their masks stay on the same stream as the flash kernel's."""
    import jax.numpy as jnp

    return ((b_base + jnp.arange(b_local))[:, None] * total_heads
            + h_base + jnp.arange(h_local)[None, :]).astype(jnp.uint32)


def _apply_causal_mask(s, q_start, k_start, offset, block_q, block_k,
                       window: Optional[int] = None):
    """Causal mask for one (block_q, block_k) score tile. ``offset`` aligns
    rectangular shapes the same way the einsum core's ``tril(k=sk-sq)`` does:
    query i attends keys j with j <= i + offset. With a sliding ``window``
    key j is visible to query i iff i - window < j <= i."""
    import jax
    import jax.numpy as jnp

    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    if window is None:
        return jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
    return jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s, NEG_INF)


# ---- the sliding window's band, in tiles. The windowed kernels walk only
# the tiles that meet the band: their inner grid dimension has the band's
# width in tiles (``_band_tiles``), grid step ``j`` stands for tile
# ``lo + j``, and steps past ``hi`` do nothing — their index maps clamp to
# ``hi``, so Pallas fetches no new tile for them.
def _band_kb(q_idx, block_q: int, block_k: int, window: int):
    """(first, last) key tile that query tile ``q_idx`` sees."""
    import jax.numpy as jnp

    lo = jnp.maximum(q_idx * block_q - (window - 1), 0) // block_k
    hi = (q_idx * block_q + block_q - 1) // block_k
    return lo, hi


def _band_qb(kb, block_q: int, block_k: int, window: int, num_qb: int):
    """(first, last) query tile that sees key tile ``kb``."""
    import jax.numpy as jnp

    lo = (kb * block_k) // block_q
    hi = jnp.minimum((kb * block_k + block_k - 1 + window - 1) // block_q,
                     num_qb - 1)
    return lo, hi


def _band_tiles(outer: int, inner: int, outer_block: int, inner_block: int,
                window: int, keys_inner: bool) -> int:
    """Static width of the band in inner tiles: the most inner tiles any
    outer tile meets (``keys_inner``: outer = query tiles, inner = key
    tiles; else the reverse)."""
    widest = 0
    for t in range(outer):
        if keys_inner:
            lo = max(t * outer_block - (window - 1), 0) // inner_block
            hi = (t * outer_block + outer_block - 1) // inner_block
        else:
            lo = (t * outer_block) // inner_block
            hi = min((t * outer_block + outer_block - 1 + window - 1)
                     // inner_block, inner - 1)
        widest = max(widest, hi - lo + 1)
    return widest


def _kv_head(h, group: int):
    """The K/V head that query head ``h`` reads (grouped-query attention:
    ``group`` query heads share one)."""
    return h if group == 1 else h // group


def _tile_contributes(q_idx, kb, block_q, block_k, offset):
    """Traced bool: does tile (q_idx, kb) intersect the causal band?
    True iff the tile's largest q_pos + offset reaches its smallest k_pos."""
    return q_idx * block_q + block_q - 1 + offset >= kb * block_k


def _first_contributing_qb(kb, block_q, block_k, offset):
    """Smallest q-block index intersecting the causal band for key block kb
    (tight: qb*block_q <= kb*block_k - offset < (qb+1)*block_q ⇒ the tile's
    last row reaches the band and qb-1's does not)."""
    import jax.numpy as jnp

    return jnp.maximum(kb * block_k - offset, 0) // block_q


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, num_kb: int, causal: bool,
                      causal_offset: int = 0,
                      dropout: float = 0.0, num_heads: int = 1,
                      window: Optional[int] = None):
    """Grid (batch, head, q_block, k_block), k innermost: one (q, k) score
    tile per program, online-softmax state (m, l, acc) carried across the k
    grid dimension in VMEM scratch (m/l lane-replicated to (block_q, 128)
    for layout). K/V tiles stream through the grid — Pallas double-buffers
    their DMAs — so VMEM residency is O(block), not O(seq_k). All tile
    accesses are static BlockSpec blocks: a register-carried
    fori_loop-over-pl.ds variant measured ~10% slower on v5e (dynamic-slice
    reads defeat the Mosaic vectorizer), so one tile per grid step it is.

    Q arrives PRE-SCALED by 1/sqrt(d) (folded into the projection by XLA),
    so no kernel multiplies the (block_q, block_k) score tile by sm_scale —
    that VPU pass (~270M multiplies/layer at seq 4096) is free."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    q_idx = pl.program_id(2)
    kb = pl.program_id(3)
    bh = pl.program_id(0) * num_heads + pl.program_id(1)
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    # ``num_kb`` is the k grid's extent: with a window, the band's width in
    # tiles, and grid step ``step`` stands for key tile ``lo + step``
    step = kb
    if window is not None:
        lo, hi = _band_kb(q_idx, block_q, block_k, window)
        kb = lo + step

    if num_kb == 1 and window is None:
        # single k block: the whole softmax row is in registers — skip the
        # scratch round-trip entirely (measured ~0.1 ms/layer at b8 s512)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, q_idx * block_q, 0, causal_offset,
                                   block_q, block_k)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            p = p * dropout_keep_scale(seed_ref[0], bh, q_idx * block_q, 0,
                                       block_q, block_k, dropout)
        acc = jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l_safe)).astype(lse_ref.dtype)
        return

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _tile():
        q = q_ref[0, 0]  # (block_q, d) — input dtype: bf16 feeds the MXU
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, q_idx * block_q, kb * block_k,
                                   causal_offset, block_q, block_k, window)
        m_prev = m_scr[...]  # (block_q, 128), lanes replicated
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        # softmax normalizer from UNDROPPED p: dropout applies to the
        # normalized probabilities, and elementwise mask/scale commutes
        # with the 1/l normalization
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            p = p * dropout_keep_scale(seed_ref[0], bh, q_idx * block_q,
                                       kb * block_k, block_q, block_k,
                                       dropout)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    if window is not None:
        pl.when(kb <= hi)(_tile)
    elif causal:
        @pl.when(_tile_contributes(q_idx, kb, block_q, block_k,
                                   causal_offset))
        def _run():
            _tile()
    else:
        _tile()

    @pl.when(step == num_kb - 1)
    def _final():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        # lse block is (block_q, 1): TPU tiling wants >=2-D blocks whose
        # minor dim matches the array
        lse_ref[0, 0] = (m_scr[:, :1] + jnp.log(l_safe)).astype(lse_ref.dtype)


def _window_suffix(window: Optional[int]) -> str:
    """The windowed kernels carry their own names, so that a trace tells
    them from the full-attention ones."""
    return "" if window is None else "_window"


def _compiler_params(interpret: bool, semantics):
    if interpret:
        return None
    import jax.experimental.pallas.tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics)


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, dropout: float = 0.0, seed=None,
                   window: Optional[int] = None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    # pre-scale q outside the kernel: XLA fuses the multiply into the
    # producing projection, and the per-score-element sm_scale VPU pass
    # disappears from the kernel. Exact when 1/sqrt(d) is a power of two
    # (d = 4^k: 1/8 at d=64, 1/16 at d=256). For d=128 (1/(8*sqrt(2))) and
    # d=192 the scale is NOT a power of two, so rounding the scaled q back
    # to bf16 costs ONE extra bf16 rounding per q element versus applying
    # sm_scale to the f32 score tile in-kernel — bounded by bf16 eps
    # (~0.4%) per element, before the f32 accumulation; the parity tests'
    # bf16 tolerances cover it.
    q = (q * np.float32(1.0 / np.sqrt(d))).astype(q.dtype)
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    seed_arr = jnp.reshape(jnp.asarray(
        seed if seed is not None else 0, jnp.uint32), (1,))

    num_kb = seq_k // block_k
    group = heads // k.shape[1]
    kv_tile = lambda b, h, i, j: (b, _kv_head(h, group), j, 0)  # noqa: E731
    if window is not None:
        num_kb = _band_tiles(seq_q // block_q, num_kb, block_q, block_k,
                             window, keys_inner=True)

        def kv_tile(b, h, i, j):
            lo, hi = _band_kb(i, block_q, block_k, window)
            return (b, _kv_head(h, group), jnp.minimum(lo + j, hi), 0)
    grid = (batch, heads, seq_q // block_q, num_kb)
    kernel = functools.partial(_flash_fwd_kernel, num_kb=num_kb,
                               causal=causal,
                               causal_offset=seq_k - seq_q, dropout=dropout,
                               num_heads=heads, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, i, j: (0,)),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_tile),
            pl.BlockSpec((1, 1, block_k, d), kv_tile),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd" + _window_suffix(window),
    )(seed_arr, q, k, v)
    return out, lse.reshape(batch, heads, seq_q)


def _flash_bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                            o_ref, dq_ref, dk_ref, dv_ref, dq_scr, *,
                            block_q: int, seq_q: int, num_kb: int,
                            causal: bool, sm_scale: float,
                            causal_offset: int = 0, dropout: float = 0.0,
                            num_heads: int = 1,
                            window: Optional[int] = None):
    """Fused one-pass backward, grid (batch, head, k_block): K/V tiles
    stream through the grid while Q/dO/lse/O stay resident per (b, h);
    dq accumulates in a (seq_q, d) f32 scratch carried across the k grid
    dimension and is flushed on the last k block. Each (q, k) tile computes
    the probabilities ONCE and derives dv, dk and dq from them — the
    two-pass schedule pays the score matmul, dO·Vᵀ matmul and the exp twice.
    δ = rowsum(dO∘O) is computed in-register from the resident tiles rather
    than as a separate HBM-roundtrip fusion before the kernel.

    Q arrives PRE-SCALED by 1/sqrt(d): s needs no scale, dk = dSᵀ·(q/√d)
    absorbs it exactly, and only the dq flush multiplies by sm_scale once.

    With dropout (mask D regenerated from the same counters as forward):
    dV = (P∘D)ᵀ dO and dS = P ∘ (D∘dP - δ) — δ = rowsum(dO∘O) already
    equals rowsum(P∘D ∘ dP), so the softmax-backward identity holds with
    the dropped probabilities folded in."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    bh = pl.program_id(0) * num_heads + pl.program_id(1)
    k = k_ref[0, 0]  # (block_k, d)
    v = v_ref[0, 0]
    block_k = k.shape[0]
    d = k.shape[1]

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    num_qb = seq_q // block_q
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)

    def body(qb, carry, sl=None):
        """One (q, k) tile; ``sl`` carries static slices when unrolled —
        dynamic-slice reads measurably defeat the Mosaic vectorizer."""
        dk, dv = carry
        if sl is None:
            sl = pl.ds(qb * block_q, block_q)
        q = q_ref[0, 0, sl, :]
        do = do_ref[0, 0, sl, :]
        lse = lse_ref[0, 0, sl, :]  # (bq, 1) f32
        o = o_ref[0, 0, sl, :]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, qb * block_q, kb * block_k,
                                   causal_offset, block_q, block_k, window)
        p = jnp.exp(s - lse)  # exact softmax probabilities from stored lse
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if dropout > 0.0:
            keep = dropout_keep_scale(seed_ref[0], bh, qb * block_q,
                                      kb * block_k, block_q, block_k,
                                      dropout)
            pd = p * keep
            dp = dp * keep
        else:
            pd = p
        dv = dv + jnp.dot(pd.astype(do.dtype).T, do,
                          preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jnp.dot(ds.astype(q.dtype).T, q,
                          preferred_element_type=jnp.float32)
        dq_scr[sl, :] = (dq_scr[sl, :]
                         + jnp.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32))
        return dk, dv

    if window is not None:
        # only the query tiles of the band
        qb_lo, qb_hi = _band_qb(kb, block_q, block_k, window, num_qb)
        dk, dv = jax.lax.fori_loop(qb_lo, qb_hi + 1, body, (dk0, dv0))
    elif causal:
        # the loop start is traced (depends on kb), so the static unroll
        # below does not apply; masked tiles would vanish numerically
        # (p == 0) but cost full compute, so keep the skip via fori_loop
        qb_start = _first_contributing_qb(kb, block_q, block_k,
                                          causal_offset)
        dk, dv = jax.lax.fori_loop(qb_start, num_qb, body, (dk0, dv0))
    elif num_qb <= MAX_UNROLL_QB:
        # non-causal: every tile contributes — unroll with static slices
        dk, dv = dk0, dv0
        for qb in range(num_qb):
            dk, dv = body(qb, (dk, dv),
                          sl=slice(qb * block_q, (qb + 1) * block_q))
    else:
        dk, dv = jax.lax.fori_loop(0, num_qb, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    @pl.when(kb == num_kb - 1)
    def _final():
        dq_ref[0, 0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                          num_qb: int, causal: bool,
                          causal_offset: int = 0, dropout: float = 0.0,
                          num_heads: int = 1, window: Optional[int] = None,
                          total_qb: int = 0):
    """Two-pass schedule, dkv kernel: grid (batch, head, k_block, q_block),
    q innermost. K/V tiles are resident per k block; Q/dO/lse/delta tiles
    stream through the q grid dimension; (dk, dv) accumulate in VMEM scratch
    carried across it (the FlashAttention-2 backward split, with O(block)
    VMEM for arbitrarily long sequences)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    qb = pl.program_id(3)
    bh = pl.program_id(0) * num_heads + pl.program_id(1)
    k = k_ref[0, 0]  # (block_k, d)
    v = v_ref[0, 0]
    block_k = k.shape[0]
    block_q = q_ref.shape[2]
    # ``num_qb`` is the q grid's extent: with a window, the band's width in
    # tiles, and grid step ``step`` stands for query tile ``lo + step``
    step = qb
    if window is not None:
        lo, hi = _band_qb(kb, block_q, block_k, window, total_qb)
        qb = lo + step

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _tile():
        q = q_ref[0, 0]  # pre-scaled by 1/sqrt(d): dk = dSᵀ·(q/√d) exactly
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1) f32
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, qb * block_q, kb * block_k,
                                   causal_offset, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if dropout > 0.0:
            keep = dropout_keep_scale(seed_ref[0], bh, qb * block_q,
                                      kb * block_k, block_q, block_k,
                                      dropout)
            pd = p * keep
            dp = dp * keep
        else:
            pd = p
        dv_scr[...] = dv_scr[...] + jnp.dot(
            pd.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32)

    if window is not None:
        pl.when(qb <= hi)(_tile)
    elif causal:
        @pl.when(_tile_contributes(qb, kb, block_q, block_k, causal_offset))
        def _run():
            _tile()
    else:
        _tile()

    @pl.when(step == num_qb - 1)
    def _final():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, num_kb: int,
                         causal: bool, sm_scale: float,
                         causal_offset: int = 0, dropout: float = 0.0,
                         num_heads: int = 1, window: Optional[int] = None):
    """Two-pass schedule, dq kernel: grid (batch, head, q_block, k_block),
    k innermost. Q/dO/lse/delta resident per q block; K/V tiles stream
    through the k grid dimension; dq accumulates in scratch."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qb = pl.program_id(2)
    kb = pl.program_id(3)
    bh = pl.program_id(0) * num_heads + pl.program_id(1)
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    step = kb  # as in the forward kernel: with a window, tile ``lo + step``
    if window is not None:
        lo, hi = _band_kb(qb, block_q, block_k, window)
        kb = lo + step

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _tile():
        q = q_ref[0, 0]  # pre-scaled by 1/sqrt(d)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (block_q, 1)
        delta = delta_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, qb * block_q, kb * block_k,
                                   causal_offset, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if dropout > 0.0:
            dp = dp * dropout_keep_scale(seed_ref[0], bh, qb * block_q,
                                         kb * block_k, block_q, block_k,
                                         dropout)
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    if window is not None:
        pl.when(kb <= hi)(_tile)
    elif causal:
        @pl.when(_tile_contributes(qb, kb, block_q, block_k, causal_offset))
        def _run():
            _tile()
    else:
        _tile()

    @pl.when(step == num_kb - 1)
    def _final():
        dq_ref[0, 0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool, dropout: float = 0.0,
                    seed=None, fused: Optional[bool] = None,
                    window: Optional[int] = None):
    """(dq, dk, dv). With fewer K/V heads than query heads the kernels write
    dk/dv per QUERY head (each reads its group's K/V head), in float32, and
    the group's sum is taken outside, in XLA."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    group = heads // k.shape[1]
    dkv_dtype = k.dtype if group == 1 else jnp.float32
    suffix = _window_suffix(window)

    def group_sum(dk, dv):
        if group == 1:
            return dk, dv
        return tuple(g.reshape(batch, heads // group, group, seq_k, d)
                     .sum(axis=2).astype(k.dtype) for g in (dk, dv))

    sm_scale = 1.0 / np.sqrt(d)
    # q pre-scaled as in the forward: the kernels recompute the identical s
    q = (q * np.float32(sm_scale)).astype(q.dtype)
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)

    dor = do.astype(q.dtype)
    lser = lse.reshape(batch, heads, seq_q, 1)
    seed_arr = jnp.reshape(jnp.asarray(
        seed if seed is not None else 0, jnp.uint32), (1,))

    seed_spec = pl.BlockSpec((1,), lambda *_: (0,))
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    if fused is None:
        fused = seq_q * d * 10 <= FUSED_BWD_RESIDENT_BUDGET

    if fused:
        # grid (b, h, kb): Q/dO/O resident, dq in (seq_q, d) scratch;
        # delta is computed in-kernel from the resident dO/O tiles
        full_q = pl.BlockSpec((1, 1, seq_q, d), lambda b, h, j: (b, h, 0, 0))
        full_q1 = pl.BlockSpec((1, 1, seq_q, 1), lambda b, h, j: (b, h, 0, 0))
        tile_k = pl.BlockSpec((1, 1, block_k, d), lambda b, h, j: (b, h, j, 0))
        tile_kv = tile_k if group == 1 else pl.BlockSpec(
            (1, 1, block_k, d), lambda b, h, j: (b, h // group, j, 0))
        kernel = functools.partial(
            _flash_bwd_fused_kernel, block_q=block_q, seq_q=seq_q,
            num_kb=num_kb, causal=causal, sm_scale=sm_scale,
            causal_offset=seq_k - seq_q, dropout=dropout, num_heads=heads,
            window=window)
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(batch, heads, num_kb),
            in_specs=[seed_spec, full_q, tile_kv, tile_kv, full_q, full_q1,
                      full_q],
            out_specs=[full_q, tile_k, tile_k],
            out_shape=[
                jax.ShapeDtypeStruct((batch, heads, seq_q, d), q.dtype),
                jax.ShapeDtypeStruct((batch, heads, seq_k, d), dkv_dtype),
                jax.ShapeDtypeStruct((batch, heads, seq_k, d), dkv_dtype),
            ],
            scratch_shapes=[pltpu.VMEM((seq_q, d), jnp.float32)],
            compiler_params=_compiler_params(
                interpret, ("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_attention_bwd_fused" + suffix,
        )(seed_arr, q, k, v, dor, lser, out)
        return (dq,) + group_sum(dk, dv)

    # two-pass streaming schedule: O(block) VMEM at any sequence length
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    q_of = lambda b, h, j, i: (b, h, i, 0)  # noqa: E731
    kv_of = lambda b, h, i, j: (b, _kv_head(h, group), j, 0)  # noqa: E731
    band_q, band_k = num_qb, num_kb  # the inner grids' extents
    if window is not None:
        band_q = _band_tiles(num_kb, num_qb, block_k, block_q, window,
                             keys_inner=False)
        band_k = _band_tiles(num_qb, num_kb, block_q, block_k, window,
                             keys_inner=True)

        def q_of(b, h, j, i):
            lo, hi = _band_qb(j, block_q, block_k, window, num_qb)
            return (b, h, jnp.minimum(lo + i, hi), 0)

        def kv_of(b, h, i, j):
            lo, hi = _band_kb(i, block_q, block_k, window)
            return (b, _kv_head(h, group), jnp.minimum(lo + j, hi), 0)
    tile_q_kv = pl.BlockSpec((1, 1, block_q, d), q_of)
    tile_q1_kv = pl.BlockSpec((1, 1, block_q, 1), q_of)
    res_k = pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0))
    res_kv = res_k if group == 1 else pl.BlockSpec(
        (1, 1, block_k, d), lambda b, h, j, i: (b, h // group, j, 0))
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, num_qb=band_q, causal=causal,
        causal_offset=seq_k - seq_q, dropout=dropout,
        num_heads=heads, window=window, total_qb=num_qb)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(batch, heads, num_kb, band_q),
        in_specs=[seed_spec, tile_q_kv, res_kv, res_kv, tile_q_kv,
                  tile_q1_kv, tile_q1_kv],
        out_specs=[res_k, res_k],
        out_shape=[jax.ShapeDtypeStruct((batch, heads, seq_k, d), dkv_dtype),
                   jax.ShapeDtypeStruct((batch, heads, seq_k, d), dkv_dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv" + suffix,
    )(seed_arr, q, k, v, dor, lser, delta)

    res_q = pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0))
    res_q1 = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    tile_k_q = pl.BlockSpec((1, 1, block_k, d), kv_of)
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, num_kb=band_k, causal=causal,
        sm_scale=sm_scale, causal_offset=seq_k - seq_q, dropout=dropout,
        num_heads=heads, window=window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(batch, heads, num_qb, band_k),
        in_specs=[seed_spec, res_q, tile_k_q, tile_k_q, res_q, res_q1,
                  res_q1],
        out_specs=res_q,
        out_shape=jax.ShapeDtypeStruct((batch, heads, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq" + suffix,
    )(seed_arr, q, k, v, dor, lser, delta)

    return (dq,) + group_sum(dk, dv)


def _reference_core(q, k, v, causal: bool, window: Optional[int] = None):
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_attention_p(q, k, v, seed, causal, block_q, block_k, interpret,
                       dropout, bwd_block_q, bwd_block_k, window):
    _check_shapes(q, k, causal, window)
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k,
                            _resolve_interpret(interpret),
                            dropout=dropout, seed=seed, window=window)
    return out


def _bwd_blocks(block_q: int, block_k: int, bwd_block_q, bwd_block_k,
                seq_q: int, seq_k: int, head_dim: Optional[int] = None):
    """Backward block defaults are SCHEDULE-AWARE:

    - Fused one-pass (seq_q*d*10 <= FUSED_BWD_RESIDENT_BUDGET): keeps three
      (block_q, block_k) f32 score-sized tiles in flight NEXT TO the
      resident Q/dO/O/dq, so block_k is capped at FUSED_BWD_MAX_BLOCK_K —
      (512, 512) timed the same 2.16 ms/layer as (512, 1024) standalone on
      v5e, and inside the train step the wider tile does not fit VMEM.
    - Two-pass streaming (past the residency budget): VMEM is O(block),
      so the k tile defaults to the full forward block — 1024-wide k tiles
      are the forward sweet spot and the long-context (8k-32k) backward
      spends its time streaming K/V, where wider tiles cut grid overhead.

    Without head_dim (legacy callers) the fused cap applies.

    Divisibility is re-checked against the sequences: a default that no
    longer divides seq_k falls back to the (valid) forward block, and an
    EXPLICIT non-dividing override raises — the grid floor-divisions would
    otherwise silently drop the tail keys from dk/dv/dq."""
    bq = bwd_block_q if bwd_block_q is not None else block_q
    if seq_q % min(bq, seq_q) != 0:
        if bwd_block_q is not None:
            raise ValueError(
                f"flash_attention bwd_block_q={bq} does not divide "
                f"sequence length {seq_q}")
        bq = block_q  # forward block divides by the public contract

    fused = head_dim is None or \
        seq_q * head_dim * 10 <= FUSED_BWD_RESIDENT_BUDGET
    k_default = min(block_k, FUSED_BWD_MAX_BLOCK_K) if fused else block_k

    bk = bwd_block_k if bwd_block_k is not None else k_default
    if seq_k % min(bk, seq_k) != 0:
        if bwd_block_k is not None:
            raise ValueError(
                f"flash_attention bwd_block_k={bk} does not divide "
                f"sequence length {seq_k}")
        bk = block_k
    return bq, bk


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None,
                    dropout: float = 0.0, seed=None,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    window: Optional[int] = None):
    """q,k,v: (batch, heads, seq, head_dim) -> (batch, heads, seq_q, head_dim).

    Grouped-query attention: k and v may carry fewer heads than q, a divisor
    of q's; query head ``n`` reads K/V head ``n // (q heads / kv heads)``,
    and dk/dv are summed over the group. ``window`` (causal self-attention,
    seq_q == seq_k): key j is visible to query i iff i - window < j <= i;
    the kernels walk only the tiles of the band and are named
    ``flash_attention_*_window``.

    seq_q/seq_k must be multiples of the block sizes (the attention op checks
    this before selecting the flash path, ops/attention.py). Causal requires
    seq_q <= seq_k: with more queries than keys the leading queries attend an
    empty window, which only the einsum core's degenerate uniform-softmax
    handles — use mha_core for that case.

    ``dropout``/``seed``: in-kernel attention-probability dropout via a
    counter-based PRNG on global (batch*head, q_pos, k_pos) coordinates, so
    forward and both backward schedules regenerate identical masks without
    materializing them in HBM (the cuDNN-MHA dropout analog,
    reference src/ops/attention.cu:225). ``seed`` is a traced uint32 scalar
    — reseed per step without recompiling."""
    dropout = float(dropout)
    seed = coerce_dropout_seed("flash_attention", dropout, seed)
    return _flash_attention_p(q, k, v, seed, causal, block_q, block_k,
                              interpret, dropout, bwd_block_q, bwd_block_k,
                              window)


def _check_shapes(q, k, causal: bool, window: Optional[int] = None) -> None:
    if causal and q.shape[-2] > k.shape[-2]:
        raise ValueError(
            f"flash_attention causal requires seq_q <= seq_k, got "
            f"{q.shape[-2]} > {k.shape[-2]}; use the einsum core instead")
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"flash_attention: {q.shape[1]} query heads are no multiple of "
            f"{k.shape[1]} key/value heads")
    if window is not None and not (causal and q.shape[-2] == k.shape[-2]
                                   and window >= 1):
        raise ValueError(
            "flash_attention: a sliding window needs causal self-attention "
            f"(seq_q == seq_k) and window >= 1, got causal={causal}, "
            f"seq_q={q.shape[-2]}, seq_k={k.shape[-2]}, window={window}")


def _fwd(q, k, v, seed, causal, block_q, block_k, interpret, dropout,
         bwd_block_q, bwd_block_k, window):
    _check_shapes(q, k, causal, window)
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              _resolve_interpret(interpret),
                              dropout=dropout, seed=seed, window=window)
    return out, (q, k, v, seed, out, lse)


def _bwd(causal, block_q, block_k, interpret, dropout, bwd_block_q,
         bwd_block_k, window, res, do):
    """Backward by recompute (never materializes the score matrix): blockwise
    Pallas kernels using the flash-attention backward identities, with exact
    probabilities reconstructed from the stored logsumexp (and the dropout
    mask regenerated from the same counters)."""
    q, k, v, seed, out, lse = res
    bq, bk = _bwd_blocks(block_q, block_k, bwd_block_q, bwd_block_k,
                         q.shape[-2], k.shape[-2], q.shape[-1])
    dq, dk, dv = _flash_backward(q, k, v, out, lse, do, causal, bq,
                                 bk, _resolve_interpret(interpret),
                                 dropout=dropout, seed=seed, window=window)
    return dq, dk, dv, None


_flash_attention_p.defvjp(_fwd, _bwd)
