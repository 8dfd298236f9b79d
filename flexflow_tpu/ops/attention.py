"""Multi-head attention.

Reference: src/ops/attention.cc (926 LoC) using cuDNN's packed
``cudnnMultiHeadAttnForward`` (attention.cu:35-128). TPU-native: separate
q/k/v/o projections (MXU matmuls) + scaled-dot-product core. The core runs
either as plain einsums (XLA fuses + tiles) or the Pallas flash-attention
kernel (kernels/flash_attention.py) for long sequences — selected at lowering
time, not by the user.

Parallelism: shardable over batch (sample) and heads (the reference's
attribute parallelism, substitution.cc:3169 create_partition_attention_combine)
by sharding the head dim of the projection weights; sequence parallelism /
ring attention is provided by the RING_ATTENTION variant (parallel extension,
absent in the reference — SURVEY §5 long-context).
"""
from __future__ import annotations


import numpy as np

from ..ffconst import OperatorType
from .base import Op, OpContext, register_op


def mha_core(q, k, v, *, causal: bool = False, dropout: float = 0.0,
             rng=None, training: bool = False, attn_mask=None,
             scale: float = None, window: int = None):
    """q,k,v: (batch, heads, seq, head_dim) -> (batch, heads, seq_q, head_dim).
    attn_mask: optional additive mask broadcastable to (b, h, seq_q, seq_k).
    k/v may carry fewer heads than q (grouped-query: query head n reads K/V
    head n // group); ``window`` (causal): key j is visible to query i iff
    i - window < j <= i."""
    import jax
    import jax.numpy as jnp

    head_dim = q.shape[-1]
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scale = scale if scale is not None else 1.0 / np.sqrt(head_dim)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if attn_mask is not None:
        if jnp.issubdtype(attn_mask.dtype, jnp.bool_):
            # torch bool-mask semantics: True = attend, False = -inf
            logits = jnp.where(attn_mask, logits, -1e30)
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool),
                              k=sk - sq - window)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if training and dropout > 0.0 and rng is not None:
        keep = 1.0 - dropout
        probs = probs * jax.random.bernoulli(rng, keep, probs.shape) / keep
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


@register_op(OperatorType.OP_MULTIHEAD_ATTENTION)
class MultiHeadAttentionOp(Op):
    """attrs: embed_dim, num_heads, kdim, vdim, dropout, bias, add_bias_kv,
    add_zero_attn, causal, use_flash (builder: FFModel::multihead_attention,
    reference model.h:520-537).

    Decoder-block attributes, each absent by default (the op is then the one
    above): ``num_kv_heads`` (grouped-query: fewer K/V heads than query
    heads), ``window`` (causal sliding window), ``rope_theta`` (rotary
    positions on q and k, rotate-half pairing), ``qk_norm`` (an RMS norm
    per head on q and on k, one gain vector each; its value is the eps),
    ``qk_norm_whole`` (that norm over the WHOLE q and k width instead —
    every head at once, a gain a channel: the OLMo family's),
    ``gated`` (the output of the core times sigmoid(x Wg), per head).

    inputs: (query, key, value), each (batch, seq, dim).
    output: (batch, seq_q, embed_dim).
    """

    def _dims(self):
        a = self.attrs
        embed = a["embed_dim"]
        heads = a["num_heads"]
        kdim = a.get("kdim") or embed // heads
        vdim = a.get("vdim") or embed // heads
        return embed, heads, kdim, vdim

    def infer_output_shapes(self, input_shapes):
        q = input_shapes[0]
        return [(q[0], q[1], self.attrs["embed_dim"])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (DefaultBiasInitializer,
                                              DefaultWeightInitializer)

        embed, heads, kdim, vdim = self._dims()
        q_in = input_shapes[0][-1]
        k_in = input_shapes[1][-1]
        v_in = input_shapes[2][-1]
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        kv_heads = self.attrs.get("num_kv_heads") or heads
        specs = {
            "wq": ((q_in, heads, kdim), self.data_type, init),
            "wk": ((k_in, kv_heads, kdim), self.data_type, init),
            "wv": ((v_in, kv_heads, vdim), self.data_type, init),
            "wo": ((heads, vdim, embed), self.data_type, init),
        }
        if self.attrs.get("bias", True):
            specs["bo"] = ((embed,), self.data_type, DefaultBiasInitializer())
        if self.attrs.get("gated"):
            specs["wg"] = ((q_in, heads, vdim), self.data_type, init)
        if self.attrs.get("qk_norm"):
            from ..execution.initializers import ConstantInitializer

            # a gain a head channel, or a channel of the whole width
            whole = self.attrs.get("qk_norm_whole")
            specs["q_norm"] = ((heads, kdim) if whole else (kdim,),
                               self.data_type, ConstantInitializer(1.0))
            specs["k_norm"] = ((kv_heads, kdim) if whole else (kdim,),
                               self.data_type, ConstantInitializer(1.0))
        return specs

    def forward(self, params, inputs, ctx: OpContext):
        import jax
        import jax.numpy as jnp

        q_in, k_in, v_in = inputs
        # NOTE: a packed q/k/v projection (one concat-weight matmul, like the
        # reference's cuDNN MHA packed weight, attention.cu:225) was measured
        # SLOWER on v5e (81.5 ms vs 72.9 ms step) — the runtime concat +
        # split copies outweigh the single-matmul win; XLA already schedules
        # the three projections back-to-back on the MXU.
        q = jnp.einsum("bsd,dhk->bhsk", q_in, params["wq"])
        k = jnp.einsum("bsd,dhk->bhsk", k_in, params["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", v_in, params["wv"])
        if self.attrs.get("qk_norm"):
            eps = float(self.attrs["qk_norm"])
            norm = _whole_rms_norm if self.attrs.get("qk_norm_whole") \
                else _head_rms_norm
            q = norm(q, params["q_norm"], eps)
            k = norm(k, params["k_norm"], eps)
        if self.attrs.get("rope_theta"):
            with jax.named_scope(_inner_scope(self.name, "rope")):
                q, k = (_rotate_half_rope(t, float(self.attrs["rope_theta"]))
                        for t in (q, k))
        window = self.attrs.get("window")
        use_flash = self.attrs.get("use_flash", "auto")
        causal = self.attrs.get("causal", False)
        seq_axis = self.attrs.get("sequence_parallel_axis")
        dropout = self.attrs.get("dropout", 0.0)
        live_dropout = _resolve_live_dropout(dropout, ctx)
        seed = _dropout_seed(ctx.rng) if live_dropout else None
        plain = k.shape[1] == q.shape[1] and window is None
        if ctx.serving is not None:
            # serving engine prefill/decode (ISSUE 6): the KV pool is
            # the execution path, selected before any kernel routing —
            # decode shapes (seq 1) must never reach flash/ring
            if window is not None or self.attrs.get("rope_theta"):
                raise NotImplementedError(
                    f"{self.name}: multihead_attention on the serving path "
                    "holds whole-context attention (grouped K/V heads "
                    "included) with positions from the graph or none; its "
                    "sliding window and rotary positions run on the "
                    "training path only (ROADMAP.md, Reach R3 (a)-(c)). "
                    "Rotary positions from the serving context are "
                    "latent_attention's (ops/latent_attention.py)")
            out = _serving_attention(self.name, q, k, v, ctx.serving,
                                     causal=causal)
        elif seq_axis and ctx.mesh is not None and seq_axis in ctx.mesh.shape:
            if not plain:
                raise NotImplementedError(
                    f"{self.name}: sequence-parallel attention (ring, "
                    "all-to-all) holds as many K/V heads as query heads and "
                    "whole-context attention; a strategy that shards the "
                    "sequence of a grouped or windowed attention is refused "
                    "rather than run unsharded")
            if self.attrs.get("sequence_parallel_mode") == "alltoall":
                from ..kernels.ulysses_attention import ulysses_attention

                out = ulysses_attention(q, k, v, ctx.mesh, seq_axis=seq_axis,
                                        causal=causal,
                                        dropout=live_dropout, seed=seed)
            else:  # default schedule: ring rotation over ICI
                from ..kernels.ring_attention import ring_attention

                out = ring_attention(q, k, v, ctx.mesh, seq_axis=seq_axis,
                                     causal=causal,
                                     dropout=live_dropout, seed=seed)
        elif _should_use_flash(use_flash, q, k, causal) \
                and _flash_blocks(q.shape[-2], k.shape[-2]) is not None:
            out = _flash_on_mesh(q, k, v, causal, live_dropout, seed,
                                 ctx.mesh, window=window)
        else:
            # the already-resolved live_dropout is the single gate (the r5
            # warning path); rng only rides along when dropout is live, so
            # _resolve_live_dropout cannot be second-guessed downstream
            out = mha_core(q, k, v, causal=causal, dropout=live_dropout,
                           rng=ctx.rng if live_dropout else None,
                           training=ctx.training, window=window)
        if "wg" in params:
            gate = jnp.einsum("bsd,dhk->bhsk", q_in, params["wg"])
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                out.dtype)
        y = jnp.einsum("bhsv,hvd->bsd", out, params["wo"],
                       preferred_element_type=jnp.float32).astype(q_in.dtype)
        if "bo" in params:
            y = y + params["bo"]
        return [y]

    def flops(self, input_shapes, output_shapes):
        b, sq, _ = input_shapes[0]
        sk = input_shapes[1][1]
        embed, heads, kdim, vdim = self._dims()
        kv_heads = self.attrs.get("num_kv_heads") or heads
        q_like = 2 if self.attrs.get("gated") else 1  # wq and the gate's wg
        proj = 2 * b * sq * input_shapes[0][-1] * (
            q_like * heads * kdim + kv_heads * (kdim + vdim)) \
            + 2 * b * sq * heads * vdim * embed
        window = self.attrs.get("window")
        if window and self.attrs.get("causal"):
            w = min(window, sq)  # the band: a triangle, then w keys a row
            pairs = w * (w + 1) // 2 + (sq - w) * w
        else:  # a causal mask is not discounted, as before
            pairs = sq * sk
        return proj + 2 * b * heads * pairs * (kdim + vdim)

    def parallelizable_dims(self, input_shapes):
        weights = {"wq": 1, "wk": 1, "wv": 1, "wo": 0}
        if self.attrs.get("gated"):
            weights["wg"] = 1
        return {
            "batch": True,
            # head (attribute) parallelism: shard heads dim of all projections
            # (with grouped K/V heads a shard holds whole groups: the degree
            # divides num_kv_heads)
            "heads": {"weights": weights, "reduces_output": True},
        }


def _inner_scope(node_name: str, what: str) -> str:
    """A scope inside a node that a trace reads as a node's: ``l3_attn_17``
    -> ``l3_attnrope`` (the layer prefix kept, so that the breakdown groups
    it by layer kind as it groups nodes)."""
    import re

    m = re.match(r"([a-z]+\d+_[a-z][a-z0-9]*)", node_name)
    return f"{m.group(1)}{what}" if m else what


def _head_rms_norm(x, gain, eps: float):
    """RMS norm over head_dim of (batch, heads, seq, head_dim), in float32,
    one gain vector for all heads."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _whole_rms_norm(x, gain, eps: float):
    """RMS norm over the WHOLE width — heads and head_dim together — of
    (batch, heads, seq, head_dim), in float32, gain (heads, head_dim)."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=(1, 3),
                                    keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)[None, :, None, :]).astype(x.dtype)


def _rotate_half_rope(x, theta: float):
    """Rotary positions on (batch, heads, seq, head_dim), rotate-half pairing
    (dim i with dim i + head_dim/2), positions 0..seq-1, angles in float32."""
    import jax.numpy as jnp

    seq, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def _serving_attention(name: str, q, k, v, sv, *, causal: bool):
    """Prefill/decode attention over the serving KV pool
    (serving/kvcache.py; ISSUE 6, paged since ISSUE 12). Numerics follow
    ``mha_core``'s einsum path — same scale, same ``-1e30`` additive mask,
    same f32-accumulating einsums — so prefill and decode logits match the
    whole-sequence forward within float32 rounding of the one-token score
    product (tests/serving_oracle.py states the tolerance; tier-1 reads
    3-8 ulp of the largest logit): masked lanes contribute
    exp(-1e30-max) == 0.0 exactly, and unwritten rows are finite, so the
    wider reduction adds exact zeros only.

    * prefill: q/k/v carry the whole padded prompt; the causal core runs
      unchanged and k/v land at position 0 of one request's contiguous
      ``max_len`` buffer, which the engine's slot writer scatters into
      the pool (``scatter_prefill_kv``).
    * decode: q/k/v carry ONE token per slot; k/v are written into the
      block pool at (table[pos // bs], pos % bs) — static shapes, no
      recompile, in place on the chip (``kvcache.write_kv_rows``) — and
      q attends under the mask ``key_pos <= position``. The read is
      either the Pallas flash-decode
      kernel (TPU fast path — O(true length) HBM traffic,
      kernels/flash_decode.py) or a pure gather back to position order
      followed by the masked einsums below — gathered rows are bitwise
      the stored rows and garbage-block rows are masked to exact zeros.
      The int8 layout dequantizes per-(token, head) rows on read and is
      judged against a pinned tolerance band instead.
    """
    import jax
    import jax.numpy as jnp

    from ..serving.kvcache import (prefill_kv_entry, read_kv,
                                   write_token_kv)

    # fewer K/V heads than query heads: the pool's grouped layout
    group = q.shape[1] // k.shape[1]
    grouped = group > 1
    if grouped and (sv.kv_dtype == "int8" or sv.mode == "chunk"
                    or sv.seq_shards > 1):
        raise NotImplementedError(
            f"{name}: grouped K/V heads on the serving path run the "
            "one-shot prefill and the decode step over a native-dtype "
            "pool; an int8 pool, a prefill chunk and sequence-parallel "
            "decode hold as many K/V heads as query heads")
    if not causal:
        raise ValueError(
            f"{name}: serving prefill/decode requires CAUSAL self-attention "
            "(bidirectional attention cannot be decoded incrementally); "
            "build the model with causal=True")
    if sv.mode == "chunk":
        return _chunk_prefill_attention(name, q, k, v, sv)
    if sv.mode == "prefill":
        sv.cache_out[name] = prefill_kv_entry(k, v, sv.max_len,
                                              v_first=grouped)
        return mha_core(q, k, v, causal=True)
    scale = 1.0 / np.sqrt(q.shape[-1])
    tables = sv.block_tables
    with jax.named_scope("kv_update"):
        entry = write_token_kv(sv.cache_in[name], k, v, sv.positions,
                               tables, sv.block_size, v_first=grouped)
    sv.cache_out[name] = entry
    kernel_out = _maybe_flash_decode(q, entry, tables, sv, scale,
                                     v_first=grouped)
    if kernel_out is not None:
        return kernel_out
    kc, vc = read_kv(entry, tables, q.shape[-1], k.dtype, v_first=grouped)
    if grouped:
        kc, vc = (jnp.repeat(t, group, axis=1) for t in (kc, vc))
    extent = kc.shape[2]  # blocks_per_slot * block_size
    if sv.seq_shards > 1:
        return _seqpar_decode(q, kc, vc, sv, scale, extent)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(extent)
    mask = kpos[None, None, None, :] <= sv.positions[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.astype(vc.dtype)


def _seqpar_decode(q, kc, vc, sv, scale, extent):
    """Sequence-parallel decode step (ISSUE 18): the gathered extent is
    partitioned into ``sv.seq_shards`` contiguous key segments — on a
    mesh each segment is one chip's run of pool blocks; on a single
    device the same decomposition runs locally, which is what tier-1
    pins. Each shard folds its segment through the flash-decode
    online-softmax recurrence into a partial ``(m, l, acc)`` and the
    priced segment-merge combines them (kernels/seqpar_decode.py) —
    ~1 ulp from the single-shard matvec, and the sharded token stream
    equals the single-shard stream in tier-1. Fully-masked segments
    (write cursor below the shard's range) contribute exact zeros via
    ``exp(-1e30 - m*)``."""
    import jax.numpy as jnp
    from jax import lax

    from ..kernels.seqpar_decode import (combine_partials,
                                         decode_shard_partial,
                                         shard_segment)

    S = int(sv.seq_shards)
    seg = shard_segment(extent, S)
    kpos = jnp.arange(extent)
    mask = kpos[None, None, None, :] <= sv.positions[:, None, None, None]
    partials = []
    for s in range(S):
        lo, hi = s * seg, (s + 1) * seg
        partials.append(decode_shard_partial(
            q, lax.slice_in_dim(kc, lo, hi, axis=2),
            lax.slice_in_dim(vc, lo, hi, axis=2),
            mask[..., lo:hi], scale))
    return combine_partials(partials).astype(vc.dtype)


def _chunk_prefill_attention(name: str, q, k, v, sv):
    """One prefill CHUNK for a single slot over the paged pool
    (ISSUE 14, docs/serving.md "Prefix cache & chunked prefill"): q/k/v
    carry ``chunk_len`` tokens of ONE request (batch 1) starting at
    position ``sv.positions[0]``; the chunk's k/v rows are scattered
    into the slot's pool blocks (pad rows beyond ``sv.lengths[0]`` go to
    the garbage block) and q attends over the slot's full gathered
    extent — the already-written prefix (a cached trie hit or earlier
    chunks) plus this chunk — under the mask ``key_pos <= row_pos``.

    Numerics are built to follow the one-shot prefill's: the chunk's
    score product always rides a full-extent GEMM (chunk rows scattered
    into a zero-padded extent-row q) so the d-axis accumulation order
    matches the whole-sequence forward's; masked lanes — the stale rows
    of freshly-recycled blocks included — are finite and contribute
    exp(-1e30 - max) == 0.0 exactly; and the row-wise projections run at
    the chunk program's fixed compiled width (floor 2). What tier-1 holds
    of it: a trie-hit admission's suffix chunk, a chunked long prompt
    and a cold one-shot prefill give the same token streams, and the
    chunk's next-token logits are within the stated tolerance of the
    one-shot prefill's (tests/serving_oracle.py; not bitwise under
    jax 0.9.0, and on the chip equal prompts may part at a reference
    tie, PERF.md §7). The extent-wide score pad is the price (one chunk
    pays O(extent^2) score FLOPs instead of O(chunk x extent)) and a
    named debt (ROADMAP.md D14): removing it moves the benchmark's
    compared numbers, so it waits for a PR that measures. int8 pools
    quantize the chunk rows per-(token, head) on write — band-judged
    like every int8 path."""
    import jax
    import jax.numpy as jnp

    from ..serving.kvcache import read_kv, write_chunk_kv

    tables = sv.block_tables  # (1, mb)
    start = sv.positions[0]
    n_new = sv.lengths[0]
    chunk_len = q.shape[2]
    pos = start + jnp.arange(chunk_len, dtype=jnp.int32)
    valid = jnp.arange(chunk_len) < n_new
    entry = write_chunk_kv(sv.cache_in[name], k, v, start, n_new,
                           tables[0], sv.block_size)
    sv.cache_out[name] = entry
    kc, vc = read_kv(entry, tables, q.shape[-1], k.dtype)
    extent = kc.shape[2]
    scale = 1.0 / np.sqrt(q.shape[-1])
    # full-extent score GEMM: chunk q rows scattered at their positions
    # into a zero extent-row buffer (pad rows dropped out of bounds),
    # rows re-extracted after the product
    safe = jnp.where(valid, pos, extent + 1)
    qpad = jnp.zeros((1, q.shape[1], extent, q.shape[-1]), q.dtype)
    qpad = qpad.at[0, :, safe].set(jnp.swapaxes(q[0], 0, 1), mode="drop")
    full = jnp.einsum("bhqd,bhkd->bhqk", qpad, kc,
                      preferred_element_type=jnp.float32) * scale
    logits = jnp.take_along_axis(
        full, jnp.clip(pos, 0, extent - 1)[None, None, :, None], axis=2)
    kpos = jnp.arange(extent)
    mask = kpos[None, None, None, :] <= pos[None, None, :, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.astype(vc.dtype)


def _maybe_flash_decode(q, entry, tables, sv, sm_scale, v_first=False):
    """Route one paged decode read through the Pallas flash-decode kernel
    when eligible (on-TPU, a pool of whole lanes and sublanes) —
    returns the (S, h, 1, hd) output or None for the gather path."""
    from ..serving.kvcache import flash_decode_kv

    if sv.seq_shards > 1:
        # the shard decomposition runs the split-K math per segment over
        # the gathered extent (_seqpar_decode); the single whole-extent
        # kernel launch would bypass the combine
        return None
    out = flash_decode_kv(q[:, :, 0, :], entry, tables, sv.positions + 1,
                          sm_scale, v_first=v_first)
    return None if out is None else out[:, :, None, :]


def _dropout_seed(rng):
    """Fold the step rng into one traced uint32 scalar for the counter-based
    in-kernel dropout PRNG (reseeds every step without recompiling)."""
    import jax
    import jax.numpy as jnp

    return jax.random.bits(rng, (), jnp.uint32)


def _resolve_live_dropout(dropout, ctx) -> float:
    """Effective dropout rate for this forward. A training context that
    requests dropout but carries no rng would otherwise SILENTLY train
    without dropout on every kernel path (the kernel entry points raise,
    the op layer used to swallow it — ADVICE r4): surface it loudly."""
    if not dropout or not ctx.training:
        return 0.0
    if ctx.rng is None:
        import warnings

        warnings.warn(
            f"attention dropout={dropout} requested with training=True but "
            f"the step context has no rng — training WITHOUT dropout. "
            f"Thread an rng through the executor (fit/make_train_step do "
            f"this automatically).", stacklevel=3)
        return 0.0
    return float(dropout)


# Flash crossover/tile constants, keyed by TPU generation (these are
# hardware-generation-specific). ONLY the v5e row is MEASURED (the chip of
# this installation, round-5 streaming kernels, b1 h16 s4096 d64 bf16
# sweep: (block_q 512, block_k 1024) fwd 1.72 ms / fwd+fused-bwd 3.58 ms vs
# 4.6 ms at (512,512) and 7.8 ms at (256,256); wider k tiles amortize the
# per-grid-step scratch round-trip, block_k > 1024 overflows VMEM in the
# fused backward's score tile; min_block 256: at 128-wide tiles — e.g. seq
# 640's only divisor — the einsum core wins). A TPU generation without a
# row is an error (_flash_tuning): add its row after running this recipe on
# that chip — time
# jax.jit(jax.grad(lambda q,k,v: flash_attention(q,k,v,False,bq,bk).sum()))
# at b1 h16 s4096 d64 bf16 over (bq, bk) in {128,256,512}x{256,512,1024}
# and vs mha_core at seq 640.
FLASH_TUNING = {
    "v5e": {"block_q_cap": 512, "block_k_cap": 1024, "min_block": 256},
}


def _flash_tuning() -> dict:
    """The FLASH_TUNING row for the current chip. A TPU whose generation
    has no measured row raises; the CPU mesh (interpret-mode tests) tiles
    by the v5e row."""
    from ..search.machine_model import local_tpu_generation

    gen = local_tpu_generation() or "v5e"
    if gen not in FLASH_TUNING:
        raise RuntimeError(
            f"flash tile table has no measured row for TPU generation "
            f"{gen!r}; measure one per the FLASH_TUNING recipe in "
            f"ops/attention.py")
    return FLASH_TUNING[gen]


def _flash_blocks(seq_q: int, seq_k: int):
    """Block sizes for the streaming flash kernels from the current chip's
    FLASH_TUNING row, or None when a sequence has no 128-multiple divisor
    (the kernel's grid floor-divisions would silently drop the tail — fall
    back to the einsum core instead)."""
    tune = _flash_tuning()

    def pick(seq, cap):
        for b in (cap, 512, 384, 256, 128):
            if b <= cap and seq % b == 0:
                return b
        return None

    bq = pick(seq_q, tune["block_q_cap"])
    bk = pick(seq_k, tune["block_k_cap"])
    if bq is None or bk is None:
        return None
    return bq, bk


def _should_use_flash(use_flash, q, k, causal) -> bool:
    if causal and q.shape[-2] > k.shape[-2]:
        return False  # empty attention windows — einsum core only
    if use_flash is True:
        return True
    if use_flash == "auto":
        from ..kernels._common import on_tpu

        if not on_tpu() or q.shape[-1] % 64 != 0:
            return False
        # head_dim 64 is fine on the MXU (the (block_q, d) tiles pad lanes
        # to 128). Only take flash when both sequences admit blocks >= the
        # generation's measured crossover (FLASH_TUNING.min_block): below
        # it the einsum core wins, e.g. seq 640 only divides by 128.
        blocks = _flash_blocks(q.shape[-2], k.shape[-2])
        return blocks is not None and \
            min(blocks) >= _flash_tuning()["min_block"]
    return False


def _flash_on_mesh(q, k, v, causal, dropout, seed, mesh, window=None):
    """The flash kernel on whatever mesh the step runs over. Mosaic kernels
    cannot be partitioned automatically (lowering one with a sharded operand
    raises), so on more than one device the call rides a ``shard_map``.
    Attention is independent per (batch, head): batch goes over the ``data``
    axis and heads over every other mesh axis, wherever the sizes divide —
    XLA reshards q/k/v to that layout if the plan holds them otherwise, and
    an axis that divides neither dimension computes redundantly. Each shard
    folds its mesh position into the dropout seed, so shards draw different
    masks."""
    import jax
    import jax.numpy as jnp

    from ..kernels.flash_attention import flash_attention

    bq, bk = _flash_blocks(q.shape[-2], k.shape[-2])
    if mesh is None or mesh.devices.size == 1:
        return flash_attention(q, k, v, causal, bq, bk, dropout=dropout,
                               seed=seed, window=window)
    from jax.sharding import PartitionSpec as P

    batch_axes, head_axes = [], []
    b, h = q.shape[0], k.shape[1]  # heads split in whole K/V groups
    for axis, size in mesh.shape.items():
        if axis == "data" and b % size == 0:
            batch_axes.append(axis)
            b //= size
        elif h % size == 0:
            head_axes.append(axis)
            h //= size
    spec = P(tuple(batch_axes) or None, tuple(head_axes) or None, None, None)

    def local(q, k, v, seed):
        if dropout:
            shard = jnp.uint32(0)
            for axis in batch_axes + head_axes:
                shard = shard * jnp.uint32(mesh.shape[axis]) + \
                    jax.lax.axis_index(axis).astype(jnp.uint32)
            seed = seed + shard * jnp.uint32(0x9E3779B1)
        return flash_attention(q, k, v, causal, bq, bk, dropout=dropout,
                               seed=seed if dropout else None, window=window)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, P()), out_specs=spec,
        check_vma=False)(q, k, v, seed if dropout else jnp.uint32(0))


@register_op(OperatorType.OP_SDPA)
class SDPAOp(Op):
    """Scaled-dot-product attention core without projections (torch
    F.scaled_dot_product_attention; reference analog: the cuDNN core inside
    src/ops/attention.cu minus the packed q/k/v/o projections).

    inputs: (q, k, v[, additive attn_mask]), q/k/v (batch, heads, seq, hd).
    attrs: dropout, causal, scale (None = 1/sqrt(head_dim)), use_flash.
    """

    def infer_output_shapes(self, input_shapes):
        q, _k, v = input_shapes[:3]
        return [tuple(q[:-1]) + (v[-1],)]

    def forward(self, params, inputs, ctx: OpContext):
        q, k, v = inputs[:3]
        mask = inputs[3] if len(inputs) > 3 else None
        causal = self.attrs.get("causal", False)
        # flash kernel has no mask/scale parameters — only take it when the
        # request needs neither (dropout IS supported in-kernel)
        dropout = self.attrs.get("dropout", 0.0)
        live_dropout = _resolve_live_dropout(dropout, ctx)
        if mask is None and self.attrs.get("scale") is None \
                and _should_use_flash(
                    self.attrs.get("use_flash", "auto"), q, k, causal) \
                and _flash_blocks(q.shape[-2], k.shape[-2]) is not None:
            seed = _dropout_seed(ctx.rng) if live_dropout else None
            return [_flash_on_mesh(q, k, v, causal, live_dropout, seed,
                                   ctx.mesh)]
        # same single-gate rule as MultiHeadAttentionOp: pass the resolved
        # live_dropout, rng only when it is live
        return [mha_core(q, k, v, causal=causal, dropout=live_dropout,
                         rng=ctx.rng if live_dropout else None,
                         training=ctx.training,
                         attn_mask=mask, scale=self.attrs.get("scale"))]

    def flops(self, input_shapes, output_shapes):
        b, h, sq, d = input_shapes[0]
        sk = input_shapes[1][2]
        vd = input_shapes[2][3]
        return 2 * b * h * sq * sk * (d + vd)

    def parallelizable_dims(self, input_shapes):
        return {"batch": True}
