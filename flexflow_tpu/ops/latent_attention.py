"""Multi-head latent attention (MLA): keys and values are up-projections of
one compressed row a token, and that row — not the heads' keys and values —
is what a serving engine caches.

With ``h`` the node's input (the block's pre-norm applied by the graph):

    c_q         = RMS(h W_qa)                       (q_rank)
    [q_n | q_r] = c_q W_qb                          heads x (nope | rope)
    [c_kv | k_r] = h W_kva                          (kv_rank | rope)
    c_kv <- RMS(c_kv);  k_r <- RoPE(k_r);  q_r <- RoPE(q_r)
    [k_n | v]   = c_kv W_kvb                        heads x (nope | v)
    score = (q_n . k_n + q_r . k_r) / sqrt(nope + rope), causal softmax
    y = concat_heads(sum p v) W_o

``k_r`` is one rotary key shared by every head (rotate-half pairing). The
cached row is ``[c_kv | k_r]``: ``kv_rank + rope`` numbers a token, whatever
the head count (serving/kvcache.py, the latent layout).

Two forms of the same mathematics:

* **materialised** — ``k_n`` and ``v`` are built from the rows and the core
  is ordinary attention with 192-wide keys and 128-wide values. The forward
  outside serving (so ``compile()``, ``eval`` and the static analysis see an
  ordinary node) and the one-shot prefill.
* **absorbed** — ``W_kvb``'s key half is folded into the query
  (``q~ = W_kvb[K]^T q_n``, per head nope -> kv_rank) and its value half is
  applied after the weighted sum (``o = W_kvb[V] sum p c_kv``), so the scores
  and the sum run against the cached rows themselves: one key row serves
  every head and the value is the row's first ``kv_rank`` lanes. The decode
  step and the prefill CHUNK (which scores chunk x extent: there is no
  extent-wide query pad here, ROADMAP.md D14 is the GPT-2 path's): the
  ``flash_decode`` kernel's latent read on the chip — for a chunk with
  ``CHUNK_TOKENS_A_STEP`` positions a grid step, ``latent_chunk_attention``
  in the compiled program — and a gather and two einsums where the kernel's
  gate declines (off the chip).

Which is cheaper is arithmetic: a query row against ``n`` keys costs
``2 n (nope + rope + v)`` materialised plus ``2 n kv_rank (nope + v)`` once a
chunk for the up-projection, and ``2 n (2 kv_rank + rope)`` absorbed. At one
query row a slot (decode) the up-projection of the whole extent dominates
and absorbed wins by two orders; at a 1,024-row chunk the up-projection is
amortised and materialised does 2.4 times fewer FLOPs — but as plain XLA its
float32 score tile goes through HBM four times over the WHOLE static extent,
pad rows and unwritten keys included, and the chip measured it at 54 ms a
layer against the kernel's absorbed read, which does work only for live rows
and live keys (PERF.md section 6, PR 37: which, and why).

Rotary positions come from the serving context: ``arange`` for a prefill,
``start + arange`` for a chunk, each slot's cursor for a decode step.
"""
from __future__ import annotations

import numpy as np

from ..ffconst import OperatorType
from .attention import _inner_scope
from .base import Op, OpContext, register_op

#: positions of a chunk that share one grid step of the kernel's chunk read
#: (``flash_decode_pool(tokens=)``): 4 x 128 heads is a 512-row query block,
#: 7 MB of the 16 MiB of scoped VMEM with its accumulators at 640 lanes
CHUNK_TOKENS_A_STEP = 4
#: the materialised core's float32 score tile, (batch x heads, rows, keys),
#: is cut into blocks of query rows past this many bytes
SCORE_TILE_BYTES = 256 << 20


def score_blocks(heads: int, rows: int, keys: int) -> int:
    """Blocks of query rows the materialised core runs in: the least power
    of two that brings the float32 score tile under ``SCORE_TILE_BYTES``
    and divides ``rows``; 1 (the whole tile at once) where it fits."""
    blocks = 1
    while heads * (rows // blocks) * keys * 4 > SCORE_TILE_BYTES \
            and rows % (2 * blocks) == 0:
        blocks *= 2
    return blocks


def yarn_inv_freq(d: int, theta: float, scaling: dict):
    """YaRN's rotary frequencies for a ``d``-wide rotary part (the
    DeepSeek-V3 family's closed form): ``theta^(-2j/d)`` kept where a pair
    turns more than ``beta_fast`` times over the original context, divided
    by ``factor`` where it turns fewer than ``beta_slow`` times, and a
    linear ramp between the two correction dims. float32 ``(d / 2,)``."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * np.log(orig / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(np.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(np.ceil(correction_dim(float(scaling["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 mscale ln(factor) + 1`` past factor 1: what YaRN multiplies the
    attention logits' temperature by."""
    return 0.1 * float(mscale) * np.log(factor) + 1.0 if factor > 1 else 1.0


def rope_at(x, positions, theta: float, scaling=None,
            interleave: bool = False):
    """Rotary positions on ``x (..., seq, d)`` at ``positions`` broadcastable
    to ``x.shape[:-1]``, angles in float32. The pairing is rotate-half (dim
    i with dim i + d/2) or, ``interleave``, neighbours (2j with 2j + 1: the
    same angles, the columns in another order). ``scaling``: a YaRN
    ``rope_scaling`` group (:func:`yarn_inv_freq`; cos and sin times
    ``mscale / mscale_all_dim``'s ratio, 1 where the two are equal)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    amp = 1.0
    if scaling is None:
        inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    else:
        inv_freq = yarn_inv_freq(d, theta, scaling)
        amp = yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0)) \
            / yarn_mscale(scaling["factor"],
                          scaling.get("mscale_all_dim", 0.0))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if scaling is None and not interleave:
        # the expression as it stood before the two options, in its order:
        # the programs that set neither trace as they did
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
        xf = x.astype(jnp.float32)
        rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
        return (xf * cos + rot * sin).astype(x.dtype)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    xf = x.astype(jnp.float32)
    if interleave:
        a, b = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
        return out.reshape(xf.shape).astype(x.dtype)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, gain, eps: float):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


@register_op(OperatorType.OP_LATENT_ATTENTION)
class LatentAttentionOp(Op):
    """attrs: embed_dim, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
    v_dim, rope_theta, eps (of the two latent RMS norms), causal (True: the
    only form); off by default: rope_scaling (a YaRN group: the frequencies
    of :func:`yarn_inv_freq`, and the softmax scale times ``m^2``, ``m =
    yarn_mscale(factor, mscale_all_dim)``), rope_interleave (neighbour
    pairs), gated (``o <- o * sigmoid(x W_g)`` a head, before ``W_o``:
    weight ``wg`` (dim, heads * v)). input (batch, seq, dim) -> (batch, seq,
    embed_dim). No bias.

    Weights, each a matrix: ``wq_a`` (dim, q_rank), ``q_norm`` (q_rank,),
    ``wq_b`` (q_rank, heads * (nope + rope)), ``wkv_a`` (dim, kv_rank +
    rope), ``kv_norm`` (kv_rank,), ``wkv_b`` (kv_rank, heads * (nope + v)),
    ``wo`` (heads * v, embed_dim)."""

    def _dims(self):
        a = self.attrs
        return (int(a["num_heads"]), int(a["q_rank"]), int(a["kv_rank"]),
                int(a["nope_dim"]), int(a["rope_dim"]), int(a["v_dim"]))

    @property
    def row_width(self) -> int:
        """Numbers cached a token: ``[c_kv | k_r]``."""
        return int(self.attrs["kv_rank"]) + int(self.attrs["rope_dim"])

    def infer_output_shapes(self, input_shapes):
        x = input_shapes[0]
        return [(x[0], x[1], self.attrs["embed_dim"])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (ConstantInitializer,
                                              DefaultWeightInitializer)

        d = input_shapes[0][-1]
        h, qr, kr, nope, rope, vd = self._dims()
        init = self.attrs.get("kernel_initializer") \
            or DefaultWeightInitializer()
        one = ConstantInitializer(1.0)
        t = self.data_type
        specs = {"wq_a": ((d, qr), t, init), "q_norm": ((qr,), t, one),
                 "wq_b": ((qr, h * (nope + rope)), t, init),
                 "wkv_a": ((d, kr + rope), t, init),
                 "kv_norm": ((kr,), t, one),
                 "wkv_b": ((kr, h * (nope + vd)), t, init),
                 "wo": ((h * vd, self.attrs["embed_dim"]), t, init)}
        if self.attrs.get("gated"):
            specs["wg"] = ((d, h * vd), t, init)
        return specs

    def _rope(self, x, positions):
        theta = float(self.attrs["rope_theta"])
        scaling = self.attrs.get("rope_scaling")
        interleave = bool(self.attrs.get("rope_interleave"))
        if scaling is None and not interleave:
            # as the parent calls it (benchmark/tests/controls_pangu.py
            # plants a three-argument fault here)
            return rope_at(x, positions, theta)
        return rope_at(x, positions, theta, scaling=scaling,
                       interleave=interleave)

    def _scale(self):
        """The softmax scale: ``(nope + rope)^-1/2``, times YaRN's ``m^2``
        under a ``rope_scaling`` that names ``mscale_all_dim``."""
        _h, _qr, _kr, nope, rope, _vd = self._dims()
        scale = 1.0 / np.sqrt(nope + rope)
        sc = self.attrs.get("rope_scaling")
        if sc and sc.get("mscale_all_dim"):
            scale = scale * yarn_mscale(sc["factor"],
                                        sc["mscale_all_dim"]) ** 2
        return scale

    # ------------------------------------------------------------ the parts
    def _queries(self, params, x, positions):
        """(q_n (b, s, h, nope), q_r (b, s, h, rope) rotated)."""
        import jax
        import jax.numpy as jnp

        h, _qr, _kr, nope, rope, _vd = self._dims()
        with jax.named_scope(_inner_scope(self.name, "q")):
            c_q = _rms(jnp.dot(x, params["wq_a"]), params["q_norm"],
                       float(self.attrs["eps"]))
            q = jnp.dot(c_q, params["wq_b"]).reshape(
                x.shape[:2] + (h, nope + rope))
            q_r = self._rope(jnp.swapaxes(q[..., nope:], 1, 2),
                             positions[:, None, :])
        return q[..., :nope], jnp.swapaxes(q_r, 1, 2)

    def _rows(self, params, x, positions):
        """The cached rows ``[RMS(c_kv) | RoPE(k_r)]`` (b, s, kv_rank +
        rope) of ``x``'s tokens."""
        import jax
        import jax.numpy as jnp

        kr = int(self.attrs["kv_rank"])
        with jax.named_scope(_inner_scope(self.name, "kv")):
            kv = jnp.dot(x, params["wkv_a"])
            c_kv = _rms(kv[..., :kr], params["kv_norm"],
                        float(self.attrs["eps"]))
            k_r = self._rope(kv[..., kr:], positions)
            return jnp.concatenate([c_kv, k_r], axis=-1)

    def _wkv_b(self, params):
        """``W_kvb`` as (kv_rank, heads, nope + v)."""
        h, _qr, kr, nope, _rope, vd = self._dims()
        return params["wkv_b"].reshape(kr, h, nope + vd)

    def _out(self, params, o, x):
        """o (b, s, h, v) -> (b, s, embed); ``gated``: each head's output
        times ``sigmoid(x W_g)`` first, the product taken in float32."""
        import jax
        import jax.numpy as jnp

        if self.attrs.get("gated"):
            with jax.named_scope(_inner_scope(self.name, "gate")):
                z = jnp.dot(x, params["wg"],
                            preferred_element_type=jnp.float32)
                o = (o.astype(jnp.float32)
                     * jax.nn.sigmoid(z).reshape(o.shape)).astype(o.dtype)
        with jax.named_scope(_inner_scope(self.name, "out")):
            return jnp.dot(o.reshape(o.shape[:2] + (-1,)), params["wo"],
                           preferred_element_type=jnp.float32
                           ).astype(o.dtype)

    def _materialised(self, params, q_n, q_r, rows, mask):
        """The core with ``k_n`` and ``v`` built from ``rows`` (b, n, row):
        queries (b, s, h, ...) under ``mask`` (b | 1, s, n) -> (b, s, h,
        v)."""
        import jax
        import jax.numpy as jnp

        _h, _qr, kr, nope, rope, _vd = self._dims()
        w = self._wkv_b(params)
        with jax.named_scope(_inner_scope(self.name, "up")):
            kv = jnp.einsum("bnc,chd->bnhd", rows[..., :kr], w)
        scale = self._scale()

        def core(q_n, q_r, mask):
            s = jnp.einsum("bshd,bnhd->bhsn", q_n, kv[..., :nope],
                           preferred_element_type=jnp.float32)
            s = s + jnp.einsum("bshr,bnr->bhsn", q_r, rows[..., kr:],
                               preferred_element_type=jnp.float32)
            s = jnp.where(mask[:, None], s * scale, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhsn,bnhd->bshd", p.astype(rows.dtype),
                              kv[..., nope:],
                              preferred_element_type=jnp.float32)

        b, sq, h = q_n.shape[:3]
        blocks = score_blocks(b * h, sq, rows.shape[1])
        with jax.named_scope(_inner_scope(self.name, "core")):
            if blocks == 1:
                o = core(q_n, q_r, mask)
            else:
                # the float32 score tile a block of query rows at a time: whole,
                # a 2,048-row prompt's is a GB a layer beside a resident engine
                cut = lambda t, axis: jnp.moveaxis(t.reshape(
                    t.shape[:axis] + (blocks, -1) + t.shape[axis + 1:]),
                    axis, 0)
                o = jax.lax.map(
                    lambda qm: core(*qm),
                    (cut(q_n, 1), cut(q_r, 1),
                     cut(jnp.broadcast_to(mask, (mask.shape[0], sq,
                                                 mask.shape[2])), 1)))
                o = jnp.moveaxis(o, 0, 1).reshape(b, sq, h, -1)
        return o.astype(rows.dtype)

    def _absorbed(self, params, q_n, q_r, entry, tables, seen,
                  tokens: int = 1):
        """The core against the cached rows themselves: queries (b, s, h,
        ...) -> (b, s, h, v), row (b, s) seeing the first ``seen[b, s]``
        rows of ``tables[b]`` (a decode step: one token a slot, each slot
        its own table row; a chunk: b = 1). The read is the kernel on the
        chip — ``tokens`` successive positions a grid step, 1 for a
        decode step — and a gather and two einsums where its gate
        declines (off the chip)."""
        import jax
        import jax.numpy as jnp

        from ..serving.kvcache import flash_decode_kv, read_kv

        h, _qr, kr, nope, rope, _vd = self._dims()
        w = self._wkv_b(params)
        scale = self._scale()
        b, c = q_n.shape[:2]
        with jax.named_scope(_inner_scope(self.name, "absorb")):
            qt = jnp.einsum("bshd,chd->bshc", q_n, w[..., :nope])
        q = jnp.concatenate([qt, q_r], axis=-1)           # (b, s, h, row)
        o_c = None
        if tokens:
            # a kernel slot: ``tokens`` positions of one sequence, heads
            # innermost, handed the keys of its FIRST position (0: a slot
            # of pad rows, or a free slot, costs nothing)
            o_c = flash_decode_kv(
                q.reshape(b * c // tokens, tokens * h, -1), entry, tables,
                seen.reshape(-1)[::tokens], scale, v_lanes=kr,
                tokens=tokens)
        if o_c is None:
            ext = read_kv(entry, tables, self.row_width,
                          q.dtype)[0][:, 0]               # (b, extent, row)
            sc = jnp.einsum("bshr,bnr->bshn", q, ext,
                            preferred_element_type=jnp.float32) * scale
            live = jnp.arange(ext.shape[1]) < seen[..., None, None]
            p = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            o_c = jnp.einsum("bshn,bnc->bshc", p.astype(ext.dtype),
                             ext[..., :kr],
                             preferred_element_type=jnp.float32
                             ).astype(ext.dtype)
        with jax.named_scope(_inner_scope(self.name, "absorb")):
            return jnp.einsum("bshc,chd->bshd", o_c.reshape(b, c, h, kr),
                              w[..., nope:])

    # -------------------------------------------------------------- forward
    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        (x,) = inputs
        if not self.attrs.get("causal", True):
            raise ValueError(f"{self.name}: latent attention is causal")
        sv = ctx.serving
        b, s, _ = x.shape
        if sv is None or sv.mode == "prefill":
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            q_n, q_r = self._queries(params, x, pos)
            rows = self._rows(params, x, pos)
            if sv is not None:
                from ..serving.kvcache import prefill_kv_entry

                sv.cache_out[self.name] = prefill_kv_entry(
                    rows[:, None], None, sv.max_len)
            mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None]
            return [self._out(params, self._materialised(
                params, q_n, q_r, rows, mask), x)]
        if sv.mode == "chunk":
            return [self._chunk(params, x, sv)]
        return [self._decode(params, x, sv)]

    def _chunk(self, params, x, sv):
        """One prefill chunk of one slot, absorbed: its rows into the
        pool, its queries against the slot's rows (the cached prefix,
        earlier chunks and this one) under ``key <= row position``. It
        scores chunk x extent; a pad row sees nothing."""
        import jax
        import jax.numpy as jnp

        from ..serving.kvcache import write_chunk_kv

        start, n_new = sv.positions[0], sv.lengths[0]
        c = x.shape[1]
        i = jnp.arange(c, dtype=jnp.int32)
        pos = (start + i)[None]
        q_n, q_r = self._queries(params, x, pos)
        rows = self._rows(params, x, pos)
        with jax.named_scope("kv_update"):
            entry = write_chunk_kv(sv.cache_in[self.name],
                                   rows[:, None], None, start, n_new,
                                   sv.block_tables[0], sv.block_size)
        sv.cache_out[self.name] = entry
        seen = jnp.where(i < n_new, start + i + 1, 0)[None]
        t = CHUNK_TOKENS_A_STEP if c % CHUNK_TOKENS_A_STEP == 0 else 0
        return self._out(params, self._absorbed(
            params, q_n, q_r, entry, sv.block_tables, seen, tokens=t), x)

    def _decode(self, params, x, sv):
        """One token a slot, absorbed: the row into the pool at the slot's
        cursor, every head's ``[q~ | q_r]`` against the slot's rows."""
        import jax

        from ..serving.kvcache import write_token_kv

        if sv.seq_shards > 1:
            raise NotImplementedError(
                f"{self.name}: sequence-parallel decode reads K and V "
                "apart; the latent pool has no such read (seq_shards 1)")
        pos = sv.positions[:, None]
        q_n, q_r = self._queries(params, x, pos)
        rows = self._rows(params, x, pos)                 # (S, 1, row)
        with jax.named_scope("kv_update"):
            entry = write_token_kv(sv.cache_in[self.name],
                                   rows[:, None], None, sv.positions,
                                   sv.block_tables, sv.block_size)
        sv.cache_out[self.name] = entry
        return self._out(params, self._absorbed(
            params, q_n, q_r, entry, sv.block_tables, pos + 1), x)

    # ---------------------------------------------------------- cost model
    def flops(self, input_shapes, output_shapes):
        b, s, d = input_shapes[0]
        h, qr, kr, nope, rope, vd = self._dims()
        proj = d * qr + qr * h * (nope + rope) + d * (kr + rope) \
            + kr * h * (nope + vd) + h * vd * self.attrs["embed_dim"] \
            + (d * h * vd if self.attrs.get("gated") else 0)
        return 2 * b * s * proj + 2 * b * h * s * s * (nope + rope + vd)

    def parallelizable_dims(self, input_shapes):
        return {"batch": True}
