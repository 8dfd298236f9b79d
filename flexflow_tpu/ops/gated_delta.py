"""Gated delta-rule mixer (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464, as ``transformers`` and ``fla`` name its parts): a
linear-attention layer whose state is a ``(d_k, d_v)`` MATRIX a head.

With ``u`` the node's input (width ``d``), ``H`` heads, ``d_k`` / ``d_v``
the key / value head widths, ``K`` the conv width, per sequence, token ``t``
and head:

    q'_t = W_q u_t  (H d_k)    k'_t = W_k u_t  (H d_k)    v'_t = W_v u_t  (H d_v)
    x_t  = silu( sum_j w_x[:, j] * x'_{t-K+1+j} )   x in {q, k, v};  x'_{<0} = 0;  no bias
    q^_t = q_t / ||q_t||_2 * d_k^-1/2       k^_t = k_t / ||k_t||_2           (a head)
    b_t  = (2 if neg_eigval else 1) * sigmoid(W_b u_t)               in (0, 2)^H
    g_t  = -exp(A_log) * softplus(W_a u_t + dt_bias)    <= 0;         a_t = exp(g_t)
    S_t  = a_t S_{t-1} + k^_t ( b_t ( v_t - (a_t S_{t-1})^T k^_t ) )^T       (d_k, d_v), f32
    o_t  = S_t^T q^_t
    y_t  = RMSNorm_{d_v}(o_t; w_n) * silu( (W_g u_t)_h )
    out_t = W_o concat_h y_t

**Grouped key heads** (``num_key_heads`` ``H_k`` < ``H``, ``r = H / H_k``):
``q'`` and ``k'`` are ``H_k d_k`` wide, value head ``j`` reads ``q^, k^`` of
key head ``j // r``, and everything else — ``v``, the gate, ``b``, ``g``, the
state — is a VALUE head's. ``q^`` and ``k^`` are repeated over their ``r``
value heads after the norms (a few MB a step against the state's GB), so the
rule and both kernels see ``H`` heads as ever. **The output gate** ``gate``:
``"silu"`` above, or ``"sigmoid2_zero_centered"``: ``y_t = o_t / rms(o_t) *
(1 + w_n) * 2 sigmoid((W_g u_t)_h)``, ``w_n`` stored about zero.

What a sequence carries from token to token is ``S`` — ``H`` matrices of
``(d_k, d_v)``, float32 — and the last ``K - 1`` rows of ``q'``, ``k'`` and
``v'``: a fixed size whatever the context, which is what a serving engine
keeps per SLOT (:meth:`GatedDeltaMixerOp.slot_state_bytes`), beside the
per-token pool rows of the graph's attention nodes.

Three forms of the same mathematics (as ops/ssm.py):

* **whole sequence** (outside serving, and the one-shot prefill): the
  projections as matmuls over all tokens, the recurrence by the
  ``gated_delta_rule`` kernel on the chip (kernels/gated_delta_rule.py: the
  chunked matrix form, on the matrix unit) and by a ``lax.scan`` over tokens
  elsewhere. A prefill's rows at and past the request's ``length`` leave
  ``S`` untouched (``g`` and ``b`` forced to 0 there) and the conv tails are
  gathered at ``length - K + 1 .. length - 1`` (``ssm.conv_tail_out``): the
  state handed to the slot is the state after the last REAL token, the
  ``LSTMOp`` contract.
* **decode**: one token a slot from ``cache_in[name] = (conv_tail, S)``,
  ``S`` as it rests (below); the one-token update reads the state once
  (``S^T k`` and ``S^T q`` from one pass: kernels/gated_delta_rule.py). A
  FREE slot's state is held at zero (``kvcache.live_slots``).
* **chunk** raises: a chunk would have to start from a carried state and a
  prefix hit from a snapshot of one, which the engine does not keep
  (ROADMAP.md, Reach R8).

The projections compute in the graph's dtype (bf16 on the chip) with float32
accumulation; ``g``, ``a``, ``b``, the L2 norms, ``S``, ``S^T k`` and ``S^T
q`` are float32, and ``S`` rests in the slot in float32. The conv tails rest
in the graph's dtype.

At rest ``S`` is ``(n_slots, H / p, d_k, p * d_v)``: ``p`` heads side by side
on the lanes of one row, ``p`` the least count for which ``p * d_v`` is whole
128-lane tiles (``kernels.gated_delta_rule.state_heads_a_row``: 2 at ``d_v``
192, where 384 = 3 x 128; 1 at 128 or 256) — the shape the update computes on,
with no lane the chip pads (a head a row, ``(n_slots, H, d_k, 192)``, rests
every row in 256 lanes, a third more bytes that every decode step read and
wrote: PR 46's shape), a row's ``d_k`` sublanes whole tiles, and no lane split
anywhere: the update carries every per-head vector spread over its head's
``d_v`` lanes, so a row's ``v``, ``exp(g)``, ``beta``, ``k . q`` and output
are ``p * d_v`` adjacent lanes of the head-major rows the projections hand
over. The packing is ``pack_state`` / ``unpack_state`` there and nowhere else;
a prefill packs its last state once as it hands it to the slot. This form
stays where it is put (PERF.md section 6, PR 49: read off the compiled text).
The two 3-D forms that would also rest unpadded do not: the chip's runtime
rests ``(n_slots, H * d_k, d_v)`` with ``H * d_k`` on the lanes (less
padding), so every step re-lays the whole state on its way into the update and
out of it, and ``(n_slots, d_k, H * d_v)`` has to split its lanes a head,
which re-lays it too (PERF.md section 6, PR 46). The tails are ``(n_slots,
(K - 1) * (2 H d_k + H d_v))``.
"""
from __future__ import annotations

from ..ffconst import OperatorType
from .attention import _head_rms_norm, _inner_scope
from .base import Op, OpContext, no_chunk_carry, register_op
from .ssm import _DtBiasInitializer, conv_tail_out

#: the output gate's forms (``gate``)
GATES = ("silu", "sigmoid2_zero_centered")


class _ALogInitializer:
    """``A = uniform(0, 16)`` a head, ``A_log = log A``: the Gated DeltaNet
    paper's start (Mamba-2's)."""

    def __call__(self, key, shape, dtype):
        import jax
        import jax.numpy as jnp

        a = jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)
        return jnp.log(a).astype(dtype)


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32 (``fla``'s
    ``l2norm``)."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


@register_op(OperatorType.OP_GATED_DELTA_MIXER)
class GatedDeltaMixerOp(Op):
    """attrs: num_heads (H, the value heads), num_key_heads (H_k, default
    H), key_dim (d_k), value_dim (d_v), conv_width (K), neg_eigval,
    norm_eps, gate (``GATES``, default "silu"). input (batch, seq, dim) ->
    same shape.

    Weights: ``w_q``, ``w_k`` (dim, H_k d_k), ``w_v``, ``w_g`` (dim, H d_v),
    ``w_a``, ``w_b`` (dim, H), ``conv_w`` (2 H_k d_k + H d_v, K), ``a_log``,
    ``dt_bias`` (H,), ``norm_w`` (d_v,), ``w_o`` (H d_v, dim)."""

    def _dims(self):
        a = self.attrs
        return (int(a["num_heads"]), int(a["key_dim"]), int(a["value_dim"]),
                int(a["conv_width"]))

    def _key_heads(self) -> int:
        return int(self.attrs.get("num_key_heads") or self.attrs["num_heads"])

    def _zero_centered(self) -> bool:
        return self.attrs.get("gate", "silu") == "sigmoid2_zero_centered"

    def infer_output_shapes(self, input_shapes):
        return [tuple(input_shapes[0])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (ConstantInitializer,
                                              DefaultWeightInitializer,
                                              NormInitializer,
                                              UniformInitializer)

        d = input_shapes[0][-1]
        H, dk, dv, K = self._dims()
        Hk = self._key_heads()
        init = self.attrs.get("kernel_initializer") \
            or DefaultWeightInitializer()
        t = self.data_type
        # a zero-centred gain rests about 0 (gain 1 + w); drawn, not 0, so
        # that no seeded test holds it at the constant 1
        norm_init = NormInitializer(stddev=0.02) if self._zero_centered() \
            else ConstantInitializer(1.0)
        return {"w_q": ((d, Hk * dk), t, init),
                "w_k": ((d, Hk * dk), t, init),
                "w_v": ((d, H * dv), t, init), "w_g": ((d, H * dv), t, init),
                "w_a": ((d, H), t, init), "w_b": ((d, H), t, init),
                # a depthwise conv's fan-in is its K taps
                "conv_w": ((2 * Hk * dk + H * dv, K), t, UniformInitializer(
                    min_val=-K ** -0.5, max_val=K ** -0.5)),
                "a_log": ((H,), t, _ALogInitializer()),
                # softplus^-1(dt), dt log-uniform in [1e-3, 1e-1]: decays
                # over hundreds of tokens, not two
                "dt_bias": ((H,), t, _DtBiasInitializer()),
                "norm_w": ((dv,), t, norm_init),
                "w_o": ((H * dv, d), t, init)}

    def slot_state_bytes(self, el: int = 0) -> int:
        from ..ffconst import size_of_datatype

        H, dk, dv, K = self._dims()
        el = el or size_of_datatype(self.data_type)
        return H * dk * dv * 4 \
            + (2 * self._key_heads() * dk + H * dv) * (K - 1) * el

    def slot_state_heads_a_row(self) -> int:
        from ..kernels.gated_delta_rule import state_heads_a_row

        H, _dk, dv, _K = self._dims()
        return state_heads_a_row(H, dv)

    def forward(self, params, inputs, ctx: OpContext):
        import jax
        import jax.numpy as jnp

        from ..kernels.gated_delta_rule import (gated_delta_rule,
                                                gated_delta_rule_reference,
                                                gated_delta_update,
                                                one_token_update,
                                                pack_state,
                                                use_gated_delta_rule)

        u = inputs[0]                                   # (b, s, d)
        batch, seq, _d = u.shape
        H, dk, dv, K = self._dims()
        Hk = self._key_heads()
        sv = ctx.serving
        if sv is not None and sv.mode == "chunk":
            raise NotImplementedError(no_chunk_carry(
                self.name, "a gated delta-rule mixer's matrix state"))
        decode = sv is not None and sv.mode == "decode"
        f32 = jnp.float32
        scope = lambda what: jax.named_scope(_inner_scope(self.name, what))
        live = None
        if decode:
            from ..serving.kvcache import live_slots

            # a free slot's state is held at zero
            live = live_slots(sv.block_tables)[:, None, None]   # (b, 1, 1)
        with scope("in"):
            proj = lambda w: jnp.einsum("bsd,df->bsf", u, params[w])
            xp = jnp.concatenate([proj("w_q"), proj("w_k"), proj("w_v")],
                                 axis=-1)               # (b, s, channels)
            gate = proj("w_g")
            a_in, b_in = (jnp.einsum("bsd,dh->bsh", u, params[w],
                                     preferred_element_type=f32)
                          for w in ("w_a", "w_b"))
        with scope("conv"):
            if decode:
                tail, s0 = sv.cache_in[self.name]
                hist = jnp.concatenate(
                    [tail.reshape(batch, K - 1, -1).astype(xp.dtype), xp],
                    axis=1)                             # (b, K, channels)
            else:
                s0 = None
                hist = jnp.pad(xp, ((0, 0), (K - 1, 0), (0, 0)))
            w = params["conv_w"].astype(f32)
            conv = sum(hist[:, j:j + seq].astype(f32) * w[:, j]
                       for j in range(K))
            # float32 from here to the head's norm: the q . k and S^T k
            # contractions cancel, and a rounding to bf16 in front of them
            # comes out of the layer more than doubled
            x = jax.nn.silu(conv)
            q, k, v = (t.reshape(batch, seq, h, -1) for t, h in (
                (x[..., :Hk * dk], Hk), (x[..., Hk * dk:2 * Hk * dk], Hk),
                (x[..., 2 * Hk * dk:], H)))
            if sv is not None:
                tail = conv_tail_out(sv, hist, live, K)
        with scope("gate"):
            beta = jax.nn.sigmoid(b_in) * (
                2.0 if self.attrs["neg_eigval"] else 1.0)
            g = -jnp.exp(params["a_log"].astype(f32)) * jax.nn.softplus(
                a_in + params["dt_bias"].astype(f32))
            q = l2_normalize(q) * dk ** -0.5
            k = l2_normalize(k)
            if Hk != H:
                # value head j reads key head j // r: the normalised rows
                # repeated, so that the rule sees H heads as ever
                q, k = (jnp.repeat(t, H // Hk, axis=2) for t in (q, k))
        with scope("rule"):
            if decode:
                # a free slot decays to nothing and writes nothing: a = 0
                # and b = 0 hold its (zero) state at zero with no pass over
                # the state beside the update's own
                g = jnp.where(live, g, -jnp.inf)
                beta = jnp.where(live, beta, 0.0)
                update = gated_delta_update if use_gated_delta_rule() \
                    else one_token_update
                o, s_last = update(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0])
                o = o[:, None]
            else:
                lengths = sv.lengths if sv is not None else None
                rule = gated_delta_rule if use_gated_delta_rule() \
                    else gated_delta_rule_reference
                o, s_last = rule(q, k, v, g, beta, lengths=lengths)
                if sv is not None:
                    # the state as the slot rests it, packed once a prompt
                    s_last = pack_state(s_last)
        if sv is not None:
            sv.cache_out[self.name] = (tail, s_last)
        with scope("out"):
            # (b, s, H, d_v) float32 in, so float32 out of the norm
            gain = params["norm_w"]
            if self._zero_centered():
                gain = 1.0 + gain.astype(f32)
            y = _head_rms_norm(o, gain, float(self.attrs["norm_eps"]))
            z = gate.astype(f32).reshape(y.shape)
            y = y * (2.0 * jax.nn.sigmoid(z) if self._zero_centered()
                     else jax.nn.silu(z))
            out = jnp.einsum(
                "bse,ed->bsd", y.reshape(batch, seq, H * dv).astype(u.dtype),
                params["w_o"], preferred_element_type=f32).astype(u.dtype)
        return [out]

    def flops(self, input_shapes, output_shapes):
        b, s, d = input_shapes[0]
        H, dk, dv, K = self._dims()
        channels = 2 * self._key_heads() * dk + H * dv
        per_token = 2 * d * (channels + H * dv + 2 * H) + 2 * channels * K \
            + 6 * H * dk * dv + 2 * H * dv * d
        return b * s * per_token

    def parallelizable_dims(self, input_shapes):
        return {"batch": True}
