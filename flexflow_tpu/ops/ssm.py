"""Selective state-space mixer (Mamba-1, the Jamba family's form with RMS
norms on ``dt``, ``B`` and ``C``).

With ``u`` the node's input (the block's pre-norm applied by the graph),
``E`` the inner width, ``N`` the state size, ``K`` the conv width, ``R`` the
``dt`` rank, per sequence and token ``t``:

    [x'_t ; z_t] = W_in u_t
    x_t   = silu(b_c + sum_k w_c[:, k] * x'_{t-K+1+k})        x'_{<0} = 0
    [r_t ; B_t ; C_t] = W_x x_t
    dt_t  = softplus(W_dt RMS(r_t) + b_dt);  B_t <- RMS(B_t);  C_t <- RMS(C_t)
    S_t   = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t    A = -exp(A_log)
    y_t   = S_t^T C_t + D * x_t
    out_t = W_out (y_t * silu(z_t))

What a sequence carries from token to token is ``S`` — ``(N, E)``, float32
— and the last ``K - 1`` rows of ``x'``: a fixed size whatever the context,
which is what a serving engine keeps per SLOT
(``SSMMixerOp.slot_state_bytes``), beside the per-token pool rows of the
graph's attention nodes.

Three forms of the same mathematics:

* **whole sequence** (outside serving, and the one-shot prefill): the three
  projections as matmuls over all tokens, the recurrence by the
  ``selective_scan`` kernel on the chip (kernels/selective_scan.py) and by
  a ``lax.scan`` over tokens elsewhere. A prefill's rows at and past the
  request's ``length`` leave ``S`` untouched (``dt`` forced to 0 there) and
  the conv tail is gathered at ``length - K + 1 .. length - 1``: the state
  handed to the slot is the state after the last REAL token, the
  ``LSTMOp`` contract (ops/recurrent.py).
* **decode**: one token a slot from ``cache_in[name] = (conv_tail, S)``;
  the one-token update is one fused elementwise expression over the
  ``(n_slots, N, E)`` state (timed on the chip against the kernel at one
  row: PERF.md section 6, PR 44). A FREE slot's state is held at zero
  (``kvcache.live_slots``), so nothing grows in a slot nobody reads.
* **chunk** raises: a chunk would have to start from a carried state and a
  prefix hit from a snapshot of one, which the engine does not keep
  (ROADMAP.md, Reach R8).

The projections compute in the graph's dtype (bf16 on the chip) with float32
accumulation; ``dt``, ``exp(dt A)``, ``S`` and the ``C`` contraction are
float32, and ``S`` rests in the slot in float32: a bf16 state would round by
2^-8 a step for thousands of steps. The conv tail rests in the graph's dtype.

At rest the state is laid out for the chip's tiles: ``S`` as ``(n_slots, N,
E)`` (``E`` on the lanes; ``(.., E, N)`` would pad N = 16 to 128 lanes, eight
times the bytes) and the tail as ``(n_slots, (K - 1) * E)``.
"""
from __future__ import annotations

from ..ffconst import OperatorType
from .attention import _head_rms_norm, _inner_scope
from .base import Op, OpContext, no_chunk_carry, register_op


class _ALogInitializer:
    """``A_log[n, :] = log(n + 1)``: the S4D-real start every Mamba uses."""

    def __call__(self, key, shape, dtype):
        import jax.numpy as jnp

        n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)


class _DtBiasInitializer:
    """``b_dt = softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1]:
    Mamba's start, under which a state decays over hundreds of tokens and
    not over two (a zero bias gives ``dt`` near 0.7)."""

    def __call__(self, key, shape, dtype):
        import jax
        import jax.numpy as jnp

        lo, hi = jnp.log(1e-3), jnp.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def conv_tail_out(sv, hist, live, width: int):
    """A causal depthwise conv's tail ``(b, (width - 1) * channels)`` for
    the slot. ``hist (b, width - 1 + s, channels)`` holds the conv's inputs,
    row ``j`` that of position ``j - (width - 1)``: a prefill's tail is the
    rows at ``length .. length + width - 2`` (positions ``length - width + 1
    .. length - 1``, the leading zero pad standing for positions before the
    sequence), a decode step's its newest ``width - 1``, zero for a free
    slot (``live (b, 1, 1)``)."""
    import jax.numpy as jnp

    if sv.mode == "decode":
        tail = jnp.where(live, hist[:, 1:], 0)
    elif sv.lengths is not None:
        idx = sv.lengths[:, None] + jnp.arange(width - 1)[None, :]
        tail = jnp.take_along_axis(hist, idx[:, :, None], axis=1)
    else:
        tail = hist[:, hist.shape[1] - (width - 1):]
    return tail.reshape(hist.shape[0], -1)


def one_token_update(s, x, dt, b, c, a):
    """One step of the recurrence for every row: ``s (rows, N, E)`` f32,
    ``x, dt (rows, E)``, ``b, c (rows, N)``, ``a (N, E)`` -> ``(y (rows, E),
    s_new)``. One fused elementwise pass over the state."""
    import jax.numpy as jnp

    s_new = jnp.exp(dt[:, None, :] * a[None]) * s \
        + (dt * x)[:, None, :] * b[:, :, None]
    return jnp.sum(s_new * c[:, :, None], axis=1), s_new


@register_op(OperatorType.OP_SSM_MIXER)
class SSMMixerOp(Op):
    """attrs: inner_dim (E), state_dim (N), conv_width (K), dt_rank (R),
    conv_bias, proj_bias, norm_eps. input (batch, seq, dim) -> same shape.

    Weights: ``w_in`` (dim, 2E), ``conv_w`` (E, K), ``conv_b`` (E,) if
    conv_bias, ``w_x`` (E, R + 2N), ``dt_norm`` (R,), ``b_norm`` (N,),
    ``c_norm`` (N,), ``w_dt`` (R, E), ``b_dt`` (E,), ``a_log`` (N, E),
    ``d_skip`` (E,), ``w_out`` (E, dim); ``b_in`` (2E,) and ``b_out``
    (dim,) if proj_bias."""

    def _dims(self):
        a = self.attrs
        return (int(a["inner_dim"]), int(a["state_dim"]),
                int(a["conv_width"]), int(a["dt_rank"]))

    def infer_output_shapes(self, input_shapes):
        return [tuple(input_shapes[0])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (ConstantInitializer,
                                              DefaultWeightInitializer,
                                              UniformInitializer,
                                              ZeroInitializer)

        d = input_shapes[0][-1]
        E, N, K, R = self._dims()
        init = self.attrs.get("kernel_initializer") \
            or DefaultWeightInitializer()
        one, zero, t = ConstantInitializer(1.0), ZeroInitializer(), \
            self.data_type
        specs = {"w_in": ((d, 2 * E), t, init),
                 # a depthwise conv's fan-in is its K taps
                 "conv_w": ((E, K), t, UniformInitializer(
                     min_val=-K ** -0.5, max_val=K ** -0.5)),
                 "w_x": ((E, R + 2 * N), t, init),
                 "dt_norm": ((R,), t, one), "b_norm": ((N,), t, one),
                 "c_norm": ((N,), t, one),
                 "w_dt": ((R, E), t, init),
                 "b_dt": ((E,), t, _DtBiasInitializer()),
                 "a_log": ((N, E), t, _ALogInitializer()),
                 "d_skip": ((E,), t, one),
                 "w_out": ((E, d), t, init)}
        if self.attrs.get("conv_bias", True):
            specs["conv_b"] = ((E,), t, zero)
        if self.attrs.get("proj_bias", False):
            specs["b_in"] = ((2 * E,), t, zero)
            specs["b_out"] = ((d,), t, zero)
        return specs

    def slot_state_bytes(self, el: int = 0) -> int:
        from ..ffconst import size_of_datatype

        # the (N, E) float32 state and the conv's last K - 1 inputs
        E, N, K, _R = self._dims()
        return E * N * 4 + E * (K - 1) * (
            el or size_of_datatype(self.data_type))

    # ------------------------------------------------------------ the parts
    def _selection(self, params, x):
        """``(dt, B, C)`` of conv outputs ``x (b, s, E)``, float32."""
        import jax
        import jax.numpy as jnp

        _E, N, _K, R = self._dims()
        eps = float(self.attrs["norm_eps"])
        rbc = jnp.einsum("bse,ef->bsf", x, params["w_x"],
                         preferred_element_type=jnp.float32)
        # float32 in (the products accumulate in it), so float32 out
        r = _head_rms_norm(rbc[..., :R], params["dt_norm"], eps)
        b = _head_rms_norm(rbc[..., R:R + N], params["b_norm"], eps)
        c = _head_rms_norm(rbc[..., R + N:], params["c_norm"], eps)
        dt = jnp.einsum("bsr,re->bse", r.astype(x.dtype), params["w_dt"],
                        preferred_element_type=jnp.float32)
        return jax.nn.softplus(dt + params["b_dt"].astype(jnp.float32)), b, c

    def forward(self, params, inputs, ctx: OpContext):
        import jax
        import jax.numpy as jnp

        from ..kernels.selective_scan import (selective_scan,
                                              selective_scan_reference,
                                              use_selective_scan)

        u = inputs[0]                                   # (b, s, d)
        batch, seq, _d = u.shape
        E, N, K, _R = self._dims()
        sv = ctx.serving
        if sv is not None and sv.mode == "chunk":
            raise NotImplementedError(no_chunk_carry(
                self.name, "a state-space mixer's state"))
        decode = sv is not None and sv.mode == "decode"
        f32 = jnp.float32
        scope = lambda what: jax.named_scope(_inner_scope(self.name, what))
        live = None
        if decode:
            from ..serving.kvcache import live_slots

            # a free slot's state is held at zero
            live = live_slots(sv.block_tables)[:, None, None]
        with scope("in"):
            xz = jnp.einsum("bsd,df->bsf", u, params["w_in"])
            if "b_in" in params:
                xz = xz + params["b_in"]
            xp, z = xz[..., :E], xz[..., E:]
        with scope("conv"):
            if decode:
                tail, s0 = sv.cache_in[self.name]
                hist = jnp.concatenate(
                    [tail.reshape(batch, K - 1, E).astype(xp.dtype), xp],
                    axis=1)                             # (b, K, E)
            else:
                s0 = None
                hist = jnp.pad(xp, ((0, 0), (K - 1, 0), (0, 0)))
            w = params["conv_w"].astype(f32)
            conv = sum(hist[:, k:k + seq].astype(f32) * w[:, k]
                       for k in range(K))
            if "conv_b" in params:
                conv = conv + params["conv_b"].astype(f32)
            x = jax.nn.silu(conv).astype(u.dtype)
            if sv is not None:
                tail = conv_tail_out(sv, hist, live, K)
        with scope("proj"):
            dt, b, c = self._selection(params, x)
        with scope("scan"):
            a = -jnp.exp(params["a_log"].astype(f32))   # (N, E)
            if decode:
                y, s_last = one_token_update(
                    s0, x[:, 0].astype(f32), dt[:, 0], b[:, 0], c[:, 0], a)
                y, s_last = y[:, None, :], jnp.where(live, s_last, 0.0)
            else:
                lengths = sv.lengths if sv is not None else None
                scan = selective_scan if use_selective_scan(N) \
                    else selective_scan_reference
                y, s_last = scan(x, dt, b, c, a, lengths=lengths)
            y = y + params["d_skip"].astype(f32) * x.astype(f32)
        if sv is not None:
            sv.cache_out[self.name] = (tail, s_last)
        with scope("out"):
            g = (y * jax.nn.silu(z.astype(f32))).astype(u.dtype)
            out = jnp.einsum("bse,ed->bsd", g, params["w_out"],
                             preferred_element_type=f32).astype(u.dtype)
            if "b_out" in params:
                out = out + params["b_out"]
        return [out]

    def flops(self, input_shapes, output_shapes):
        b, s, d = input_shapes[0]
        E, N, K, R = self._dims()
        per_token = 2 * d * 2 * E + 2 * E * K + 2 * E * (R + 2 * N) \
            + 2 * R * E + 7 * E * N + 2 * E * d
        return b * s * per_token

    def parallelizable_dims(self, input_shapes):
        return {"batch": True}
