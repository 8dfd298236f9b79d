"""Recurrent ops: LSTM.

The reference ships LSTM only as hand-written CUDA in the legacy NMT app
(nmt/lstm.cu — cuDNN RNN descriptors over LSTM_PER_NODE_LENGTH=10 chunks,
nmt/rnn.h:242) that predates the FFModel op set. Here LSTM is a first-class
op, TPU-native: one fused gate matmul per step under ``lax.scan`` — the
(batch, 4*hidden) GEMM rides the MXU, scan keeps the trace size constant
regardless of sequence length, and the op is differentiable through scan for
free (the reference hand-writes the backward pass in lstm.cu).

Layout: input (batch, seq, in_dim) -> outputs (batch, seq, hidden).
Optional second input: initial state (batch, 2*hidden) = [h, c] concatenated
(how an NMT decoder receives the encoder's final state).
Outputs: [sequence_outputs, final_state(batch, 2*hidden)].
"""
from __future__ import annotations

from ..ffconst import OperatorType
from .base import Op, OpContext, no_chunk_carry, register_op


@register_op(OperatorType.OP_LSTM)
class LSTMOp(Op):
    """attrs: hidden_size; optional 2nd input = initial [h, c]."""

    def infer_output_shapes(self, input_shapes):
        b, s, _ = input_shapes[0]
        h = self.attrs["hidden_size"]
        return [(b, s, h), (b, 2 * h)]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (GlorotUniformInitializer,
                                              ZeroInitializer)

        in_dim = input_shapes[0][-1]
        h = self.attrs["hidden_size"]
        glorot = GlorotUniformInitializer()
        zero = ZeroInitializer()
        return {
            "wx": ((in_dim, 4 * h), self.data_type, glorot),
            "wh": ((h, 4 * h), self.data_type, glorot),
            "bias": ((4 * h,), self.data_type, zero),
        }

    def slot_state_bytes(self, el: int = 0) -> int:
        from ..ffconst import size_of_datatype

        # the carry [h, c]
        return 2 * int(self.attrs["hidden_size"]) * (
            el or size_of_datatype(self.data_type))

    def forward(self, params, inputs, ctx: OpContext):
        import jax.lax as lax
        import jax.numpy as jnp

        x = inputs[0]  # (b, s, d)
        b = x.shape[0]
        h = self.attrs["hidden_size"]
        sv = ctx.serving  # serving engine prefill/decode (ISSUE 6)
        if sv is not None and sv.mode == "chunk":
            # the engine refuses chunks and the prefix cache for a graph
            # with a recurrent node at construction; this is the backstop
            raise NotImplementedError(no_chunk_carry(
                self.name, "the LSTM's [h, c] carry"))
        if sv is not None and sv.mode == "decode" and sv.cache_in is not None \
                and self.name in sv.cache_in:
            # the LSTM's recurrent carry IS its decode state: resume from
            # the cached [h, c] (which already folds any graph-provided
            # initial_state through the prefill scan)
            state = sv.cache_in[self.name]
            h0, c0 = state[:, :h], state[:, h:]
        elif len(inputs) > 1:
            h0, c0 = inputs[1][:, :h], inputs[1][:, h:]
        else:
            h0 = jnp.zeros((b, h), x.dtype)
            c0 = jnp.zeros((b, h), x.dtype)
        wx, wh, bias = params["wx"], params["wh"], params["bias"]

        # precompute input projections for ALL steps in one big MXU-friendly
        # GEMM: (b*s, d) @ (d, 4h); the scan then only does the (b,h)@(h,4h)
        # recurrent matmul per step
        xproj = jnp.einsum("bsd,dg->bsg", x, wx) + bias

        from jax.nn import sigmoid

        def step(carry, xp_t):
            h_t, c_t = carry
            gates = xp_t + h_t @ wh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c_n = sigmoid(f) * c_t + sigmoid(i) * jnp.tanh(g)
            h_n = sigmoid(o) * jnp.tanh(c_n)
            return (h_n, c_n), (h_n, c_n)

        (h_f, c_f), (ys, cs) = lax.scan(step, (h0, c0),
                                        jnp.swapaxes(xproj, 0, 1))
        outputs = jnp.swapaxes(ys, 0, 1)  # (b, s, h)
        final_state = jnp.concatenate([h_f, c_f], axis=-1)
        if sv is not None:
            if sv.mode == "prefill" and sv.lengths is not None:
                # right-padded prompt: the carry to hand decode is the state
                # at the LAST REAL token (length-1), not at the padded tail
                # the scan kept marching through
                states = jnp.concatenate(
                    [jnp.swapaxes(ys, 0, 1), jnp.swapaxes(cs, 0, 1)],
                    axis=-1)  # (b, s, 2h)
                idx = jnp.clip(sv.lengths - 1, 0, states.shape[1] - 1)
                sv.cache_out[self.name] = jnp.take_along_axis(
                    states, idx[:, None, None], axis=1)[:, 0]
            else:
                sv.cache_out[self.name] = final_state
        return [outputs, final_state]

    def flops(self, input_shapes, output_shapes):
        b, s, d = input_shapes[0]
        h = self.attrs["hidden_size"]
        # per step: x@wx (shared precompute) + h@wh, 4 gates
        return 2 * b * s * (d * 4 * h + h * 4 * h)

    def parallelizable_dims(self, input_shapes):
        return {"batch": True}
