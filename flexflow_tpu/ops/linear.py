"""Linear (dense) operator — the canonical op (reference: src/ops/linear.cc:1184,
kernels src/ops/kernels/linear_kernels.cu).

TPU-native: a single jnp.dot that XLA tiles onto the MXU, with the activation
fused by XLA (the reference fuses via cuBLAS epilogue / cuDNN activation).
Weight layout is (in_dim, out_dim) so row/column tensor-parallelism is a
sharding of one weight dim:

* column-parallel = shard ``out_dim`` (reference: replicate-linear-combine xfer,
  substitution.cc:3226) — output is sharded, no collective.
* row-parallel = shard ``in_dim`` (reference: partition-linear-combine,
  substitution.cc:3041) — output needs a psum, inserted by XLA when the
  contraction dim is sharded.
"""
from __future__ import annotations


import jax
import numpy as np

from ..ffconst import ActiMode, OperatorType
from .base import Op, OpContext, register_op


@jax.custom_vjp
def bias_add(y, b):
    """Broadcast bias add with a layout-friendly gradient.

    The naive ``y + b`` backward asks XLA to reduce dy over EVERY leading
    axis at once; at bf16 that lowers to the multi-axis convert+reduce
    fusion that showed up as 2.2 ms/step of the r05 seq-4096 baseline
    (it re-reads dy once per reduced axis in a minor-dim-hostile order).
    The custom backward collapses the leading axes FIRST — one reshape to
    (rows, out_dim), which is free on a row-major layout — then does a
    single-axis f32 column reduce, the shape the TPU reducer streams at
    full HBM bandwidth."""
    return y + b


def _bias_add_fwd(y, b):
    # residual is the (out_dim,) bias itself — only its dtype is consumed,
    # but a raw numpy dtype is not a pytree leaf JAX transforms accept
    return y + b, b


def _bias_add_bwd(b, g):
    import jax.numpy as jnp

    rows = g.reshape(-1, g.shape[-1])
    db = jnp.sum(rows.astype(jnp.float32), axis=0).astype(b.dtype)
    return g, db


bias_add.defvjp(_bias_add_fwd, _bias_add_bwd)


def apply_activation(x, activation: ActiMode):
    import jax.numpy as jnp
    import jax.nn as jnn

    if activation == ActiMode.AC_MODE_NONE:
        return x
    if activation == ActiMode.AC_MODE_RELU:
        return jnn.relu(x)
    if activation == ActiMode.AC_MODE_SIGMOID:
        return jnn.sigmoid(x)
    if activation == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if activation == ActiMode.AC_MODE_GELU:
        return jnn.gelu(x)
    if activation == ActiMode.AC_MODE_SILU:
        return jnn.silu(x)
    raise ValueError(f"unknown activation {activation}")


def apply_weight_regularizer(spec, kernel, ctx: OpContext) -> None:
    """("l1"|"l2", lambda) weight-decay penalty added to the training loss
    via the aux-loss hook (reference: keras/regularizers.py carries the
    RegularizerMode into the Linear layer)."""
    if not spec or not ctx.training or ctx.aux_losses is None:
        return
    kind, lam = spec
    import jax.numpy as jnp

    w = kernel.astype(jnp.float32)
    if kind == "l1":
        ctx.aux_losses.append(lam * jnp.sum(jnp.abs(w)))
    elif kind == "l2":
        ctx.aux_losses.append(lam * jnp.sum(w * w))
    else:
        raise ValueError(f"unknown regularizer kind {kind!r}")


@register_op(OperatorType.OP_LINEAR)
class LinearOp(Op):
    """attrs: out_dim, activation, use_bias, kernel_initializer, bias_initializer."""

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        return [tuple(ishape[:-1]) + (self.attrs["out_dim"],)]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (DefaultBiasInitializer,
                                              DefaultWeightInitializer)

        in_dim = input_shapes[0][-1]
        out_dim = self.attrs["out_dim"]
        specs = {
            "kernel": ((in_dim, out_dim), self.data_type,
                       self.attrs.get("kernel_initializer")
                       or DefaultWeightInitializer()),
        }
        if self.attrs.get("use_bias", True):
            specs["bias"] = ((out_dim,), self.data_type,
                             self.attrs.get("bias_initializer")
                             or DefaultBiasInitializer())
        return specs

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        (x,) = inputs
        kernel = params["kernel"]
        y = jnp.dot(x, kernel, preferred_element_type=jnp.float32)
        y = y.astype(x.dtype)
        if "bias" in params:
            y = bias_add(y, params["bias"])
        apply_weight_regularizer(self.attrs.get("kernel_regularizer"),
                                 kernel, ctx)
        return [apply_activation(y, self.attrs.get("activation",
                                                   ActiMode.AC_MODE_NONE))]

    def flops(self, input_shapes, output_shapes):
        ishape = input_shapes[0]
        return 2 * int(np.prod(ishape)) * self.attrs["out_dim"]

    def parallelizable_dims(self, input_shapes):
        ndim = len(input_shapes[0])
        return {
            "batch": True,
            # shard out_dim (column-parallel): kernel dim 1, bias dim 0
            "channel_out": {"output_dim": ndim - 1,
                            "weights": {"kernel": 1, "bias": 0}},
            # shard in_dim (row-parallel): kernel dim 0; output unreduced -> psum
            "channel_in": {"input_dim": ndim - 1, "weights": {"kernel": 0},
                           "reduces_output": True},
        }


def swiglu(g, u, limit=None):
    """``silu(g) * u`` in the operands' dtype (float32 here); ``limit`` L:
    the clamped form ``silu(min(g, L)) * clip(u, -L, L)`` — the gate cut
    from above alone (``silu`` is bounded below), the linear half from both
    sides."""
    import jax.numpy as jnp

    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jax.nn.silu(g) * u


@register_op(OperatorType.OP_GATED_MLP)
class GatedMLPOp(Op):
    """The gated (SwiGLU) MLP as one node: ``W_down(silu(W_gate x) * W_up x)``,
    no biases. attrs: intermediate, kernel_initializer, limit (off by
    default; L: ``silu(min(g, L)) * clip(u, -L, L)``, :func:`swiglu`).
    Weights ``gate`` and ``up`` (in_dim, intermediate), ``down``
    (intermediate, in_dim); the product with the gate is taken in float32."""

    def infer_output_shapes(self, input_shapes):
        return [tuple(input_shapes[0])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import DefaultWeightInitializer

        h, i = input_shapes[0][-1], self.attrs["intermediate"]
        init = self.attrs.get("kernel_initializer") \
            or DefaultWeightInitializer()
        return {"gate": ((h, i), self.data_type, init),
                "up": ((h, i), self.data_type, init),
                "down": ((i, h), self.data_type, init)}

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        (x,) = inputs
        g = jnp.dot(x, params["gate"], preferred_element_type=jnp.float32)
        u = jnp.dot(x, params["up"], preferred_element_type=jnp.float32)
        a = swiglu(g, u, self.attrs.get("limit")).astype(x.dtype)
        return [jnp.dot(a, params["down"],
                        preferred_element_type=jnp.float32).astype(x.dtype)]

    def flops(self, input_shapes, output_shapes):
        return 6 * int(np.prod(input_shapes[0])) * self.attrs["intermediate"]

    def parallelizable_dims(self, input_shapes):
        return {
            "batch": True,
            # tensor parallelism over the intermediate width: gate/up by
            # columns, down by rows; the output is a partial sum
            "channel_in": {"weights": {"gate": 1, "up": 1, "down": 0},
                           "reduces_output": True},
        }
