"""LayerNorm, RMSNorm, Softmax.

Reference: src/ops/layer_norm.cc (601 LoC, custom kernels), softmax.cc (cuDNN).
RMSNorm is a TPU-native extension (no reference analog; standard for LLM
parity). XLA fuses these; a Pallas fused-softmax lives in kernels/ for the
attention path.
"""
from __future__ import annotations

from ..ffconst import OperatorType
from .base import Op, OpContext, register_op


@register_op(OperatorType.OP_LAYERNORM)
class LayerNormOp(Op):
    """attrs: axes (list of ints), elementwise_affine, eps
    (reference builder: FFModel::layer_norm, src/ops/layer_norm.cc)."""

    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def _norm_shape(self, ishape):
        axes = [a % len(ishape) for a in self.attrs.get("axes", [len(ishape) - 1])]
        return tuple(ishape[a] for a in sorted(axes))

    def weight_specs(self, input_shapes):
        from ..execution.initializers import ConstantInitializer, ZeroInitializer

        if not self.attrs.get("elementwise_affine", True):
            return {}
        nshape = self._norm_shape(input_shapes[0])
        return {
            "scale": (nshape, self.data_type, ConstantInitializer(1.0)),
            "bias": (nshape, self.data_type, ZeroInitializer()),
        }

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        (x,) = inputs
        ndim = x.ndim
        axes = tuple(sorted(a % ndim for a in self.attrs.get("axes", [ndim - 1])))
        eps = self.attrs.get("eps", 1e-5)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        y = (xf - mean) / jnp.sqrt(var + eps)
        if "scale" in params:
            bshape = [x.shape[a] if a in axes else 1 for a in range(ndim)]
            y = y * params["scale"].reshape(bshape) + params["bias"].reshape(bshape)
        return [y.astype(x.dtype)]


#: the forms of an RMS norm's gain (``gain``); None: the stored scale itself
NORM_GAINS = ("sigmoid2",)


def norm_gain(w, form=None):
    """The gain an RMS norm multiplies by, from its stored ``scale``, in
    float32. ``None``: the scale itself (stored about 1). ``"sigmoid2"``
    (``ZeroCenteredGatedNorm`` with gating weight 2): ``2 sigmoid(w)``, ``w``
    stored about zero — gain 1 at ``w = 0``, never negative, at most 2. The
    published keys name the form and the constant, not the formula: this
    function (and its twin in the plain reference) is the one place another
    reading changes."""
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if form is None:
        return w
    if form == "sigmoid2":
        return 2.0 * jax.nn.sigmoid(w)
    raise ValueError(f"norm_gain: form {form!r} is none of {NORM_GAINS}")


@register_op(OperatorType.OP_RMSNORM)
class RMSNormOp(Op):
    """attrs: axes, eps, gain (off by default: a form of :func:`norm_gain`,
    whose scale is drawn N(0, 0.02) and not the constant 1). TPU-native
    extension for LLM blocks."""

    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import ConstantInitializer

        ishape = input_shapes[0]
        axes = [a % len(ishape) for a in self.attrs.get("axes", [len(ishape) - 1])]
        nshape = tuple(ishape[a] for a in sorted(axes))
        if self.attrs.get("gain"):
            from ..execution.initializers import NormInitializer

            return {"scale": (nshape, self.data_type,
                              NormInitializer(stddev=0.02))}
        return {"scale": (nshape, self.data_type, ConstantInitializer(1.0))}

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        (x,) = inputs
        ndim = x.ndim
        axes = tuple(sorted(a % ndim for a in self.attrs.get("axes", [ndim - 1])))
        eps = self.attrs.get("eps", 1e-6)
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
        y = xf / jnp.sqrt(ms + eps)
        bshape = [x.shape[a] if a in axes else 1 for a in range(ndim)]
        scale = params["scale"]
        if self.attrs.get("gain"):
            scale = norm_gain(scale, self.attrs["gain"])
        return [(y * scale.reshape(bshape)).astype(x.dtype)]


@register_op(OperatorType.OP_SOFTMAX)
class SoftmaxOp(Op):
    """attrs: axis (reference: src/ops/softmax.cc; -1 default like
    FFModel::softmax), use_pallas (opt-in: route MXU-aligned last-dim rows
    through the Pallas row-softmax kernel, kernels/softmax.py — the cuDNN
    softmax analog; XLA's fusion measured at parity on v5e, so the default
    path stays jax.nn.softmax)."""

    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def forward(self, params, inputs, ctx: OpContext):
        import jax.nn as jnn

        (x,) = inputs
        axis = self.attrs.get("axis", -1)
        from ..kernels.softmax import (pallas_softmax,
                                       should_use_pallas_softmax)

        if should_use_pallas_softmax(
                x, axis, opt_in=bool(self.attrs.get("use_pallas"))):
            return [pallas_softmax(x)]
        return [jnn.softmax(x, axis=axis)]
