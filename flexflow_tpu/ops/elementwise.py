"""Elementwise binary/unary ops, scalar ops, cast, dropout.

Reference: src/ops/element_binary.cc (812 LoC, broadcast support),
element_unary.cc (720, inplace option), cast.cc, dropout.cc. TPU-native these
are single jnp calls — XLA fuses them into neighboring matmuls, which is the
whole point of not hand-writing kernels for them.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from ..ffconst import OperatorType, dtype_to_jnp
from .base import Op, OpContext, register_op


def _broadcast_shape(a: Tuple[int, ...], b: Tuple[int, ...]) -> Tuple[int, ...]:
    return tuple(np.broadcast_shapes(a, b))


class _BinaryOp(Op):
    _fn_name = ""

    def infer_output_shapes(self, input_shapes):
        a, b = input_shapes
        return [_broadcast_shape(a, b)]

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        a, b = inputs
        fn = getattr(jnp, self._fn_name)
        return [fn(a, b)]

    def can_inplace_output(self):
        return True


@register_op(OperatorType.OP_EW_ADD)
class AddOp(_BinaryOp):
    _fn_name = "add"


@register_op(OperatorType.OP_EW_SUB)
class SubOp(_BinaryOp):
    _fn_name = "subtract"


@register_op(OperatorType.OP_EW_MUL)
class MulOp(_BinaryOp):
    _fn_name = "multiply"


@register_op(OperatorType.OP_EW_DIV)
class DivOp(_BinaryOp):
    _fn_name = "divide"


@register_op(OperatorType.OP_EW_MAX)
class MaxOp(_BinaryOp):
    _fn_name = "maximum"


@register_op(OperatorType.OP_EW_MIN)
class MinOp(_BinaryOp):
    _fn_name = "minimum"


class _UnaryOp(Op):
    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def _apply(self, x):
        raise NotImplementedError

    def forward(self, params, inputs, ctx: OpContext):
        return [self._apply(inputs[0])]

    def can_inplace_output(self):
        return True


def _make_unary(op_type: OperatorType, fn_src: str, name: str):
    """fn_src: 'jnn.<f>' or 'jnp.<f>'."""

    @register_op(op_type)
    class _U(_UnaryOp):
        def _apply(self, x):
            import jax.numpy as jnp
            import jax.nn as jnn

            mod, f = fn_src.split(".")
            return getattr({"jnp": jnp, "jnn": jnn}[mod], f)(x)

    _U.__name__ = name
    return _U


ReluOp = _make_unary(OperatorType.OP_RELU, "jnn.relu", "ReluOp")
SigmoidOp = _make_unary(OperatorType.OP_SIGMOID, "jnn.sigmoid", "SigmoidOp")
TanhOp = _make_unary(OperatorType.OP_TANH, "jnp.tanh", "TanhOp")
EluOp = _make_unary(OperatorType.OP_ELU, "jnn.elu", "EluOp")
GeluOp = _make_unary(OperatorType.OP_GELU, "jnn.gelu", "GeluOp")
SiluOp = _make_unary(OperatorType.OP_SILU, "jnn.silu", "SiluOp")
ExpOp = _make_unary(OperatorType.OP_EXP, "jnp.exp", "ExpOp")
LogOp = _make_unary(OperatorType.OP_LOG, "jnp.log", "LogOp")
SinOp = _make_unary(OperatorType.OP_SIN, "jnp.sin", "SinOp")
CosOp = _make_unary(OperatorType.OP_COS, "jnp.cos", "CosOp")
SqrtOp = _make_unary(OperatorType.OP_SQRT, "jnp.sqrt", "SqrtOp")
CeilOp = _make_unary(OperatorType.OP_CEIL, "jnp.ceil", "CeilOp")
RoundOp = _make_unary(OperatorType.OP_ROUND, "jnp.round", "RoundOp")


@register_op(OperatorType.OP_IDENTITY)
class IdentityOp(_UnaryOp):
    def _apply(self, x):
        return x


@register_op(OperatorType.OP_RSQRT)
class RsqrtOp(_UnaryOp):
    def _apply(self, x):
        import jax.lax as lax

        return lax.rsqrt(x)


@register_op(OperatorType.OP_POW)
class PowOp(_UnaryOp):
    def _apply(self, x):
        import jax.numpy as jnp

        return jnp.power(x, self.attrs["exponent"])


@register_op(OperatorType.OP_SCALAR_MULTIPLY)
class ScalarMultiplyOp(_UnaryOp):
    def _apply(self, x):
        return x * self.attrs["scalar"]


@register_op(OperatorType.OP_SCALAR_ADD)
class ScalarAddOp(_UnaryOp):
    def _apply(self, x):
        return x + self.attrs["scalar"]


@register_op(OperatorType.OP_SCALAR_SUB)
class ScalarSubOp(_UnaryOp):
    def _apply(self, x):
        return x - self.attrs["scalar"]


@register_op(OperatorType.OP_SCALAR_TRUE_DIV)
class ScalarTrueDivOp(_UnaryOp):
    def _apply(self, x):
        return x / self.attrs["scalar"]


@register_op(OperatorType.OP_CAST)
class CastOp(Op):
    """reference: src/ops/cast.cc."""

    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def output_dtype(self, input_dtypes):
        return self.attrs["target_dtype"]

    def forward(self, params, inputs, ctx: OpContext):
        return [inputs[0].astype(dtype_to_jnp(self.attrs["target_dtype"]))]


@register_op(OperatorType.OP_DROPOUT)
class DropoutOp(Op):
    """reference: src/ops/dropout.cc (cuDNN dropout state -> jax.random here)."""

    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def forward(self, params, inputs, ctx: OpContext):
        import jax

        (x,) = inputs
        rate = float(self.attrs.get("rate", 0.5))
        if not ctx.training or rate <= 0.0:
            return [x]
        keep = 1.0 - rate
        mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
        return [(x * mask) / keep]
