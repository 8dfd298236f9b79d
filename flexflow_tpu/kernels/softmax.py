"""Pallas row-softmax kernel for TPU.

Reference analog: the cuDNN softmax kernel behind src/ops/softmax.cc
(kernels/softmax_kernels.cu). SURVEY §7 lists softmax among the ops worth a
Pallas kernel: XLA's fused softmax materializes the row max/sum reductions
through HBM for large rows, while this kernel keeps one (block_rows, dim)
tile resident in VMEM per grid step — one HBM read + one write per element.
Backward uses the standard identity dsm = p * (g - sum(p * g)) as a second
rowwise kernel via ``jax.custom_vjp``.

Measured on v5e (fwd+bwd, bf16): 0.675 ms vs jax.nn.softmax's 0.694 ms at
(1024, 8192) and 0.789 vs 0.738 at (4096, 4096) — XLA's softmax fusion is
already at parity on TPU, so SoftmaxOp routes here only on explicit opt-in
(attrs["use_pallas"]); the kernel exists for parity with the reference's
dedicated softmax kernel and as the building block for fused epilogues.
Interpret mode serves the CPU test mesh."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from ._common import (on_tpu as _on_tpu,
                      pick_block_rows as _pick_block_rows,
                      resolve_interpret as _resolve_interpret)


def _softmax_fwd_kernel(x_ref, o_ref):
    import jax.numpy as jnp

    x = x_ref[...].astype(jnp.float32)  # (block_rows, dim)
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    o_ref[...] = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_bwd_kernel(p_ref, g_ref, o_ref):
    import jax.numpy as jnp

    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    inner = jnp.sum(p * g, axis=-1, keepdims=True)
    o_ref[...] = (p * (g - inner)).astype(o_ref.dtype)


def _rowwise_call(kernel, args, rows: int, dim: int, out_dtype,
                  block_rows: int, interpret: bool):
    from jax.experimental import pallas as pl

    spec = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, dim), out_dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def pallas_softmax(x, interpret: Optional[bool] = None):
    """Softmax over the last dim of an arbitrary-rank array."""
    out, _ = _fwd(x, interpret)
    return out


def _fwd(x, interpret):
    shape = x.shape
    dim = shape[-1]
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    xr = x.reshape(rows, dim)
    p = _rowwise_call(_softmax_fwd_kernel, [xr], rows, dim, x.dtype,
                      _pick_block_rows(rows, dim),
                      _resolve_interpret(interpret))
    return p.reshape(shape), p


def _bwd(interpret, p, g):
    shape = g.shape
    dim = shape[-1]
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    dx = _rowwise_call(_softmax_bwd_kernel, [p, g.reshape(rows, dim)],
                       rows, dim, g.dtype, _pick_block_rows(rows, dim),
                       _resolve_interpret(interpret))
    return (dx.reshape(shape),)


pallas_softmax.defvjp(_fwd, _bwd)


def should_use_pallas_softmax(x, axis: int, opt_in: bool = False) -> bool:
    """Valid only for last-axis softmax with MXU-aligned rows on TPU, and
    only on explicit opt-in: measured at parity with XLA's fused softmax on
    v5e (module docstring), so the default path stays jax.nn.softmax."""
    if not opt_in:
        return False
    if axis not in (-1, x.ndim - 1):
        return False
    if x.shape[-1] < 1024 or x.shape[-1] % 128 != 0:
        return False
    rows = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    if rows == 0 or x.shape[-1] == 0:
        return False
    return _on_tpu()
