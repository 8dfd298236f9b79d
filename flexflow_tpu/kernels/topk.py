"""Pallas row-wise top-k kernel for TPU.

Reference analog: the hand-written top-k GPU kernel behind src/ops/topk.cc
(kernels/topk_kernels.cu — per-thread heaps merged across the warp). SURVEY
§7 lists top-k among the ops worth a Pallas kernel. On TPU the natural
formulation for the small ``k`` MoE routing uses (k <= 4) is ``k`` unrolled
max+argmax sweeps over a row tile held in VMEM: one HBM read of the scores
per element total, versus lax.top_k's generic sort lowering. Ties resolve
to the lowest index, matching ``jax.lax.top_k``.

Backward matches lax.top_k's vjp: the value cotangent scatters to the
selected positions (indices are non-differentiable), done as an XLA
one-hot scatter — no kernel needed on the backward path.

Routing: ``TopKOp`` uses this only on explicit opt-in
(attrs["use_pallas"]) — like the softmax kernel, XLA's top-k lowering is
already competitive at MoE-routing sizes, and the kernel exists for parity
with the reference's dedicated kernel and as a fusion anchor for a future
router epilogue. Interpret mode serves the CPU test mesh."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import numpy as np

from ._common import (on_tpu as _on_tpu,
                      pick_block_rows as _pick_block_rows,
                      resolve_interpret as _resolve_interpret)

MAX_PALLAS_K = 8  # the unrolled-sweep formulation only pays off for small k


def _topk_kernel(k: int, x_ref, vals_ref, idx_ref):
    import jax.numpy as jnp
    from jax import lax

    x = x_ref[...].astype(jnp.float32)  # (block_rows, dim)
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    neg_inf = jnp.float32(-np.inf)
    # selection key clamps -inf inputs to -FLT_MAX so -inf stays reserved
    # for "already taken": rows with fewer than k finite entries must still
    # return k DISTINCT indices (the lax.top_k contract; MoE routers mask
    # logits with -inf, so this path is live). A genuine -FLT_MAX input
    # ties with masked -inf entries — resolved by lowest index like any tie.
    key = jnp.maximum(x, jnp.float32(np.finfo(np.float32).min))
    for j in range(k):  # unrolled: k is static and small
        i = jnp.argmax(key, axis=-1).astype(jnp.int32)
        sel = cols == i[:, None]
        # original value at i (not the clamped key): x[row, i]
        vals_ref[:, j] = jnp.max(jnp.where(sel, x, neg_inf),
                                 axis=-1).astype(vals_ref.dtype)
        idx_ref[:, j] = i
        key = jnp.where(sel, neg_inf, key)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def pallas_topk(x, k: int, interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Top-k over the last dim of an arbitrary-rank array.

    Returns (values, indices) with values sorted descending — the
    ``jax.lax.top_k`` contract."""
    out, _ = _topk_fwd(x, k, interpret)
    return out


def _topk_call(x, k: int, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    shape = x.shape
    dim = shape[-1]
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    xr = x.reshape(rows, dim)
    block_rows = _pick_block_rows(rows, dim)
    in_spec = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k),
        grid=(rows // block_rows,),
        in_specs=[in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, k), x.dtype),
                   jax.ShapeDtypeStruct((rows, k), jnp.int32)],
        interpret=interpret,
    )(xr)
    out_shape = shape[:-1] + (k,)
    return vals.reshape(out_shape), idx.reshape(out_shape)


def _topk_fwd(x, k: int, interpret: Optional[bool]):
    vals, idx = _topk_call(x, k, _resolve_interpret(interpret))
    return (vals, idx), (idx, x.shape[-1])


def _topk_bwd(k: int, interpret: Optional[bool], res, cotangents):
    import jax.nn as jnn
    import jax.numpy as jnp

    idx, dim = res
    g_vals, _ = cotangents  # indices carry no cotangent
    onehot = jnn.one_hot(idx, dim, dtype=g_vals.dtype)  # (..., k, dim)
    dx = jnp.sum(onehot * g_vals[..., None], axis=-2)
    return (dx,)


pallas_topk.defvjp(_topk_fwd, _topk_bwd)


def should_use_pallas_topk(x, k: int, opt_in: bool = False) -> bool:
    """Opt-in only (attrs["use_pallas"]); requires TPU, small k, last-axis
    rows wide enough to amortize the sweep and lane-aligned for the VPU."""
    import jax.numpy as jnp

    if not opt_in:
        return False
    if k > MAX_PALLAS_K or k < 1:
        return False
    if x.ndim < 2 or x.shape[-1] < 128 or x.shape[-1] % 128 != 0:
        return False
    # the kernel computes in f32 with -inf masking: integer (and f64) inputs
    # would silently lose precision, so only sub-f32 floats route here
    if not jnp.issubdtype(x.dtype, jnp.floating) or \
            jnp.dtype(x.dtype).itemsize > 4:
        return False
    return _on_tpu()
