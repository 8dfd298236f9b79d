"""Mixture-of-Experts building blocks: GroupBy, Aggregate, AggregateSpec,
Experts, Cache.

Reference: src/ops/group_by.cc (534 LoC, ragged scatter with capacity factor
``alpha``), aggregate.cc (569, gate-weighted gather + load-balance loss term
``lambda_bal``), aggregate_spec.cc (519, speculative variant), cache.cc (291).

TPU-native design (SURVEY §7 hard-part 4): the reference's dynamic ragged
routing becomes **fixed-capacity scatter/gather dispatch** — per-token
destination slots computed from a cumulative count (O(tokens·experts) int32,
no (tokens, experts, capacity) one-hot blow-up), scattered with
``.at[].add`` and gathered back by slot index; both directions differentiate
through XLA. Capacity = ceil(k * batch * alpha / n), matching the
reference's per-expert buffer; overflowing tokens are dropped exactly as the
reference drops them when the buffer fills (priority = scan order,
group_by.cu). GroupBy and Aggregate recompute the same deterministic
dispatch from ``assign`` so they stay consistent without ragged state.

``Experts`` (OP_EXPERTS) is the TPU-native batched form of the reference's
per-expert Linear nodes: all experts' FFN weights stacked into one
(n, d_in, d_out) tensor driven by a batched matmul on the MXU, shardable
over the expert dim — the expert-parallel strategy the reference expresses
with per-expert MachineViews becomes one NamedSharding axis, and the
token all-to-all is emitted by XLA at the sharding boundary.
"""
from __future__ import annotations

import functools

import numpy as np

from ..ffconst import OperatorType
from .base import Op, OpContext, register_op
from .linear import swiglu


def moe_capacity(k: int, batch: int, alpha: float, n: int) -> int:
    return int(np.ceil(k * batch * alpha / n))


def dispatch_indices(assign_flat, n: int, capacity: int):
    """assign_flat: (t,) int in [0, n) -> (dest (t,), keep (t,)).

    ``dest`` is the flat slot ``expert * capacity + position`` where each
    token lands; ``keep`` is False for tokens past their expert's capacity
    (dropped, like the reference when the buffer fills). Position is the
    token's rank among same-expert tokens in scan order (group_by.cu packs
    in this order). O(t·n) int32 intermediate — the (t, n, cap) one-hot of
    the dense-dispatch formulation never materializes."""
    import jax.nn as jnn
    import jax.numpy as jnp

    onehot = jnn.one_hot(assign_flat, n, dtype=jnp.int32)  # (t, n)
    pos_all = jnp.cumsum(onehot, axis=0) - 1  # (t, n)
    pos = jnp.take_along_axis(pos_all, assign_flat[:, None], axis=1)[:, 0]
    keep = pos < capacity
    dest = assign_flat * capacity + jnp.clip(pos, 0, capacity - 1)
    return dest, keep


def dispatch_mask(assign, n: int, capacity: int):
    """assign: (tokens,) -> (tokens, n, capacity) one-hot dispatch tensor.

    Kept as the reference implementation for the alignment tests (grads of
    the scatter path are verified against it); production ops use
    ``dispatch_indices``."""
    import jax.nn as jnn
    import jax.numpy as jnp

    expert_onehot = jnn.one_hot(assign, n, dtype=jnp.int32)  # (t, n)
    pos = jnp.cumsum(expert_onehot, axis=0) * expert_onehot - 1  # (t, n)
    pos_clipped = jnp.clip(pos, 0, capacity - 1)
    keep = (pos >= 0) & (pos < capacity)
    slot = jnn.one_hot(pos_clipped, capacity, dtype=jnp.int32)  # (t, n, cap)
    return slot * keep[..., None]  # (t, n, cap) in {0,1}


def _scatter_group(x_flat, assign_flat, n: int, cap: int):
    """(t, d) tokens -> (n, cap, d) expert buffers via scatter-add."""
    import jax.numpy as jnp

    d = x_flat.shape[-1]
    dest, keep = dispatch_indices(assign_flat, n, cap)
    contrib = x_flat * keep[:, None].astype(x_flat.dtype)
    grouped = jnp.zeros((n * cap, d), x_flat.dtype).at[dest].add(contrib)
    return grouped.reshape(n, cap, d)


@register_op(OperatorType.OP_GROUP_BY)
class GroupByOp(Op):
    """attrs: n (num experts), alpha (capacity factor), stacked (bool —
    TPU-native: emit one (n, cap, d) tensor instead of n (cap, d) tensors,
    feeding the batched Experts op).

    inputs: (input (batch, d), assign (batch, k) int)
    outputs: n tensors of (capacity, d) — reference: FFModel::group_by,
    src/ops/group_by.cc — or [(n, capacity, d)] when stacked.
    """

    def _cap(self, input_shapes):
        (batch, _d), (_, k) = input_shapes
        n = self.attrs["n"]
        return moe_capacity(k, batch, self.attrs.get("alpha", 1.0), n)

    def infer_output_shapes(self, input_shapes):
        (_batch, d) = input_shapes[0]
        n = self.attrs["n"]
        cap = self._cap(input_shapes)
        if self.attrs.get("stacked"):
            return [(n, cap, d)]
        return [(cap, d)] * n

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        x, assign = inputs
        batch, d = x.shape
        k = assign.shape[1]
        n = self.attrs["n"]
        cap = moe_capacity(k, batch, self.attrs.get("alpha", 1.0), n)
        assign_flat = assign.reshape(-1).astype(jnp.int32)  # (batch*k,)
        x_flat = jnp.repeat(x, k, axis=0)  # token order matches assign_flat
        grouped = _scatter_group(x_flat, assign_flat, n, cap)
        if self.attrs.get("stacked"):
            return [grouped]
        return [grouped[e] for e in range(n)]

    def parallelizable_dims(self, input_shapes):
        # expert parallelism: the expert dim shards over the model axis
        # (reference: per-expert MachineViews)
        return {"batch": False, "expert": True}


@register_op(OperatorType.OP_EXPERTS)
class ExpertsOp(Op):
    """Batched expert FFN (TPU-native; replaces the reference's n separate
    Linear ops consuming group_by outputs — src/ops/moe.cc:20-45 builds
    those): one (n, d_in, out_dim) weight, one batched matmul.

    attrs: n, out_dim, activation, use_bias.
    inputs: (dispatched (n, cap, d),)
    output: (n, cap, out_dim).
    Expert-parallel: shard dim 0 of weights/activations over the model axis.
    """

    def infer_output_shapes(self, input_shapes):
        n, cap, _d = input_shapes[0]
        return [(n, cap, self.attrs["out_dim"])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (DefaultBiasInitializer,
                                              DefaultWeightInitializer)

        n, _cap, d = input_shapes[0]
        out = self.attrs["out_dim"]
        specs = {"kernel": ((n, d, out), self.data_type,
                            self.attrs.get("kernel_initializer")
                            or DefaultWeightInitializer())}
        if self.attrs.get("use_bias", True):
            specs["bias"] = ((n, out), self.data_type,
                             DefaultBiasInitializer())
        return specs

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        (x,) = inputs  # (n, cap, d)
        y = jnp.einsum("ncd,ndo->nco", x, params["kernel"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        if "bias" in params:
            y = y + params["bias"][:, None, :].astype(y.dtype)
        from ..ffconst import ActiMode
        from .linear import apply_activation

        return [apply_activation(y, self.attrs.get(
            "activation", ActiMode.AC_MODE_NONE) or ActiMode.AC_MODE_NONE)]

    def flops(self, input_shapes, output_shapes):
        n, cap, d = input_shapes[0]
        return 2 * n * cap * d * self.attrs["out_dim"]

    def parallelizable_dims(self, input_shapes):
        return {"batch": False, "expert": True}


def _combine_tokens(exp_preds, gate_preds, gate_assign, n: int,
                    weighted: bool = True):
    """(n, cap, d) expert outputs -> (batch, k, d) per-assignment rows."""
    import jax.numpy as jnp

    batch, k = gate_assign.shape
    cap = exp_preds.shape[1]
    d = exp_preds.shape[2]
    assign_flat = gate_assign.reshape(-1).astype(jnp.int32)
    dest, keep = dispatch_indices(assign_flat, n, cap)
    gathered = exp_preds.reshape(n * cap, d)[dest]  # (t, d)
    gathered = gathered * keep[:, None].astype(gathered.dtype)
    if weighted:
        gathered = gathered * gate_preds.reshape(-1)[:, None].astype(
            gathered.dtype)
    return gathered.reshape(batch, k, d)


def _load_balance_aux(gate_assign, full_gate, n: int, lambda_bal: float,
                      ctx: OpContext):
    """The lambda_bal surrogate (reference: aggregate.cu backward): load_e =
    fraction of routed (token, k) assignments to expert e — ALL k slots, not
    just top-1 — times mean gate probability, summed over experts."""
    import jax.nn as jnn
    import jax.numpy as jnp

    if not lambda_bal or not ctx.training or ctx.aux_losses is None:
        return
    assign_all = gate_assign.reshape(-1).astype(jnp.int32)  # (batch*k,)
    load = jnp.mean(jnn.one_hot(assign_all, n, dtype=jnp.float32), axis=0)
    importance = jnp.mean(full_gate.astype(jnp.float32), axis=0)
    ctx.aux_losses.append(lambda_bal * n * jnp.sum(load * importance))


@register_op(OperatorType.OP_AGGREGATE)
class AggregateOp(Op):
    """attrs: n, lambda_bal.

    inputs: (gate_preds (batch, k), gate_assign (batch, k),
             true_gate_assign (batch, k), full_gate_grads (batch, n),
             exp_pred_0..exp_pred_{n-1} each (capacity, d) — or one stacked
             (n, capacity, d) tensor)
    output: (batch, d) — reference: src/ops/aggregate.cc. The load-balance
    term flows through autodiff via the aux-loss hook (the reference
    hand-codes it in aggregate.cu's backward).
    """

    def infer_output_shapes(self, input_shapes):
        batch = input_shapes[0][0]
        d = input_shapes[4][-1]
        return [(batch, d)]

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        gate_preds, gate_assign = inputs[0], inputs[1]
        if len(inputs) == 5 and inputs[4].ndim == 3:
            exp_preds = inputs[4]  # stacked (n, cap, d)
        else:
            exp_preds = jnp.stack(inputs[4:], axis=0)
        n = self.attrs["n"]
        rows = _combine_tokens(exp_preds, gate_preds, gate_assign, n)
        out = rows.sum(axis=1)  # (batch, d)
        _load_balance_aux(gate_assign, inputs[3], n,
                          self.attrs.get("lambda_bal", 0.0), ctx)
        return [out.astype(exp_preds.dtype)]


@register_op(OperatorType.OP_AGG_SPEC)
class AggregateSpecOp(Op):
    """Speculative aggregation: one output row per (token, assignment) so the
    loss supervises every expert's prediction; labels are replicated k times
    by compile (reference: aggregate_spec.cc; model.cc:2875-2877).
    """

    def infer_output_shapes(self, input_shapes):
        batch, k = input_shapes[1]
        d = input_shapes[4][-1]
        return [(batch * k, d)]

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        gate_assign = inputs[1]
        if len(inputs) == 5 and inputs[4].ndim == 3:
            exp_preds = inputs[4]
        else:
            exp_preds = jnp.stack(inputs[4:], axis=0)
        n = self.attrs["n"]
        batch, k = gate_assign.shape
        rows = _combine_tokens(exp_preds, None, gate_assign, n,
                               weighted=False)
        _load_balance_aux(gate_assign, inputs[3], n,
                          self.attrs.get("lambda_bal", 0.0), ctx)
        return [rows.reshape(batch * k, -1).astype(exp_preds.dtype)]


@register_op(OperatorType.OP_CACHE)
class CacheOp(Op):
    """Caches an intermediate tensor across iterations, re-using it while a
    user score function deems it fresh (reference: src/ops/cache.cc:291; pairs
    with dynamic recompile, recompile.h). The executor threads a cache-state
    pytree: forward blends the cached value in via ``ctx.cache_in`` and
    publishes the fresh value through ``ctx.cache_out`` (the executor's
    train/eval step returns it; FFModel.fit scores it host-side with
    ``score_fn`` and feeds the recompile trigger).

    attrs: num_batches, score_fn (callable(cached, fresh) -> float).
    """

    def infer_output_shapes(self, input_shapes):
        return [input_shapes[0]]

    def forward(self, params, inputs, ctx: OpContext):
        fresh = inputs[0]
        if ctx.cache_out is not None:
            ctx.cache_out[self.name] = fresh
        if ctx.cache_in is not None and self.name in ctx.cache_in:
            use_cache = ctx.cache_in.get("__use_cache__")
            if use_cache is not None:
                import jax.numpy as jnp

                cached = ctx.cache_in[self.name]
                return [jnp.where(use_cache, cached.astype(fresh.dtype),
                                  fresh)]
        return [fresh]


# ------------------------------------------------- the dropless routed layer
# Beside the fixed-capacity path above (which drops a token when its
# expert's buffer is full), a routed layer that drops none, as four nodes:
#
#   router   x -> (weights (.., k) f32, chosen (.., k) int32): sigmoid or
#            softmax scores over ALL ``num_experts``, top-k of score + bias
#            (the bias selects and takes no gradient), the chosen scores
#            normalised and scaled
#   dispatch (x, chosen) -> rows sorted by expert id (a stable sort of the
#            (token, slot) pairs), the held experts' group sizes, and the
#            sort's permutation with its inverse
#   experts  (rows, group sizes) -> a gated MLP per held expert, as grouped
#            matrix products (``jax.lax.ragged_dot``)
#   combine  (expert rows, permutation, weights, chosen) -> (.., d)
#
# ``held=(first, count)``: the layer is told which experts of ``num_experts``
# this device holds (expert parallelism's share). The router ranks all of
# them and normalises over all k chosen; dispatch, experts and combine see
# only the pairs whose expert is held, and the layer's output is that
# partial sum — the other shares are other devices'. The row buffer between
# the nodes has tokens * k rows, the case of every pair landing here, so no
# routing can overflow it; the held pairs are sorted to its front and
# counted, and rows past them belong to no group, may hold anything, and
# are masked wherever they are read.
#
# What the buffer costs: work in proportion to the rows COUNTED, not to its
# size. Each of the three nodes works on a static prefix of ``_row_bound``
# rows (the rows expected here under uniform routing times
# ``ROW_BOUND_HEADROOM``, a tile multiple) when the counted pairs fit it, and
# on the whole buffer — the fallback, the same arithmetic over every row —
# when they do not: one ``lax.cond`` a node on a count the device holds, so
# a skewed routing is slower and never wrong. The buffer keeps its shape
# between the nodes; the bounded path works on its prefix and hands the rows
# after it on as zeros (``_pad_rows``: one fill of the buffer a node each
# way, 10 ms of the ``trinity-mini`` cell's 342 ms step; leaving them
# unwritten read 8 ms less and was refused, PERF.md section 6, PR 38), so
# whatever reads the buffer, and whichever branch the next node takes, finds
# numbers.
# Where there is nothing to bound (every expert held, or so few pairs that
# the bound is the buffer) a node is the whole-buffer code with no ``cond``.
# ``bounded`` among dispatch's counters says which path a step took.

#: rows of the bounded path over the rows expected here under uniform
#: routing. The sum over 16 held experts of 128 read 1.02 of its expectation
#: at seeded routers while a single expert read 2.1 (PERF.md section 6,
#: PRs 35 and 38): the bound is on the sum.
ROW_BOUND_HEADROOM = 1.5
#: the bound is a multiple of this many rows: whole tiles of every dtype, and
#: for a small buffer the row tile of the grouped products, whose time
#: follows the rows they are HANDED until those pass their own tile (a decode
#: step's three products: 3.67 ms on its 512-row buffer, 2.45 / 2.02 / 1.83
#: on 256 / 128 / 64 rows, the last the held matrices' bytes at the chip's
#: bandwidth; PERF.md section 6, PR 38). 128 leaves a decode step of 32
#: expected pairs four times its expectation before it falls back.
ROW_TILE = 128


def _held(attrs):
    first, count = attrs["held"]
    return int(first), int(count)


def _held_pairs(chosen_flat, first: int, count: int):
    """(local expert id or ``count`` for a pair not held here, held mask)."""
    import jax.numpy as jnp

    local = chosen_flat - first
    here = (local >= 0) & (local < count)
    return jnp.where(here, local, count), here


def _gather_rows(x, idx, back, fan: int):
    """``x[idx // fan]`` whose backward is a gather too: ``idx`` is a
    permutation of the (row, slot) pairs and ``back`` its inverse, so
    d x = sum over a row's ``fan`` slots of dy[back]. (XLA's own transpose
    of a gather is a scatter-add, which the TPU serialises.)"""
    import functools

    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def f(x, idx, back, fan):
        return x[idx // fan] if fan > 1 else x[idx]

    def fwd(x, idx, back, fan):
        return f(x, idx, back, fan), back

    def bwd(fan, back, dy):
        dx = dy[back]
        if fan > 1:
            dx = dx.reshape(-1, fan, dx.shape[-1]).sum(axis=1)
        return dx.astype(dy.dtype), None, None

    f.defvjp(fwd, bwd)
    return f(x, idx, back, fan)


def _mask_cotangent(rows, n_valid):
    """Identity whose backward zeroes the cotangent of rows >= n_valid."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def f(rows, n_valid):
        return rows

    def bwd(n_valid, dy):
        valid = jnp.arange(dy.shape[0])[:, None] < n_valid
        return jnp.where(valid, dy, 0), None

    f.defvjp(lambda rows, n_valid: (rows, n_valid), bwd)
    return f(rows, n_valid)


def _row_bound(pairs: int, attrs):
    """The bounded path's rows for a buffer of ``pairs`` rows, from what is
    static; None where the node is the whole-buffer code alone."""
    share = _held(attrs)[1] / float(attrs["num_experts"])
    bound = -(-int(np.ceil(pairs * share * ROW_BOUND_HEADROOM))
              // ROW_TILE) * ROW_TILE
    return bound if bound < pairs else None


def _by_rows(n_here, bound, bounded, whole, floats, ints):
    """``bounded(*floats, *ints)`` where the counted rows fit ``bound``,
    ``whole(*floats, *ints)`` where not (or where there is no bound: then
    no ``cond``), on the device. Differentiable in ``floats``: the backward
    is a ``cond`` of its own over the two branches' transposes and keeps
    the INPUTS alone between the two, so that no residual of the whole-
    buffer branch is allocated, filled or copied on the bounded path
    (``cond``'s own partial evaluation hands every branch's residuals out of
    both branches, zeros from the one not taken)."""
    import jax

    if bound is None:
        return whole(*floats, *ints)

    @jax.custom_vjp
    def f(floats, ints, n_here):
        return jax.lax.cond(n_here <= bound,
                            lambda fl, it: bounded(*fl, *it),
                            lambda fl, it: whole(*fl, *it), floats, ints)

    def bwd(res, dy):
        floats, ints, n_here = res

        def transposed(fn):
            return lambda fl, it, dy: jax.vjp(
                lambda *fl: fn(*fl, *it), *fl)[1](dy)

        return jax.lax.cond(n_here <= bound, transposed(bounded),
                            transposed(whole), floats, ints, dy), None, None

    f.defvjp(lambda *args: (f(*args), args), bwd)
    return f(tuple(floats), tuple(ints), n_here)


@functools.lru_cache(maxsize=None)
def _shared_program(rows_fn, *static):
    """``rows_fn`` with ``static`` bound, under one ``jax.jit`` a
    (function, static) so that the layers of a model — and the three times
    a differentiated ``cond`` is traced — share ONE traced and lowered
    program: the ``trinity-mini`` cell's train step lowers to the parent's
    1.01 MB of StableHLO, 1.16 MB with twelve nodes traced apiece, and that
    cell's set-up traces, lowers and hashes it twice (the step's one build
    and the benchmark's reading of its text): 0.5 s of a warm ``setup_s``
    when it did so three times (PERF.md section 6, PR 38). Where there is
    no bound there is no ``cond`` and the function is called as it is."""
    import jax

    @functools.wraps(rows_fn)
    def program(*arrays):
        return rows_fn(*static, *arrays)

    return program if static[0] is None else jax.jit(program)


def _dispatch_rows(bound, k, x, order, here, n_here):
    pairs = order.shape[1]

    def whole(x, order, here, n_here):
        rows = _gather_rows(x, order[0], order[1], k)
        # rows past the held pairs belong to no group: their cotangent is
        # whatever the grouped products left there, never a gradient
        return _mask_cotangent(rows, n_here)

    def bounded(x, order, here, n_here):
        pair, slot = _prefix_route(order, here, bound, k)
        return _pad_rows(_rows_of_tokens(x, pair // k, slot), pairs)

    return _by_rows(n_here, bound, bounded, whole, (x,),
                    (order, here, n_here))


def _expert_rows(bound, product, limit, rows, gate, up, down, group_sizes):
    """The gated MLP of every held expert over its rows. ``product``:
    ``jax.lax.ragged_dot`` as the caller finds it (a check that plants a
    fault in it must not be handed a program traced before); ``limit``:
    the clamp of ``linear.swiglu`` (None: none). As
    ``_by_rows``, with one difference: the bounded path's two first
    products leave its forward as residuals of the BOUND's shape, so its
    backward is the six transposed products and computes none again (the
    fallback's backward starts from the inputs, as ``_by_rows`` does)."""
    import jax
    import jax.numpy as jnp

    dtype, pairs = rows.dtype, rows.shape[0]

    def grouped(lhs, rhs, group_sizes):
        return product(lhs, rhs, group_sizes,
                       preferred_element_type=jnp.float32)

    def gated(g, u):
        return swiglu(g, u, limit).astype(dtype)

    def whole(rows, gate, up, down, group_sizes):
        a = gated(grouped(rows, gate, group_sizes),
                  grouped(rows, up, group_sizes))
        return grouped(a, down, group_sizes).astype(dtype)

    if bound is None:
        return whole(rows, gate, up, down, group_sizes)

    def fits(group_sizes):
        return jnp.sum(group_sizes) <= bound

    def bounded(rows, gate, up, down, group_sizes):
        g = grouped(rows[:bound], gate, group_sizes)
        u = grouped(rows[:bound], up, group_sizes)
        out = grouped(gated(g, u), down, group_sizes).astype(dtype)
        return _pad_rows(out, pairs), (g, u)

    def whole_no_residuals(rows, gate, up, down, group_sizes):
        nothing = jnp.zeros((bound, gate.shape[-1]), jnp.float32)
        return whole(rows, gate, up, down, group_sizes), (nothing, nothing)

    @jax.custom_vjp
    def f(rows, gate, up, down, group_sizes):
        return jax.lax.cond(
            fits(group_sizes), lambda *args: bounded(*args)[0], whole,
            rows, gate, up, down, group_sizes)

    def fwd(*args):
        out, residuals = jax.lax.cond(fits(args[-1]), bounded,
                                      whole_no_residuals, *args)
        return out, (args, residuals)

    def bounded_bwd(rows, gate, up, down, group_sizes, g, u, dy):
        def transposed(fn, *operands):       # the products' own values are
            return jax.vjp(fn, *operands)[1]  # not used: XLA drops them

        a, of_gated = jax.vjp(gated, g, u)
        da, d_down = transposed(
            lambda a, w: grouped(a, w, group_sizes).astype(dtype),
            a, down)(dy[:bound])
        dg, du = of_gated(da)
        x = rows[:bound]
        dx_gate, d_gate = transposed(
            lambda x, w: grouped(x, w, group_sizes), x, gate)(dg)
        dx_up, d_up = transposed(
            lambda x, w: grouped(x, w, group_sizes), x, up)(du)
        return _pad_rows(dx_gate + dx_up, pairs), d_gate, d_up, d_down

    def whole_bwd(rows, gate, up, down, group_sizes, g, u, dy):
        return jax.vjp(lambda *fl: whole(*fl, group_sizes),
                       rows, gate, up, down)[1](dy)

    def bwd(res, dy):
        args, (g, u) = res
        return jax.lax.cond(fits(args[-1]), bounded_bwd, whole_bwd,
                            *args, g, u, dy) + (None,)

    f.defvjp(fwd, bwd)
    return f(rows, gate, up, down, group_sizes)


def _combine_rows(bound, k, rows, weights, order, here):
    import jax.numpy as jnp

    def whole(rows, weights, order, here):
        slots = _gather_rows(rows, order[1], order[0], 1)
        slots = slots.reshape(-1, k, rows.shape[-1])
        slots = jnp.where(here[..., None], slots, 0).astype(jnp.float32)
        return jnp.einsum("tk,tkd->td", jnp.where(here, weights, 0.0), slots)

    def bounded(rows, weights, order, here):
        return _tokens_of_rows(
            rows[:bound], jnp.where(here, weights, 0.0),
            *_prefix_route(order, here, bound, k))

    return _by_rows(jnp.sum(here.astype(jnp.int32)), bound, bounded, whole,
                    (rows, weights), (order, here))


def _prefix_route(order, here, bound: int, k: int):
    """The bounded path's two index arrays: ``pair (bound,)``, the (token,
    slot) pair of each prefix row (its token is ``pair // k``), and ``slot
    (tokens, k)``, the prefix row of each pair — ``bound``, a row of zeros
    appended to the prefix, for a pair not held here (a held pair's row is
    under the count, so under the bound)."""
    import jax.numpy as jnp

    return (order[0, :bound],
            jnp.where(here.reshape(-1), order[1], bound).reshape(-1, k))


def _with_zero_row(rows):
    import jax.numpy as jnp

    return jnp.concatenate([rows, jnp.zeros((1, rows.shape[-1]), rows.dtype)])


def _sum_slots(rows, slot, weights=None):
    """``y[t] = sum_j weights[t, j] * rows0[slot[t, j]]`` (rows0: ``rows``
    and one row of zeros) in float32: one gather of (tokens, k, d) in the
    rows' dtype and a sum over the k slots that accumulates in float32."""
    import jax.numpy as jnp

    slots = _with_zero_row(rows)[slot]
    if weights is None:
        return jnp.sum(slots, axis=1, dtype=jnp.float32)
    return jnp.einsum("tk,tkd->td", weights, slots,
                      preferred_element_type=jnp.float32)


def _rows_of_tokens(x, tok, slot):
    """``x[tok]``, the bounded dispatch; its backward is the gather back to
    tokens (``_sum_slots`` of the cotangent rows: the pairs not held read
    the zero row, so rows past the count hand no gradient on)."""
    import jax

    @jax.custom_vjp
    def f(x, tok, slot):
        return x[tok]

    f.defvjp(lambda x, tok, slot: (x[tok], slot),
             lambda slot, dy: (_sum_slots(dy, slot).astype(dy.dtype), None,
                               None))
    return f(x, tok, slot)


def _tokens_of_rows(rows, weights, pair, slot):
    """The bounded combine, ``_sum_slots(rows, slot, weights)`` in float32
    (``weights`` zero at the pairs not held); its backward gathers too:
    d rows[r] = weights[pair[r]] * dy[pair[r] // k], d weights[t, j] =
    dy[t] . rows0[slot[t, j]]."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def f(rows, weights, pair, slot):
        return _sum_slots(rows, slot, weights)

    def bwd(res, dy):
        rows, weights, pair, slot = res
        d_rows = weights.reshape(-1)[pair][:, None] * dy[pair // slot.shape[1]]
        d_weights = jnp.einsum("td,tkd->tk", dy, _with_zero_row(rows)[slot],
                               preferred_element_type=jnp.float32)
        return d_rows.astype(rows.dtype), d_weights, None, None

    f.defvjp(lambda *args: (f(*args), args), bwd)
    return f(rows, weights, pair, slot)


def _pad_rows(prefix, pairs: int):
    """The prefix as the first rows of a buffer of ``pairs``, zeros after
    (its transpose reads the cotangent's prefix)."""
    import jax.numpy as jnp

    return jnp.pad(prefix, ((0, pairs - prefix.shape[0]), (0, 0)))


@register_op(OperatorType.OP_MOE_ROUTER)
class MoERouterOp(Op):
    """attrs: num_experts, k, route_norm, route_scale, kernel_initializer,
    selection_bias (False: no ``expert_bias``, the scores alone rank).
    Scores are ``sigmoid`` of the logits in float32. Weights: ``kernel`` (d, num_experts, no
    bias) and ``expert_bias`` (num_experts,), a buffer added to the scores
    for the SELECTION only: the weights are the plain scores of the chosen,
    and no gradient reaches the buffer.

    input (.., d) -> outputs (weights (.., k) float32, chosen (.., k) int32).
    """

    def infer_output_shapes(self, input_shapes):
        out = tuple(input_shapes[0][:-1]) + (self.attrs["k"],)
        return [out, out]

    def output_dtypes(self, input_dtypes, num_outputs):
        from ..ffconst import DataType

        return [DataType.DT_FLOAT, DataType.DT_INT32]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import (DefaultWeightInitializer,
                                              UniformInitializer)

        d, n = input_shapes[0][-1], self.attrs["num_experts"]
        specs = {"kernel": ((d, n), self.data_type,
                            self.attrs.get("kernel_initializer")
                            or DefaultWeightInitializer())}
        if self.attrs.get("selection_bias", True):
            specs["expert_bias"] = ((n,), self.data_type,
                                    UniformInitializer(min_val=-0.01,
                                                       max_val=0.01))
        return specs

    def forward(self, params, inputs, ctx: OpContext):
        import jax
        import jax.numpy as jnp

        (x,) = inputs
        logits = jnp.dot(x, params["kernel"],
                         preferred_element_type=jnp.float32)
        scores = jax.nn.sigmoid(logits)
        ranked = scores
        if "expert_bias" in params:
            ranked = scores + jax.lax.stop_gradient(
                params["expert_bias"].astype(jnp.float32))
        _, chosen = jax.lax.top_k(ranked, self.attrs["k"])
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.attrs.get("route_norm", True):
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        weights = weights * float(self.attrs.get("route_scale", 1.0))
        return [weights, chosen.astype(jnp.int32)]

    def flops(self, input_shapes, output_shapes):
        return 2 * int(np.prod(input_shapes[0])) * self.attrs["num_experts"]


@register_op(OperatorType.OP_MOE_DISPATCH)
class MoEDispatchOp(Op):
    """attrs: num_experts, held. inputs (x (.., d), chosen (.., k)) ->
    (rows (tokens * k, d) sorted by expert id with the pairs not held here
    last, group_sizes (count,) int32, order (2, tokens * k) int32: the
    sort's permutation and its inverse).

    Publishes the layer's routing counters (``ctx.stats_out``): the tokens
    each held expert received, the pairs held here, and the pairs that were
    held and reached no group (dropped: 0 by construction, counted from the
    two sides)."""

    def infer_output_shapes(self, input_shapes):
        x, chosen = input_shapes
        pairs = int(np.prod(chosen))
        return [(pairs, x[-1]), (_held(self.attrs)[1],), (2, pairs)]

    def output_dtypes(self, input_dtypes, num_outputs):
        from ..ffconst import DataType

        return [input_dtypes[0], DataType.DT_INT32, DataType.DT_INT32]

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        x, chosen = inputs
        first, count = _held(self.attrs)
        k = chosen.shape[-1]
        key, here = _held_pairs(chosen.reshape(-1), first, count)
        perm = jnp.argsort(key, stable=True).astype(jnp.int32)
        # the inverse permutation by a second sort (a scatter of 65,536
        # indices is serial on the TPU: 0.3 ms)
        inv = jnp.argsort(perm).astype(jnp.int32)
        # one compare-and-sum a held expert (a bincount is a scatter-add,
        # which the TPU serialises: 0.6 ms for 65,536 pairs)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=key.dtype),
            axis=0, dtype=jnp.int32)
        n_here = jnp.sum(here.astype(jnp.int32))
        order = jnp.stack([perm, inv])
        bound = _row_bound(perm.shape[0], self.attrs)
        rows = _shared_program(_dispatch_rows, bound, k)(
            x.reshape(-1, x.shape[-1]), order, here, n_here)
        if ctx.stats_out is not None:
            ctx.stats_out[self.name] = {
                "tokens_per_expert": group_sizes,
                "pairs_here": n_here,
                "dropped": n_here - jnp.sum(group_sizes),
                "bounded": jnp.int32(0) if bound is None
                else (n_here <= bound).astype(jnp.int32)}
        return [rows, group_sizes, order]

    def parallelizable_dims(self, input_shapes):
        return {"batch": False, "expert": True}


@register_op(OperatorType.OP_MOE_ROUTED_EXPERTS)
class MoERoutedExpertsOp(Op):
    """attrs: held, intermediate, kernel_initializer, limit (off by
    default: the clamp of ``linear.swiglu``). inputs (rows
    (pairs, d) sorted by expert, group_sizes (count,)) -> (pairs, d): per
    held expert ``W_down(silu(W_gate x) * W_up x)`` over its rows, three
    grouped products. Weights ``gate``/``up`` (count, d, intermediate),
    ``down`` (count, intermediate, d): the held experts' alone, each
    initialised as a matrix of its own (the default initialiser would read
    the expert dim as a receptive field and scale every expert down by
    sqrt(count): an expert's output by count^1.5). Rows of no group come out
    undefined (zero off-TPU) and are the consumer's to mask."""

    def infer_output_shapes(self, input_shapes):
        return [tuple(input_shapes[0])]

    def weight_specs(self, input_shapes):
        from ..execution.initializers import GlorotUniformInitializer

        d, i = input_shapes[0][-1], self.attrs["intermediate"]
        n = _held(self.attrs)[1]
        init = self.attrs.get("kernel_initializer") \
            or GlorotUniformInitializer(stacked=True)
        return {"gate": ((n, d, i), self.data_type, init),
                "up": ((n, d, i), self.data_type, init),
                "down": ((n, i, d), self.data_type, init)}

    def forward(self, params, inputs, ctx: OpContext):
        import jax
        import jax.numpy as jnp

        rows, group_sizes = inputs
        bound = _row_bound(rows.shape[0], self.attrs)
        return [_shared_program(_expert_rows, bound, jax.lax.ragged_dot,
                                self.attrs.get("limit"))(
            rows, params["gate"], params["up"], params["down"],
            group_sizes)]

    def flops(self, input_shapes, output_shapes):
        """Of the rows expected here under uniform routing (pairs * held /
        num_experts), not of the buffer: the grouped products skip rows of
        no group."""
        pairs, d = input_shapes[0]
        share = _held(self.attrs)[1] / float(self.attrs["num_experts"])
        return int(6 * pairs * share * d * self.attrs["intermediate"])

    def memory_bytes(self, input_shapes, output_shapes):
        from ..ffconst import size_of_datatype

        el = size_of_datatype(self.data_type)
        pairs, d = input_shapes[0]
        share = _held(self.attrs)[1] / float(self.attrs["num_experts"])
        weights = 3 * _held(self.attrs)[1] * d * self.attrs["intermediate"]
        return int(el * (weights + 2 * pairs * share * d))

    def parallelizable_dims(self, input_shapes):
        # expert parallelism: the expert dim of the three weights is the
        # shardable one, as ExpertsOp says
        return {"batch": False, "expert": True}


@register_op(OperatorType.OP_MOE_COMBINE)
class MoECombineOp(Op):
    """attrs: num_experts, held. inputs (expert rows (pairs, d), order
    (2, pairs), weights (.., k), chosen (.., k)) -> (.., d): each token's
    sum over its pairs held here of weight * expert row (the rows gathered
    back by the sort's inverse; pairs not held here contribute nothing)."""

    def infer_output_shapes(self, input_shapes):
        rows, _order, weights, _chosen = input_shapes
        return [tuple(weights[:-1]) + (rows[-1],)]

    def forward(self, params, inputs, ctx: OpContext):
        import jax.numpy as jnp

        rows, order, weights, chosen = inputs
        first, count = _held(self.attrs)
        k = chosen.shape[-1]
        _, here = _held_pairs(chosen.reshape(-1, k), first, count)
        bound = _row_bound(rows.shape[0], self.attrs)
        y = _shared_program(_combine_rows, bound, k)(
            rows, weights.reshape(-1, k), order, here)
        return [y.reshape(chosen.shape[:-1] + (rows.shape[-1],)).astype(
            rows.dtype)]

    def flops(self, input_shapes, output_shapes):
        return 2 * int(np.prod(input_shapes[0]))

    def parallelizable_dims(self, input_shapes):
        return {"batch": False, "expert": True}
