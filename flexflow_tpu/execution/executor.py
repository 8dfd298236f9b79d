"""Executor: lowers a PCG + Strategy into jitted JAX train/eval steps.

This replaces the reference's entire task-launch machinery: FFModel::forward/
backward/update index launches (model.cc:2415-2469), the FFMapper
(src/mapper/mapper.cc), Legion trace capture (begin/end_trace), and the NCCL
bootstrap (model.cc:3129-3166). One ``jax.jit`` over the whole training step
with NamedShardings plays all those roles: tracing ≙ Legion trace replay,
SPMD partitioning ≙ mapper + parallel-op partitions, sharded autodiff ≙ NCCL
allreduce in the optimizer (SURVEY §7 architecture mapping).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ..ffconst import DataType, OperatorType, dtype_to_jnp
from ..ops.base import OpContext
from ..parallel.pcg import PCG, PCGNode
from ..parallel.strategy import Strategy
from .losses import loss_value
from .metrics import Metrics

#: The jitted programs a device trace is read by: a launch shows on the
#: chip's ``XLA Modules`` line as ``jit_<name>``, and the benchmark's
#: reduction finds the train step and the serving programs under these
#: names. Renaming one renames the source of a metric
#: (tests/test_program_spans.py lowers each and reads the module's name).
PROGRAM_NAMES = ("step", "decode", "prefill", "prefill_chunk", "write")


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` under the program name ``name`` (one of
    ``PROGRAM_NAMES``), whatever the inner function is called."""
    import jax

    if name not in PROGRAM_NAMES:
        raise KeyError(name)
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


class Executor:
    def __init__(self, pcg: PCG, mesh, strategy: Strategy, loss_type,
                 metrics: Metrics, optimizer, config, final_guid: int,
                 label_dtype: DataType, repl_labels: bool = False,
                 final_out_idx: int = 0):
        self.final_out_idx = final_out_idx
        self.pcg = pcg
        self.mesh = mesh
        self.strategy = strategy
        self.loss_type = loss_type
        self.metrics = metrics
        self.optimizer = optimizer
        self.config = config
        self.final_guid = final_guid
        self.label_dtype = label_dtype
        self.repl_labels = repl_labels

        self._train_step = None
        self._guarded_train_step = None
        self._eval_step = None
        self._forward_jit = None
        self._probe_step = None
        # serving engine jits (ISSUE 6): {("prefill", bucket_len, max_len) |
        # ("decode", max_len): jitted fn} — one compile per prefill bucket
        # plus ONE decode compile, the engine's recompile-free contract
        self._serving_jits: Dict[Tuple, Any] = {}
        # the RematPlan make_train_step resolved and applied (None until
        # built, and None when remat is off/ineligible) — telemetry reads it
        self.remat_plan = None
        # cache-op state (reference: src/ops/cache.cc — cached intermediate
        # tensors across iterations, host-scored, paired with recompile)
        self.cache_nodes = [n for n in pcg.compute_nodes()
                            if n.op.op_type == OperatorType.OP_CACHE]

        # apply strategy op-attr overrides (e.g. ring-attention seq axis)
        for guid, ns in strategy.node_strategies.items():
            if ns.extra and guid in pcg.nodes:
                pcg.nodes[guid].op.attrs.update(ns.extra)

    # ------------------------------------------------------------------ sharding
    def _named_sharding(self, spec_entries):
        from jax.sharding import NamedSharding, PartitionSpec

        if self.mesh is None:
            return None
        if spec_entries is None:
            return NamedSharding(self.mesh, PartitionSpec())
        entries = list(spec_entries)
        while entries and entries[-1] is None:
            entries.pop()
        return NamedSharding(self.mesh, PartitionSpec(*entries))

    def batch_sharding(self, ndim: int):
        from jax.sharding import NamedSharding, PartitionSpec

        if self.mesh is None:
            return None
        axis = self.strategy.data_axis
        if axis not in self.mesh.shape:
            return NamedSharding(self.mesh, PartitionSpec())
        return NamedSharding(self.mesh,
                             PartitionSpec(*([axis] + [None] * (ndim - 1))))

    def param_shardings(self):
        """Pytree of NamedShardings matching init_params output."""
        out: Dict[str, Dict[str, Any]] = {}
        for node in self.pcg.compute_nodes():
            in_shapes = self._node_input_shapes(node)
            specs = node.op.weight_specs(in_shapes)
            if not specs:
                continue
            ns = self.strategy.node_strategies.get(node.guid)
            d = {}
            for wname, (shape, dtype, init) in specs.items():
                entries = (ns.weight_specs.get(wname) if ns else None)
                d[wname] = self._named_sharding(entries)
            out[node.name] = d
        return out

    # ------------------------------------------------------------------- params
    def _node_input_shapes(self, node: PCGNode) -> List[Tuple[int, ...]]:
        return [self.pcg.nodes[g].out_shapes[i] for g, i in node.inputs]

    def weight_entries(self):
        """[(node, wname, shape, dtype, init)] in topo order."""
        entries = []
        for node in self.pcg.compute_nodes():
            in_shapes = self._node_input_shapes(node)
            for wname, (shape, dtype, init) in node.op.weight_specs(
                    in_shapes).items():
                entries.append((node, wname, shape, dtype, init))
        return entries

    def init_params(self, seed: int = 0):
        """Sharded weight init: one jitted function with out_shardings, so big
        tables initialize directly on their owner shards (the reference runs
        per-shard Legion init tasks, initializer.cc)."""
        import jax

        entries = self.weight_entries()
        rest = getattr(self.config, "param_dtype", None)
        if rest is not None and rest != DataType.DT_NONE:
            return self._init_params_at_rest(entries, seed,
                                             dtype_to_jnp(rest))

        def init_fn(key):
            params: Dict[str, Dict[str, Any]] = {}
            for i, (node, wname, shape, dtype, init) in enumerate(entries):
                sub = jax.random.fold_in(key, i)
                params.setdefault(node.name, {})[wname] = init(
                    sub, shape, dtype_to_jnp(dtype))
            return params

        key = jax.random.PRNGKey(seed)
        if self.mesh is not None:
            shardings = self.param_shardings()
            return jax.jit(init_fn, out_shardings=shardings)(key)
        return jax.jit(init_fn)(key)

    def _init_params_at_rest(self, entries, seed: int, rest):
        """The tree with every floating-point leaf resting in ``rest``
        (``--param-dtype``): one small program a leaf — drawn in the
        weight's own dtype, as the one-program init draws it, and cast
        before the next leaf is made — so the peak is the tree at rest
        plus ONE leaf in float32, never a float32 tree."""
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(seed)
        shardings = self.param_shardings() if self.mesh is not None else None
        programs: Dict[Tuple, Any] = {}
        params: Dict[str, Dict[str, Any]] = {}
        for i, (node, wname, shape, dtype, init) in enumerate(entries):
            drawn = dtype_to_jnp(dtype)
            to = rest if jnp.issubdtype(drawn, jnp.floating) else drawn
            out = shardings[node.name][wname] if shardings else None
            # one compile for the leaves that share shape and initialiser
            # (the layers' twins), whatever their place in the tree
            pkey = (type(init), tuple(sorted(vars(init).items())),
                    tuple(shape), drawn, out)
            if pkey not in programs:
                programs[pkey] = jax.jit(
                    lambda k, init=init, shape=shape, drawn=drawn, to=to:
                    init(k, shape, drawn).astype(to), out_shardings=out)
            leaf = programs[pkey](jax.random.fold_in(key, i))
            params.setdefault(node.name, {})[wname] = leaf
            leaf.block_until_ready()  # the f32 draw is gone before the next
        return params

    # --------------------------------------------------------- mixed precision
    def _compute_jnp_dtype(self):
        """jnp dtype for forward compute, or None for full precision.

        Master weights, the loss, and normalization statistics stay float32;
        only the forward/backward compute (matmuls on the MXU) runs in the
        reduced dtype. The cast happens inside the differentiated function, so
        gradients flow back to the float32 master params.
        """
        cd = getattr(self.config, "compute_dtype", None)
        if cd is None or cd == DataType.DT_NONE:
            return None
        return dtype_to_jnp(cd)

    @staticmethod
    def _cast_floats(tree, dtype):
        import jax
        import jax.numpy as jnp

        def cast(x):
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(dtype)
            return x

        return jax.tree.map(cast, tree)

    def _cast_for_compute(self, params, xs):
        cdtype = self._compute_jnp_dtype()
        if cdtype is None:
            return params, xs
        return (self._cast_floats(params, cdtype),
                self._cast_floats(xs, cdtype))

    @staticmethod
    def _logits_f32(logits):
        import jax.numpy as jnp

        if jnp.issubdtype(logits.dtype, jnp.floating):
            return logits.astype(jnp.float32)
        return logits

    # ------------------------------------------------------------------ forward
    def _exec_node(self, node: PCGNode, node_params, inputs,
                   ctx: OpContext) -> List[Any]:
        """Run ONE node: per-node OpContext (guid-folded rng), per-op
        named scope (op names become HLO metadata, so XLA/xprof timelines
        attribute fused kernels back to PCG nodes — the reference gets
        this from per-op Legion task names; here it is free at trace
        time), and the strategy's output sharding constraint. The single
        recipe both the plain forward and the remat blocks execute."""
        import jax
        import jax.lax as lax

        node_ctx = OpContext(
            training=ctx.training,
            rng=(jax.random.fold_in(ctx.rng, node.guid)
                 if ctx.rng is not None else None),
            seq_length=ctx.seq_length, mesh=ctx.mesh,
            profiling=ctx.profiling, aux_losses=ctx.aux_losses,
            cache_in=ctx.cache_in, cache_out=ctx.cache_out,
            serving=ctx.serving, stats_out=ctx.stats_out)
        with jax.named_scope(node.name):
            outs = node.op.forward(node_params, inputs, node_ctx)
        # apply the strategy's output sharding constraint (parallel ops and
        # any node the search pinned)
        ns = self.strategy.node_strategies.get(node.guid)
        if ns is not None and ns.output_spec is not None \
                and self.mesh is not None:
            sh = self._named_sharding(ns.output_spec)
            outs = [lax.with_sharding_constraint(outs[0], sh)] + outs[1:]
        return outs

    def forward_outputs(self, params, bound_inputs: Dict[int, Any],
                        ctx: OpContext,
                        overrides: Optional[Dict[int, List[Any]]] = None
                        ) -> Dict[int, List[Any]]:
        """Run the graph; returns {node_guid: [outputs]}.

        ``overrides`` substitutes the outputs of specific compute nodes
        without executing them — the serving engine's hook for replacing
        baked position-id constants with the live per-slot positions
        (serving/kvcache.is_position_constant). None on every training
        path."""
        values: Dict[int, List[Any]] = {}
        for node in self.pcg.topo_order():
            op = node.op
            if op.op_type in (OperatorType.OP_INPUT,
                              OperatorType.OP_WEIGHT):
                values[node.guid] = [bound_inputs[node.guid]]
                continue
            if overrides is not None and node.guid in overrides:
                values[node.guid] = overrides[node.guid]
                continue
            inputs = [values[g][i] for g, i in node.inputs]
            values[node.guid] = self._exec_node(
                node, params.get(node.name, {}), inputs, ctx)
        return values

    def _bind_inputs(self, xs: List[Any]) -> Dict[int, Any]:
        input_nodes = self.pcg.input_nodes()
        assert len(xs) == len(input_nodes), \
            f"model has {len(input_nodes)} inputs, got {len(xs)}"
        return {n.guid: x for n, x in zip(input_nodes, xs)}

    # ------------------------------------------------- rematerialized forward
    def _build_remat_program(self, plan):
        """Compile the PCG into checkpointed remat blocks for ``plan``
        (execution/remat.py — the SAME segmentation the Simulator's memory
        model prices). Each block becomes a pure function
        ``(block_params, boundary_values, rng) -> (exposed_outputs, aux)``
        wrapped in ``jax.checkpoint`` with the plan's save policy, so the
        backward pass recomputes the block's interior instead of saving it.
        Per-op ``jax.named_scope`` is preserved inside the blocks (the
        recompute shows up attributed in xprof timelines)."""
        import jax

        from ..ops.base import OpContext
        from .remat import checkpoint_policy, remat_segments

        policy = checkpoint_policy(plan.level)
        segments = remat_segments(self.pcg, plan.segment_size)
        seg_of = {g: k for k, seg in enumerate(segments) for g in seg}
        # every (guid, out_idx) consumed across a block boundary (or the
        # loss anchor) must be exposed as a block output — these are the
        # only activations `full` remat keeps
        needed = {(self.final_guid, self.final_out_idx)}
        for node in self.pcg.compute_nodes():
            for pg, i in node.inputs:
                if pg in seg_of and seg_of[pg] != seg_of[node.guid]:
                    needed.add((pg, i))

        mesh = self.mesh
        profiling = bool(getattr(self.config, "profiling", False))
        program = []
        for k, seg in enumerate(segments):
            seg_set = set(seg)
            ext_refs: List[Tuple[int, int]] = []
            seen = set()
            for g in seg:
                for pg, i in self.pcg.nodes[g].inputs:
                    if pg in seg_set or (pg, i) in seen:
                        continue
                    seen.add((pg, i))
                    ext_refs.append((pg, i))
            out_refs = [(g, i) for g in seg
                        for i in range(len(self.pcg.nodes[g].out_shapes))
                        if (g, i) in needed]
            names = [self.pcg.nodes[g].name for g in seg]

            # cache-stateful nodes of this block (reference: cache.cc):
            # their fresh values leave the block as EXPLICIT outputs —
            # the same no-host-side-mutation rule as aux losses. This is
            # the ISSUE 6 inversion of the old "CacheOp graphs opt out of
            # remat" rule: cache state threads through jax.checkpoint like
            # any other block boundary value.
            cache_names = [self.pcg.nodes[g].name for g in seg
                           if self.pcg.nodes[g].op.op_type ==
                           OperatorType.OP_CACHE]

            def make_fn(seg=seg, ext_refs=ext_refs, out_refs=out_refs,
                        cache_names=cache_names):
                def fn(block_params, ext_vals, rng, cache_in):
                    import jax.numpy as jnp

                    values = dict(zip(ext_refs, ext_vals))
                    aux: List[Any] = []
                    cache_out: Dict[str, Any] = {}
                    # counters ops hand out (OpContext.stats_out) leave the
                    # block the same way
                    stats: Dict[str, Any] = {}
                    # block-local ctx: _exec_node folds the rng per node,
                    # exactly as the plain forward does (recompute replays
                    # identical dropout masks)
                    block_ctx = OpContext(training=True, rng=rng,
                                          mesh=mesh, profiling=profiling,
                                          aux_losses=aux,
                                          cache_in=cache_in,
                                          cache_out=cache_out,
                                          stats_out=stats)
                    for g in seg:
                        node = self.pcg.nodes[g]
                        inputs = [values[(pg, i)] for pg, i in node.inputs]
                        outs = self._exec_node(
                            node, block_params.get(node.name, {}), inputs,
                            block_ctx)
                        for i, v in enumerate(outs):
                            values[(g, i)] = v
                    # aux losses leave the block as an explicit output —
                    # appending traced interiors to a host-side list from
                    # inside jax.checkpoint would leak residual tracers
                    aux_sum = sum(aux) if aux else jnp.zeros((), jnp.float32)
                    return (tuple(values[r] for r in out_refs), aux_sum,
                            tuple(cache_out[n] for n in cache_names), stats)
                return fn

            fn = make_fn()
            if policy is not None:
                fn = jax.checkpoint(fn, policy=policy)
            program.append((fn, ext_refs, out_refs, names, k, cache_names))
        return program

    def _forward_remat(self, params, bound_inputs: Dict[int, Any],
                       ctx: OpContext, program):
        """Run the checkpointed block program; returns the loss-anchor
        logits. Boundary values flow block to block; everything interior is
        recomputed in backward per the plan's policy."""
        import jax

        values = {(g, 0): v for g, v in bound_inputs.items()}
        for fn, ext_refs, out_refs, names, k, cache_names in program:
            block_params = {n: params[n] for n in names if n in params}
            ext_vals = tuple(values[r] for r in ext_refs)
            with jax.named_scope(f"remat_block_{k}"):
                outs, aux, cache_vals, stats = fn(block_params, ext_vals,
                                                  ctx.rng, ctx.cache_in)
            if ctx.stats_out is not None:
                ctx.stats_out.update(stats)
            if ctx.aux_losses is not None:
                ctx.aux_losses.append(aux)
            if ctx.cache_out is not None:
                ctx.cache_out.update(zip(cache_names, cache_vals))
            values.update(zip(out_refs, outs))
        return values[(self.final_guid, self.final_out_idx)]

    # --------------------------------------------- collective-compute overlap
    def _blockwise_value_and_grad(self, program, params, xs, labels, rng,
                                  cache):
        """Forward + loss + grads over the remat block program with the
        gradient synchronization SPLIT per block (``--collective-overlap
        on``, ISSUE 10): each block's backward runs through its own
        ``jax.vjp``, and as it completes its weight grads are (a) pinned to
        their final shardings via ``with_sharding_constraint`` — the SPMD
        partitioner materializes that block's grad all-reduce at this
        program point instead of deferring every psum to the step tail —
        and (b) coupled to the outgoing boundary cotangents through
        ``lax.optimization_barrier``, so upstream blocks' backward compute
        cannot be scheduled before the block's reduction is issuable: the
        collectives hide behind the remaining backward instead of
        serializing after it.

        Numerics are IDENTICAL to the synchronous ``value_and_grad`` path:
        the same block functions run in the same order, cotangents
        accumulate in the same reverse-block order, the sharding
        constraint and the barrier are value-identities, and each psum
        happens exactly once on the same mesh — loss, grads, and the
        updated params are bitwise-equal (tests/test_pipeline_schedules).
        Returns ``((loss, (logits, cache_out, stats)), grads)`` with
        ``grads`` matching the ``params`` pytree (blocks partition the
        layers)."""
        import jax
        import jax.lax as lax
        import jax.numpy as jnp

        cdtype = self._compute_jnp_dtype()
        if cdtype is not None:
            xs = self._cast_floats(xs, cdtype)
        bound = self._bind_inputs(xs)
        values: Dict[Tuple[int, int], Any] = {(g, 0): v
                                              for g, v in bound.items()}
        shardings = self.param_shardings() if self.mesh is not None else {}
        tapes = []
        aux_primals = []
        cache_out: Dict[str, Any] = {}
        stats_out: Dict[str, Any] = {}
        for fn, ext_refs, out_refs, names, k, cache_names in program:
            block_params = {n: params[n] for n in names if n in params}
            ext_vals = tuple(values[r] for r in ext_refs)

            def run(bp, ev, _fn=fn):
                # the mixed-precision cast lives INSIDE the vjp, exactly
                # as in the synchronous loss_fn: grads flow back to the
                # float32 master params
                if cdtype is not None:
                    bp = self._cast_floats(bp, cdtype)
                *diff, stats = _fn(bp, ev, rng, cache)
                return tuple(diff), stats

            with jax.named_scope(f"remat_block_{k}"):
                (outs, aux, cache_vals), vjp, stats = jax.vjp(
                    run, block_params, ext_vals, has_aux=True)
            stats_out.update(stats)
            aux_primals.append(aux)
            cache_out.update(zip(cache_names, cache_vals))
            values.update(zip(out_refs, outs))
            tapes.append((vjp, ext_refs, out_refs, outs, aux, cache_vals))

        raw = values[(self.final_guid, self.final_out_idx)]

        def tail(r):
            logits = self._logits_f32(r)
            from .losses import loss_value

            return loss_value(self.loss_type, logits, labels,
                              self.repl_labels), logits

        loss, tail_vjp, logits = jax.vjp(tail, raw, has_aux=True)
        # aux losses add in block order, matching the synchronous path's
        # `for aux in ctx.aux_losses: loss = loss + aux`
        for aux in aux_primals:
            loss = loss + aux

        cot: Dict[Tuple[int, int], Any] = {}
        (d_raw,) = tail_vjp(jnp.ones_like(loss))
        cot[(self.final_guid, self.final_out_idx)] = d_raw
        grads: Dict[str, Dict[str, Any]] = {}
        for vjp, ext_refs, out_refs, outs, aux, cache_vals in \
                reversed(tapes):
            cots_outs = tuple(
                cot.pop(r) if r in cot else jnp.zeros_like(o)
                for r, o in zip(out_refs, outs))
            dbp, dext = vjp((cots_outs, jnp.ones_like(aux),
                             tuple(jnp.zeros_like(c) for c in cache_vals)))
            # pin each weight grad to its final sharding — the psum
            # happens HERE, overlappable with the upstream backward ...
            if shardings:
                dbp = {n: {w: (lax.with_sharding_constraint(
                    g, shardings[n][w])
                    if shardings.get(n, {}).get(w) is not None else g)
                    for w, g in ws.items()} for n, ws in dbp.items()}
            # ... and order it before the upstream blocks consume the
            # boundary cotangents (a pure scheduling fence, value-identity)
            dbp, dext = lax.optimization_barrier((dbp, dext))
            grads.update(dbp)
            for r, d in zip(ext_refs, dext):
                prev = cot.get(r)
                cot[r] = d if prev is None else jax.tree_util.tree_map(
                    jnp.add, prev, d)
        return (loss, (logits, cache_out, stats_out)), grads

    # ----------------------------------------------------------- cache state
    def init_cache(self):
        """Zeroed cache-state pytree for the graph's CacheOps:
        {"__use_cache__": False, op_name: zeros(input shape)}."""
        import jax.numpy as jnp

        cache = {"__use_cache__": jnp.asarray(False)}
        for node in self.cache_nodes:
            g, i = node.inputs[0]
            src = self.pcg.nodes[g]
            cache[node.name] = jnp.zeros(
                src.out_shapes[i], dtype_to_jnp(src.out_dtypes[i]))
        return cache

    # --------------------------------------------------------------- train step
    def invalidate_jit_cache(self) -> None:
        """Drop every cached jitted function. Required after anything the
        jits bake in as a constant changes — an optimizer learning-rate
        edit (keras LR scheduler, the sentinel's reduced-LR rollback) or
        an op-attr mutation outside recompile()."""
        self._train_step = None
        self._guarded_train_step = None
        self._eval_step = None
        self._forward_jit = None
        self._probe_step = None
        self._serving_jits = {}

    def make_train_step(self, guard: bool = False):
        """One fused jitted step: forward + loss + grad + metrics + update
        (SURVEY §7 hard-part 6 — the reference's separate
        zero_gradients/forward/backward/update phases collapse into this).

        With CacheOps in the graph the step takes the cache pytree as an
        extra trailing argument and returns the fresh cache values as an
        extra trailing result (reference: cache.cc's update/score tasks).

        Activation rematerialization (ISSUE 3): the resolved RematPlan
        (``--remat`` flag > searched ``strategy.remat`` > none) routes the
        forward through checkpointed remat blocks — ``jax.checkpoint``
        with the leveled save policy over bottleneck-cut segments — so the
        saved-for-backward set shrinks to what the plan keeps. Donation
        and the per-op named_scope observability are unchanged.

        Divergence sentinel (ISSUE 4): with ``guard=True`` the step checks
        ``isfinite(loss) & isfinite(|grad|²)`` on device and applies the
        optimizer update under ``lax.cond`` — a non-finite step returns
        params/opt_state UNCHANGED (the poison never reaches the weights)
        plus a trailing ``ok`` bool scalar, the single value the host-side
        ``resilience.GuardedTrainStep`` transfers per step."""
        import jax

        cached = self._guarded_train_step if guard else self._train_step
        if cached is not None:
            return cached

        mesh = self.mesh
        opt = self.optimizer
        has_cache = bool(self.cache_nodes)

        profiling = bool(getattr(self.config, "profiling", False))

        from .remat import resolve_remat_plan

        plan = resolve_remat_plan(self.config, self.strategy)
        # collective-compute overlap (ISSUE 10): per-remat-block grad
        # psums issued as each block's backward completes, instead of the
        # synchronous all-reduces at step end. Needs the block program
        # even at remat level "none" (blocks stay unwrapped — the
        # checkpoint policy is None — but give the backward its per-block
        # sync points).
        overlap = (getattr(self.config, "collective_overlap", "off")
                   or "off") == "on"
        remat_program = None
        if plan.level != "none" or overlap:
            # CacheOp graphs remat too (ISSUE 6 inversion of the old
            # opt-out): cache state threads through the checkpointed
            # blocks as explicit inputs/outputs
            remat_program = self._build_remat_program(plan)
        self.remat_plan = plan if (remat_program is not None
                                   and plan.level != "none") else None

        def loss_fn(params, xs, labels, rng, cache):
            params_c, xs = self._cast_for_compute(params, xs)
            cache_out = {}
            stats_out = {}
            ctx = OpContext(training=True, rng=rng, mesh=mesh, aux_losses=[],
                            profiling=profiling,
                            cache_in=cache, cache_out=cache_out,
                            stats_out=stats_out)
            if remat_program is not None:
                raw = self._forward_remat(params_c, self._bind_inputs(xs),
                                          ctx, remat_program)
            else:
                values = self.forward_outputs(params_c,
                                              self._bind_inputs(xs), ctx)
                raw = values[self.final_guid][self.final_out_idx]
            logits = self._logits_f32(raw)
            with jax.named_scope("loss"):
                loss = loss_value(self.loss_type, logits, labels,
                                  self.repl_labels)
                for aux in ctx.aux_losses:
                    loss = loss + aux
            return loss, (logits, cache_out, stats_out)

        def step(params, opt_state, xs, labels, rng, cache=None):
            if overlap:
                (loss, (logits, cache_out, stats_out)), grads = \
                    self._blockwise_value_and_grad(
                        remat_program, params, xs, labels, rng, cache)
            else:
                (loss, (logits, cache_out, stats_out)), grads = \
                    jax.value_and_grad(loss_fn, has_aux=True)(
                        params, xs, labels, rng, cache)
            if guard:
                import jax.numpy as jnp

                # one reduction over all grads: any NaN/Inf anywhere in the
                # gradient (or the loss) poisons the scalar, so a single
                # isfinite pair is the whole check
                leaves = jax.tree_util.tree_leaves(grads)
                gsq = (sum(jnp.vdot(g, g) for g in leaves)
                       if leaves else jnp.zeros((), jnp.float32))
                ok = jnp.logical_and(jnp.isfinite(loss), jnp.isfinite(gsq))
                with jax.named_scope("optimizer_update"):
                    new_params, new_state = jax.lax.cond(
                        ok,
                        lambda: opt.update(params, grads, opt_state),
                        lambda: (params, opt_state))
            else:
                with jax.named_scope("optimizer_update"):
                    new_params, new_state = opt.update(params, grads,
                                                       opt_state)
            with jax.named_scope("metrics"):
                m = self._compute_metrics(logits, labels)
            if stats_out:
                # the ops' counters ride with the step's metrics: fit fetches
                # both once an epoch (FFModel.routing_stats)
                m = dict(m, op_stats=stats_out)
            out = (new_params, new_state, loss, m)
            if has_cache:
                out = out + (cache_out,)
            if guard:
                out = out + (ok,)
            return out

        fn = named_jit("step", step, donate_argnums=(0, 1))
        if guard:
            self._guarded_train_step = fn
        else:
            self._train_step = fn
        return fn

    def make_probe_step(self):
        """(params, xs, labels, rng[, cache]) -> (loss, grad_l2_norm):
        forward + loss + grad with NO optimizer update and NO donation —
        the parallel-correctness auditor's probe (resilience/audit.py).
        The same loss recipe as the train step (mixed-precision cast, aux
        losses, per-node guid-folded rng, so dropout masks replay
        identically across strategies over the same graph); the two
        returned scalars are the whole comparison surface."""
        import jax
        import jax.numpy as jnp

        if self._probe_step is not None:
            return self._probe_step
        mesh = self.mesh

        def loss_fn(params, xs, labels, rng, cache):
            params_c, xs = self._cast_for_compute(params, xs)
            ctx = OpContext(training=True, rng=rng, mesh=mesh, aux_losses=[],
                            cache_in=cache, cache_out={})
            values = self.forward_outputs(params_c, self._bind_inputs(xs),
                                          ctx)
            logits = self._logits_f32(
                values[self.final_guid][self.final_out_idx])
            loss = loss_value(self.loss_type, logits, labels,
                              self.repl_labels)
            for aux in ctx.aux_losses:
                loss = loss + aux
            return loss

        def probe(params, xs, labels, rng, cache=None):
            loss, grads = jax.value_and_grad(loss_fn)(params, xs, labels,
                                                      rng, cache)
            leaves = jax.tree_util.tree_leaves(grads)
            gsq = (sum(jnp.vdot(g, g).real.astype(jnp.float32)
                       for g in leaves)
                   if leaves else jnp.zeros((), jnp.float32))
            return loss, jnp.sqrt(gsq)

        self._probe_step = jax.jit(probe)
        return self._probe_step

    def profile_ops(self, params, xs, iters: int = 3):
        """ProfiledStep mode (ISSUE 8, docs/calibration.md): execute the
        graph node by node, each node through its own jitted function
        (per-op ``jax.named_scope`` preserved — the spans land in xprof
        timelines too), and time each DISTINCT op shape on device:
        block-until-ready per node, best-of-``iters`` repeats, with the
        jit dispatch overhead (measured once on an identity jit)
        subtracted — the same protocol as the Simulator's standalone
        microbench, but over the LIVE graph with the LIVE weights and
        batch, so the timings reflect the step the loop actually runs.

        Returns one raw record per distinct ``(op params, in-shapes)``
        key: ``{guid, name, op_type, in_shapes, measured_fwd_s, count}``
        (``guid`` is the first node carrying the key; ``count`` how many
        share it — BERT's 24 identical layers yield ONE timed record).
        ``obs.profile.profile_model`` joins these with the live sharding
        assignment into serializable OpRecords."""
        import time

        import jax
        import jax.numpy as jnp

        params_c, xs_c = self._cast_for_compute(params, list(xs))
        mesh = self.mesh
        profiling = bool(getattr(self.config, "profiling", False))
        ctx = OpContext(training=False, rng=None, mesh=mesh,
                        profiling=profiling)

        def timed(fn, *args):
            out = fn(*args)  # warmup: compile + settle
            jax.block_until_ready(out)
            best = float("inf")
            for _ in range(max(iters, 1)):
                t0 = time.perf_counter()
                out = fn(*args)
                jax.block_until_ready(out)
                best = min(best, time.perf_counter() - t0)
            return out, best

        ident = jax.jit(lambda t: t * 1.000001)
        _, overhead = timed(ident, jnp.ones((8, 8), jnp.float32))

        bound = self._bind_inputs(list(xs_c))
        values: Dict[int, List[Any]] = {}
        timings: Dict[Tuple, Optional[Dict[str, Any]]] = {}
        fns: Dict[Tuple, Any] = {}
        # liveness-based freeing: the node-by-node pass would otherwise
        # hold EVERY activation at once (the jitted step lets XLA free
        # intermediates; remat shrinks residency further) — a model sized
        # near HBM would OOM in the very pass meant to profile it. Drop a
        # producer's outputs once its last consumer has run.
        order = self.pcg.topo_order()
        uses: Dict[int, int] = {}
        for node in order:
            for g, _i in node.inputs:
                uses[g] = uses.get(g, 0) + 1
        for node in order:
            if node.op.op_type in (OperatorType.OP_INPUT,
                                   OperatorType.OP_WEIGHT):
                values[node.guid] = [bound[node.guid]]
                continue
            inputs = [values[g][i] for g, i in node.inputs]
            in_shapes = tuple(map(tuple, self._node_input_shapes(node)))
            key = (node.op.params_key(), in_shapes)
            node_params = params_c.get(node.name, {})

            def make_fn(node=node):
                def f(np_, ins):
                    return self._exec_node(node, np_, ins, ctx)
                return jax.jit(f)

            # one compile per distinct key: duplicate-key nodes (BERT's 24
            # identical layers) reuse the first node's jitted fn — their
            # op math is identical and ctx carries no rng to fold, so only
            # the named_scope label (cosmetic here) would differ; a fresh
            # closure per node would retrace+recompile every one
            fn = fns.get(key)
            if fn is None:
                fn = fns[key] = make_fn()
            rec = timings.get(key)
            if rec is None and node.op.op_type == OperatorType.OP_DROPOUT:
                # training-gated: the inference-mode forward is identity,
                # so a timing here would measure dispatch overhead and the
                # closed loop would slam the key's calibration to the
                # floor — execute for consumers, never emit a record
                # (backward ratios likewise stay on calibrate_from_pcg's
                # training-semantics measurement)
                rec = timings[key] = None
            if rec is None and key in timings:
                outs = fn(node_params, inputs)
            elif rec is None:
                outs, best = timed(fn, node_params, inputs)
                timings[key] = {
                    "guid": node.guid, "name": node.name,
                    "op_type": node.op.op_type.name,
                    "in_shapes": in_shapes,
                    "measured_fwd_s": max(best - overhead, 1e-9),
                    "count": 1,
                }
            else:
                # identical key: execute (values feed consumers) without
                # re-timing — the record just counts the extra occurrence
                outs = fn(node_params, inputs)
                rec["count"] += 1
            values[node.guid] = outs
            for g, _i in node.inputs:
                uses[g] -= 1
                if not uses[g]:
                    values.pop(g, None)
        jax.block_until_ready([values[g] for g in values])
        return [r for r in timings.values() if r is not None]

    def train_step_memory_analysis(self, params, opt_state, xs, labels):
        """XLA's compiled memory stats for the full training step
        (jax.stages.Compiled.memory_analysis) — the ground truth the
        analytic ``outputs*2 + weights*4`` model is validated against
        (reference: per-device memory validation vs the framebuffer budget,
        src/runtime/graph.cc:1984-2032). Returns the CompiledMemoryStats
        object (``peak_memory_in_bytes`` is the headline number)."""
        import jax

        step = self.make_train_step()
        rng = jax.random.PRNGKey(0)
        args = (params, opt_state, xs, labels, rng)
        if self.cache_nodes:
            args = args + (self.init_cache(),)
        return step.lower(*args).compile().memory_analysis()

    def _compute_metrics(self, logits, labels):
        if not self.metrics:
            return {}
        if self.repl_labels:
            import jax.numpy as jnp

            k = logits.shape[0] // labels.shape[0]
            labels = jnp.repeat(labels, k, axis=0)
        return self.metrics.compute(logits, labels)

    def make_eval_step(self):
        import jax

        if self._eval_step is not None:
            return self._eval_step
        mesh = self.mesh

        profiling = bool(getattr(self.config, "profiling", False))

        def estep(params, xs, labels):
            params, xs = self._cast_for_compute(params, xs)
            ctx = OpContext(training=False, rng=None, mesh=mesh,
                            profiling=profiling)
            values = self.forward_outputs(params, self._bind_inputs(xs), ctx)
            logits = self._logits_f32(values[self.final_guid][self.final_out_idx])
            loss = loss_value(self.loss_type, logits, labels, self.repl_labels)
            m = self._compute_metrics(logits, labels)
            return loss, m

        self._eval_step = jax.jit(estep)
        return self._eval_step

    def make_forward(self):
        """Inference-only forward (comp mode COMP_MODE_INFERENCE)."""
        import jax

        if self._forward_jit is not None:
            return self._forward_jit
        mesh = self.mesh

        profiling = bool(getattr(self.config, "profiling", False))

        def fwd(params, xs):
            params, xs = self._cast_for_compute(params, xs)
            ctx = OpContext(training=False, rng=None, mesh=mesh,
                            profiling=profiling)
            values = self.forward_outputs(params, self._bind_inputs(xs), ctx)
            return values[self.final_guid][self.final_out_idx]

        self._forward_jit = jax.jit(fwd)
        return self._forward_jit

    # ------------------------------------------------------------- serving
    # Prefill/decode split (ISSUE 6, flexflow_tpu/serving, docs/serving.md):
    # the graph's one forward recipe lowers into TWO inference programs —
    # a per-bucket prefill that populates the KV-cache pytree and ONE
    # static-shape decode step that consumes/extends it. Both reuse
    # forward_outputs (per-op named scopes, strategy output constraints,
    # mixed-precision cast), so the serving path inherits every training-
    # side op improvement for free.
    def _position_const_guids(self) -> List[int]:
        """Compute nodes holding the baked position-id constant (the
        ``broadcast(arange(seq))`` pattern of models/gpt2.py) — serving
        regenerates their value per phase via forward_outputs overrides."""
        from ..serving.kvcache import is_position_constant

        out = []
        for node in self.pcg.compute_nodes():
            if node.op.op_type == OperatorType.OP_CONSTANT and \
                    is_position_constant(node.op.attrs.get("value")):
                out.append(node.guid)
        return out

    def _serving_overrides(self, guids, value):
        return {g: [value] for g in guids}

    def make_prefill_step(self, bucket_len: int, max_decode_len: int):
        """Jitted ``(params, xs, lengths) -> (logits, last_logits, cache)``:
        run the whole right-padded prompt (padded to the scheduler's
        ``bucket_len`` — one compile per bucket, not per prompt length),
        populating a fresh ``max_decode_len`` KV ring buffer per stateful
        node. ``lengths`` (batch,) are the true prompt lengths; the
        returned ``last_logits`` (batch, vocab) are gathered at
        ``lengths - 1`` (the next-token distribution), ``logits`` is the
        full (batch, bucket_len, vocab) tensor for scoring/teacher-forcing
        consumers."""
        import jax

        key = ("prefill", int(bucket_len), int(max_decode_len))
        cached = self._serving_jits.get(key)
        if cached is not None:
            return cached
        mesh = self.mesh
        profiling = bool(getattr(self.config, "profiling", False))
        pos_guids = self._position_const_guids()

        from ..serving.kvcache import ServingState

        def prefill(params, xs, lengths):
            import jax.numpy as jnp

            params, xs = self._cast_for_compute(params, xs)
            lengths = lengths.astype(jnp.int32)
            sv = ServingState(mode="prefill", max_len=max_decode_len,
                              positions=jnp.zeros_like(lengths),
                              lengths=lengths)
            ctx = OpContext(training=False, rng=None, mesh=mesh,
                            profiling=profiling, serving=sv)
            b = xs[0].shape[0]
            pos = jnp.broadcast_to(
                jnp.arange(bucket_len, dtype=jnp.int32), (b, bucket_len))
            values = self.forward_outputs(
                params, self._bind_inputs(xs), ctx,
                overrides=self._serving_overrides(pos_guids, pos))
            logits = self._logits_f32(
                values[self.final_guid][self.final_out_idx])
            idx = jnp.clip(lengths - 1, 0, logits.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]
            return logits, last, sv.cache_out

        fn = named_jit("prefill", prefill)
        self._serving_jits[key] = fn
        return fn

    def make_chunk_prefill_step(self, chunk_len: int, max_decode_len: int,
                                block_size: int, kv_dtype: str = "native"):
        """Jitted ``(params, xs, state, table_row, start, n_new) ->
        (last_logits, new_state)``: ONE prefill chunk of ``chunk_len``
        token slots for a SINGLE request (batch 1) against the paged
        pool (ISSUE 14, chunked prefill + prefix-cache suffix prefill).
        ``xs`` carries the chunk's token ids ``(1, chunk_len)`` (rows
        beyond ``n_new`` are pad), ``table_row`` the slot's (mb,) int32
        block-table row, ``start`` the chunk's first position. The
        chunk's k/v rows are written into the slot's pool blocks and its
        queries attend over the slot's full gathered extent — the cached
        prefix (trie hit) and/or earlier chunks plus this chunk — so a
        long prompt prefills across several co-scheduled iterations and
        a trie-hit admission prefills only its suffix.

        ``last_logits`` (1, vocab) is the next-token distribution at the
        chunk's final REAL row — meaningful on the final chunk only
        (earlier chunks' logits are discarded). One compile per chunk
        shape (``chunk_len``), like the prefill buckets; ``start`` /
        ``n_new`` / the table row are traced, so chunk position and
        block choice never recompile. Numerics follow the one-shot
        prefill's (same streams, logits within the stated tolerance in
        tier-1) — see ``ops.attention._chunk_prefill_attention``.
        ``state`` is donated: the pool updates in place; lengths and
        block tables pass through untouched (the engine arms the slot's
        device-side row and cursor only at prefill completion, so decode
        steps running BETWEEN chunks keep writing the slot's discarded
        tokens into the garbage block, never into its real blocks)."""
        import jax

        key = ("chunk", int(chunk_len), int(max_decode_len),
               int(block_size), str(kv_dtype))
        cached = self._serving_jits.get(key)
        if cached is not None:
            return cached
        mesh = self.mesh
        profiling = bool(getattr(self.config, "profiling", False))
        pos_guids = self._position_const_guids()

        from ..serving.kvcache import DecodeState, ServingState

        def chunk(params, xs, state, table_row, start, n_new):
            import jax.numpy as jnp

            params, xs = self._cast_for_compute(params, xs)
            start = jnp.asarray(start, jnp.int32)
            n_new = jnp.asarray(n_new, jnp.int32)
            sv = ServingState(mode="chunk", max_len=max_decode_len,
                              positions=start[None],
                              lengths=n_new[None],
                              cache_in=state.caches,
                              block_tables=table_row[None, :],
                              block_size=int(block_size),
                              kv_dtype=str(kv_dtype))
            ctx = OpContext(training=False, rng=None, mesh=mesh,
                            profiling=profiling, serving=sv)
            # pad rows (beyond n_new) can place past the position table
            # when start + chunk_len overhangs the context (a trie-hit
            # suffix chunk admitted deep into the prompt): jnp.take's
            # fill mode turns that gather into NaN embeddings, the pad
            # rows' NaN k/v land in the garbage block, and the gathered
            # extent's softmax-zero x NaN poisons the REAL rows. Clamp
            # pads to the chunk's last real position — real rows are
            # untouched, pads stay finite, garbage stays finite.
            pos = (start + jnp.arange(chunk_len, dtype=jnp.int32))[None, :]
            pos = jnp.minimum(pos, start + n_new - 1)
            values = self.forward_outputs(
                params, self._bind_inputs(xs), ctx,
                overrides=self._serving_overrides(pos_guids, pos))
            logits = self._logits_f32(
                values[self.final_guid][self.final_out_idx])
            idx = jnp.clip(n_new - 1, 0, logits.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, idx[None, None, None], axis=1)[:, 0]
            caches = dict(state.caches)
            caches.update(sv.cache_out)
            new_state = DecodeState(caches=caches, lengths=state.lengths,
                                    block_tables=state.block_tables)
            return last, new_state

        fn = named_jit("prefill_chunk", chunk, donate_argnums=(2,))
        self._serving_jits[key] = fn
        return fn

    def make_decode_step(self, max_decode_len: int, block_size: int,
                         guard: bool = False, kv_dtype: str = "native",
                         seq_shards: int = 1):
        """Jitted ``(params, xs, state) -> (logits, new_state)``: ONE token
        per slot through the graph, consuming and extending the
        ``DecodeState`` block pool at each slot's ``lengths`` cursor.
        Static shapes throughout — after the single warmup compile the
        decode loop never recompiles (the engine asserts this via the jit
        cache size). The state argument is donated: the pool updates in
        place on device. ``guard=True`` is the decode-health
        sentinel (ISSUE 9, mirroring ``make_train_step(guard=True)``): the
        step additionally returns ``ok`` — ``isfinite`` of each slot's
        logits reduced to a (n_slots,) bool vector — fused into the same
        program, so the only extra host traffic is that one bool vector
        per step. A graph with routed expert layers returns one more
        value, last: the step's routing counters ``(3,)`` int32 (see
        below), counted on the device. The logits themselves are
        untouched: a poisoned slot's
        quarantine decision is the HOST's (serving/resilience.py), and
        every healthy slot's values stay bitwise-identical to the
        unguarded step's.

        Paged KV (ISSUE 12): the carried ``DecodeState`` holds the block
        tables, ``block_size``/``kv_dtype`` name the pool's layout —
        the tables ride the jitted signature as one more int32 array, so
        the single-compile contract is unchanged.

        ``seq_shards`` (ISSUE 18) selects the sequence-parallel decode
        decomposition (ServingState.seq_shards): the gathered extent is
        scored as that many contiguous key segments merged by the flash
        segment combine — a static trace-time choice, so it joins the
        jit key and keeps the single-compile contract."""
        import jax

        key = ("decode", int(max_decode_len), bool(guard),
               int(block_size), str(kv_dtype), int(seq_shards))
        cached = self._serving_jits.get(key)
        if cached is not None:
            return cached
        mesh = self.mesh
        profiling = bool(getattr(self.config, "profiling", False))
        pos_guids = self._position_const_guids()

        from ..serving.kvcache import DecodeState, ServingState

        def decode(params, xs, state):
            import jax.numpy as jnp

            params, xs = self._cast_for_compute(params, xs)
            sv = ServingState(mode="decode", max_len=max_decode_len,
                              positions=state.lengths,
                              cache_in=state.caches,
                              block_tables=state.block_tables,
                              block_size=int(block_size),
                              kv_dtype=str(kv_dtype),
                              seq_shards=int(seq_shards))
            stats_out: Dict[str, Any] = {}
            ctx = OpContext(training=False, rng=None, mesh=mesh,
                            profiling=profiling, serving=sv,
                            stats_out=stats_out)
            values = self.forward_outputs(
                params, self._bind_inputs(xs), ctx,
                overrides=self._serving_overrides(
                    pos_guids, state.lengths[:, None]))
            logits = self._logits_f32(
                values[self.final_guid][self.final_out_idx])[:, 0]
            new_state = DecodeState(caches=sv.cache_out,
                                    lengths=state.advanced_lengths(),
                                    block_tables=state.block_tables)
            out = (logits, new_state)
            if guard:
                out += (jnp.all(jnp.isfinite(logits), axis=-1),)
            routed = [v["tokens_per_expert"] for v in stats_out.values()
                      if "tokens_per_expert" in v]
            if routed:
                # a graph with routed expert layers hands its step's
                # counters out LAST: [pairs held here, held experts that
                # got a row, the fullest expert's rows over its layer's
                # mean in thousandths (the largest over the layers)]
                out += (jnp.stack([
                    sum(jnp.sum(t) for t in routed),
                    sum(jnp.sum(t > 0) for t in routed),
                    jnp.max(jnp.stack([
                        1000 * jnp.max(t) * t.shape[0]
                        // jnp.maximum(jnp.sum(t), 1) for t in routed])),
                ]).astype(jnp.int32),)
            return out

        fn = named_jit("decode", decode, donate_argnums=(2,))
        self._serving_jits[key] = fn
        return fn
