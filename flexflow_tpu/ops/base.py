"""Op base class: the typed node of the Parallel Computation Graph.

Analog of the reference's ``class Op`` (include/flexflow/operator.h:51). The
reference contract — virtual ``init/forward/backward`` building Legion index
launches plus ``measure_operator_cost`` — maps TPU-natively to:

* ``forward(params, inputs, ctx)``: a pure, jax-traceable function. Backward is
  derived by ``jax.grad`` (sharded autodiff inserts the collectives the
  reference implements by hand in optimizer_kernel.cu / parallel ops).
* shape/dtype inference (``infer_output_shapes``) replacing Legion region setup.
* ``weight_specs``: declared parameters with initializers (reference: per-op
  weight ParallelTensors).
* ``flops`` / ``memory_bytes``: analytic cost hooks for the simulator
  (reference: measure_operator_cost, simulator.cc:489).

Op *Params* dataclass-equality/hashing for node dedup (reference:
``get_or_create_node`` cache, include/flexflow/model.h:679-706) is provided by
``params_key``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..ffconst import DataType, OperatorType
from ..machine_view import MachineView


@dataclasses.dataclass
class OpContext:
    """Per-call context threaded through forward (replaces reference OpMeta)."""

    training: bool = True
    rng: Any = None  # jax PRNGKey, split per dropout-like op
    seq_length: int = -1
    mesh: Any = None  # jax Mesh when running under pjit
    profiling: bool = False
    # auxiliary loss terms appended by ops (e.g. MoE load-balance, the
    # reference's lambda_bal term in aggregate.cu backward); the executor adds
    # their sum to the training loss. Shared list across all node contexts.
    aux_losses: Any = None
    # cache-op state (reference: src/ops/cache.cc + recompile pairing):
    # cache_in = {op_name: cached_tensor, "__use_cache__": bool scalar} fed
    # into the step; cache_out = dict the CacheOps fill with fresh values,
    # returned by the executor's step for host-side scoring. Shared dicts
    # across all node contexts.
    cache_in: Any = None
    cache_out: Any = None
    # serving state (ISSUE 6, flexflow_tpu/serving): a
    # ``serving.kvcache.ServingState`` when this forward is a prefill or
    # decode step of the inference engine — ops with sequence state
    # (causal attention's KV, the LSTM carry) read ``cache_in`` and
    # publish into ``cache_out`` keyed by op name. None during training
    # and plain whole-sequence inference, which is the only cost the
    # existing paths pay.
    serving: Any = None
    # counters an op hands out of the step with no sync (the routed expert
    # layer's tokens per held expert): {op_name: {counter: array}}, filled
    # during the forward, returned with the step's metrics and fetched where
    # ``fit`` fetches those (FFModel.routing_stats). None outside training.
    stats_out: Any = None


# registry: OperatorType -> Op subclass
_OP_REGISTRY: Dict[OperatorType, type] = {}


def register_op(op_type: OperatorType):
    def deco(cls):
        _OP_REGISTRY[op_type] = cls
        cls.op_type = op_type
        return cls

    return deco


def op_class_for(op_type: OperatorType) -> type:
    if op_type not in _OP_REGISTRY:
        raise KeyError(f"no Op registered for {op_type.name}")
    return _OP_REGISTRY[op_type]


class Op:
    """Base PCG operator."""

    op_type: OperatorType = OperatorType.OP_NOOP

    def __init__(self, name: str, attrs: Dict[str, Any], dtype: DataType,
                 num_inputs: int = 1):
        self.name = name
        self.attrs = dict(attrs)
        self.data_type = dtype
        self.num_inputs = num_inputs
        self.machine_view: Optional[MachineView] = None

    # -- identity / dedup -------------------------------------------------------
    def params_key(self) -> Tuple:
        """Hashable params tuple (reference: <op>_params.h structs)."""
        return (self.op_type, self.data_type,
                tuple(sorted((k, _freeze(v)) for k, v in self.attrs.items())))

    # -- shape inference --------------------------------------------------------
    def infer_output_shapes(
        self, input_shapes: List[Tuple[int, ...]]
    ) -> List[Tuple[int, ...]]:
        raise NotImplementedError(self.op_type.name)

    def output_dtype(self, input_dtypes: List[DataType]) -> DataType:
        return input_dtypes[0] if input_dtypes else self.data_type

    def output_dtypes(self, input_dtypes: List[DataType],
                      num_outputs: int) -> List[DataType]:
        """Per-output dtypes; override for ops with heterogeneous outputs
        (e.g. TopK's int32 indices)."""
        return [self.output_dtype(input_dtypes)] * num_outputs

    # -- parameters -------------------------------------------------------------
    def weight_specs(
        self, input_shapes: List[Tuple[int, ...]]
    ) -> Dict[str, Tuple[Tuple[int, ...], DataType, Any]]:
        """name -> (shape, dtype, initializer); empty for stateless ops."""
        return {}

    # -- compute ----------------------------------------------------------------
    def forward(self, params: Dict[str, Any], inputs: List[Any],
                ctx: OpContext) -> List[Any]:
        raise NotImplementedError(self.op_type.name)

    # -- cost model hooks (reference: measure_operator_cost) --------------------
    def flops(self, input_shapes: List[Tuple[int, ...]],
              output_shapes: List[Tuple[int, ...]]) -> int:
        """Forward FLOPs; default = elementwise over outputs."""
        return sum(int(np.prod(s)) for s in output_shapes)

    def memory_bytes(self, input_shapes, output_shapes) -> int:
        from ..ffconst import size_of_datatype

        el = size_of_datatype(self.data_type)
        return el * (sum(int(np.prod(s)) for s in input_shapes)
                     + sum(int(np.prod(s)) for s in output_shapes))

    # -- serving state ----------------------------------------------------------
    def slot_state_bytes(self, el: int = 0) -> int:
        """What ONE serving slot costs in this op's slot-major RECURRENT
        state — a summary of the whole prefix, a fixed size whatever the
        context — or 0 for an op that carries none (attention's per-token
        pool rows are priced a token: ``kvcache.node_token_bytes``).
        ``el``: bytes of an element as the state's reduced-precision leaves
        rest — the compute dtype's where the graph computes in a reduced
        one (the engine says); the node's own dtype otherwise. The engine's
        ``recurrent_state_bytes``, its refusal of chunks and prefix hits,
        the serving search's price a slot and the fusion rule all ask
        here."""
        return 0

    def slot_state_heads_a_row(self) -> int:
        """Heads that share one row of this op's recurrent state AS IT
        RESTS (1: a head, or no head at all, a row). The bytes do not
        change with it (:meth:`slot_state_bytes` is the logical count);
        what the chip pads does — the engine's
        ``recurrent_state_bytes_at_rest`` and ``state_heads_a_row``."""
        return 1

    # -- parallelization metadata ----------------------------------------------
    def parallelizable_dims(self, input_shapes) -> Dict[str, Any]:
        """Which logical dims of output 0 may be sharded, and how weights follow.

        TPU-native analog of the reference's ParallelDimMappingRecord machinery
        (operator.h:22-118): returns {"batch": True, "channel_out": idx or None,
        ...} consumed by the strategy search.
        """
        return {"batch": True}

    def can_inplace_output(self) -> bool:
        return False

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.name})"


def no_chunk_carry(node: str, what: str, asked: str = "") -> str:
    """The one sentence with which a graph that holds a recurrent node
    refuses chunked prefill and the prefix cache: the engine's at
    construction (``asked`` names the option) and each recurrent op's own
    under a chunk, whatever else the graph holds (attention or latent
    attention nodes beside it change nothing: their pool rows could be
    chunked and shared, the state beside them cannot)."""
    return (f"{asked + ': ' if asked else ''}chunked prefill and the prefix "
            "cache support attention-only stateful graphs; this model has a "
            f"recurrent node ({node}): {what} is a summary of the whole "
            "prefix, not per-token pool rows, so a chunk would have to start "
            "from a carried state and a prefix hit from a snapshot of one, "
            "which the engine does not keep (ROADMAP.md, Reach R8): serve "
            "without --prefill-chunk-tokens and with --prefix-cache off")


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return (v.shape, v.dtype.str, v.tobytes())
    if callable(v) and not isinstance(v, type):
        return getattr(v, "__name__", repr(v))
    return v
