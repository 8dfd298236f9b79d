"""FFModel: the central model object.

TPU-native rebuild of the reference's ``FFModel`` (include/flexflow/model.h:326,
src/runtime/model.cc:4708): the op-builder API (model.h:336-554, mirrored from
the Python surface flexflow_cffi.py:883-2100 which is the compatibility
contract), ``compile`` (model.cc:2803), and the train-step drivers
(forward/backward/update/fit/eval).

``compile`` here follows the same pipeline as the reference's (SURVEY §3.3):
Layer graph -> PCG (`create_operators_from_layers`, model.cc:2785) -> strategy
selection (Unity search / data-parallel default / imported strategy) -> lowering
(Executor builds the jitted step; XLA replaces Legion mapping + regions).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .config import FFConfig
from .ffconst import (ActiMode, AggrMode, CompMode, DataType, LossType,
                      MetricsType, OperatorType, PoolType, dtype_to_jnp,
                      jnp_to_dtype)
from .layer import Layer
from .tensor import Tensor
from .execution.losses import loss_value
from .execution.metrics import Metrics, PerfMetrics
from .execution.optimizers import Optimizer, SGDOptimizer
from .obs.builds import build_mark, built_since, enter, leave
from .obs.trace import setup_span


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self._layers: List[Layer] = []
        self._input_tensors: List[Tensor] = []
        self.optimizer: Optional[Optimizer] = None

        # populated by compile()
        self.pcg = None
        self.strategy = None
        self.mesh = None
        self.executor = None
        self.params = None
        self.opt_state = None
        self.metrics_obj: Optional[Metrics] = None
        self.loss_type: Optional[LossType] = None
        self.label_tensor: Optional[Tensor] = None
        self._perf = PerfMetrics()
        self._tensor_to_node: Dict[int, int] = {}  # tensor.guid -> pcg guid/idx
        self._layer_to_node: Dict[int, int] = {}
        self._rng_counter = 0
        # manual-loop staging (API parity: forward/backward/update phases)
        self._staged: Dict[str, Any] = {}
        self._recompile_state = None
        self._pipeline_trainer = None  # set by compile for GPipe strategies
        # {cache_op_name: latest score_fn value} filled during fit
        # (reference: cache.cc score futures read by the recompile trigger)
        self.cache_scores: Dict[str, float] = {}

    # ======================================================= tensor creation ==
    def create_tensor(self, dims: Sequence[int],
                      dtype: DataType = DataType.DT_FLOAT,
                      create_grad: bool = True, name: str = "") -> Tensor:
        if not isinstance(dtype, DataType):
            raise TypeError(
                f"create_tensor dtype must be a DataType, got {dtype!r} "
                "(signature: create_tensor(dims, dtype, create_grad, name))")
        t = Tensor(dims, dtype, create_grad=create_grad,
                   name=name or f"input_{len(self._input_tensors)}", model=self)
        self._input_tensors.append(t)
        return t

    # ================================================================ builders ==
    def _add_layer(self, op_type: OperatorType, inputs: List[Tensor],
                   attrs: Dict[str, Any], dtype: Optional[DataType] = None,
                   name: Optional[str] = None
                   ) -> Union[Tensor, List[Tensor]]:
        from .ops.base import op_class_for

        dtype = dtype or (inputs[0].dtype if inputs else DataType.DT_FLOAT)
        layer = Layer(op_type, dtype, name, inputs, attrs=attrs,
                      index=len(self._layers))
        op = op_class_for(op_type)(layer.name, attrs, dtype,
                                   num_inputs=len(inputs))
        out_shapes = op.infer_output_shapes([t.dims for t in inputs])
        out_dtypes = op.output_dtypes([t.dtype for t in inputs],
                                      len(out_shapes))
        # surface declared weights as user-visible tensors (reference parity)
        for wname, (shape, wdtype, init) in op.weight_specs(
                [t.dims for t in inputs]).items():
            layer.add_weight(wname, shape, wdtype, init)
        outs = []
        for i, s in enumerate(out_shapes):
            t = Tensor(s, out_dtypes[i], owner_layer=layer, owner_idx=i,
                       model=self)
            t.name = f"{layer.name}:out{i}"
            outs.append(t)
        layer.outputs = outs
        self._layers.append(layer)
        return outs[0] if len(outs) == 1 else outs

    # ---- dense / conv / pool (reference model.h:336-554) ----------------------
    def dense(self, input: Tensor, out_dim: int,
              activation: ActiMode = ActiMode.AC_MODE_NONE,
              use_bias: bool = True, datatype: Optional[DataType] = None,
              kernel_initializer=None, bias_initializer=None,
              kernel_regularizer=None,
              name: Optional[str] = None) -> Tensor:
        """kernel_regularizer: ("l1"|"l2", lambda) weight-decay spec added to
        the training loss (reference: RegularizerMode on Linear)."""
        return self._add_layer(
            OperatorType.OP_LINEAR, [input],
            {"out_dim": out_dim, "activation": activation, "use_bias": use_bias,
             "kernel_initializer": kernel_initializer,
             "bias_initializer": bias_initializer,
             "kernel_regularizer": kernel_regularizer},
            datatype or input.dtype, name)

    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int,
               kernel_w: int, stride_h: int, stride_w: int, padding_h: int,
               padding_w: int, activation: ActiMode = ActiMode.AC_MODE_NONE,
               groups: int = 1, use_bias: bool = True,
               kernel_initializer=None, bias_initializer=None,
               name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.OP_CONV2D, [input],
            {"out_channels": out_channels, "kernel_h": kernel_h,
             "kernel_w": kernel_w, "stride_h": stride_h, "stride_w": stride_w,
             "padding_h": padding_h, "padding_w": padding_w,
             "activation": activation, "groups": groups, "use_bias": use_bias,
             "kernel_initializer": kernel_initializer,
             "bias_initializer": bias_initializer},
            input.dtype, name)

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               pool_type: PoolType = PoolType.POOL_MAX,
               activation: ActiMode = ActiMode.AC_MODE_NONE,
               name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.OP_POOL2D, [input],
            {"kernel_h": kernel_h, "kernel_w": kernel_w, "stride_h": stride_h,
             "stride_w": stride_w, "padding_h": padding_h,
             "padding_w": padding_w, "pool_type": pool_type,
             "activation": activation}, input.dtype, name)

    def batch_norm(self, input: Tensor, relu: bool = True,
                   name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.OP_BATCHNORM, [input],
                               {"relu": relu}, input.dtype, name)

    def layer_norm(self, input: Tensor, axes: Sequence[int],
                   elementwise_affine: bool = True, eps: float = 1e-5,
                   name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.OP_LAYERNORM, [input],
            {"axes": list(axes), "elementwise_affine": elementwise_affine,
             "eps": eps}, input.dtype, name)

    def rms_norm(self, input: Tensor, axes: Sequence[int] = (-1,),
                 eps: float = 1e-6, name: Optional[str] = None,
                 gain: Optional[str] = None) -> Tensor:
        """``x / rms(x) * g(scale)``: ``g`` the identity, or a form of
        ``ops.normalization.norm_gain`` (``gain="sigmoid2"``: ``2
        sigmoid(scale)``, ``scale`` stored about zero)."""
        attrs = {"axes": list(axes), "eps": eps}
        if gain:
            attrs["gain"] = gain
        return self._add_layer(OperatorType.OP_RMSNORM, [input], attrs,
                               input.dtype, name)

    def batch_matmul(self, A: Tensor, B: Tensor,
                     name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.OP_BATCHMATMUL, [A, B], {},
                               A.dtype, name)

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
                  dtype: DataType = DataType.DT_FLOAT, shared_op=None,
                  kernel_initializer=None, name: Optional[str] = None
                  ) -> Tensor:
        return self._add_layer(
            OperatorType.OP_EMBEDDING, [input],
            {"num_entries": num_entries, "out_dim": out_dim, "aggr": aggr,
             "kernel_initializer": kernel_initializer}, dtype, name)

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int, kdim: int = 0,
                            vdim: int = 0, dropout: float = 0.0,
                            bias: bool = True, add_bias_kv: bool = False,
                            add_zero_attn: bool = False,
                            kernel_initializer=None, causal: bool = False,
                            name: Optional[str] = None,
                            num_kv_heads: Optional[int] = None,
                            window: Optional[int] = None,
                            rope_theta: Optional[float] = None,
                            qk_norm: Optional[float] = None,
                            gated: bool = False,
                            qk_norm_whole: bool = False) -> Tensor:
        """The decoder-block attributes after ``name`` are off by default
        (ops/attention.py): ``num_kv_heads`` grouped-query K/V heads,
        ``window`` a causal sliding window, ``rope_theta`` rotary positions,
        ``qk_norm`` the eps of a per-head RMS norm on q and k, ``gated`` a
        sigmoid gate on the core's output, ``qk_norm_whole`` that norm over
        the WHOLE q and k width (every head at once, a gain a channel)."""
        attrs = {"embed_dim": embed_dim, "num_heads": num_heads, "kdim": kdim,
                 "vdim": vdim, "dropout": dropout, "bias": bias,
                 "add_bias_kv": add_bias_kv, "add_zero_attn": add_zero_attn,
                 "kernel_initializer": kernel_initializer, "causal": causal}
        for attr, given in (("num_kv_heads", num_kv_heads),
                            ("window", window), ("rope_theta", rope_theta),
                            ("qk_norm", qk_norm), ("gated", gated),
                            ("qk_norm_whole", qk_norm_whole)):
            if given:
                attrs[attr] = given
        if window and not causal:
            raise ValueError("multihead_attention: a sliding window needs "
                             "causal=True")
        if qk_norm_whole and not qk_norm:
            raise ValueError("multihead_attention: qk_norm_whole is a form "
                             "of qk_norm, whose eps it needs")
        if num_kv_heads and num_heads % num_kv_heads:
            raise ValueError(
                f"multihead_attention: {num_heads} heads are no multiple of "
                f"{num_kv_heads} key/value heads")
        return self._add_layer(OperatorType.OP_MULTIHEAD_ATTENTION,
                               [query, key, value], attrs, query.dtype, name)

    def latent_attention(self, input: Tensor, embed_dim: int,
                         num_heads: int, q_rank: int, kv_rank: int,
                         nope_dim: int, rope_dim: int, v_dim: int,
                         rope_theta: float = 10000.0, eps: float = 1e-6,
                         kernel_initializer=None,
                         name: Optional[str] = None,
                         rope_scaling: Optional[dict] = None,
                         rope_interleave: bool = False,
                         gated: bool = False) -> Tensor:
        """Causal multi-head latent attention (ops/latent_attention.py):
        queries through a ``q_rank`` bottleneck, keys and values
        up-projected from a ``kv_rank`` row a token that carries one shared
        rotary key of ``rope_dim`` beside it; heads of ``nope_dim +
        rope_dim`` for the score and ``v_dim`` for the value. Under a
        serving context the row is what the KV pool holds. Off by default:
        ``rope_scaling`` (a YaRN group: its frequencies, and ``m^2`` on the
        softmax scale), ``rope_interleave`` (neighbour pairs), ``gated`` (a
        sigmoid gate a head on the core's output)."""
        attrs = {"embed_dim": embed_dim, "num_heads": num_heads,
                 "q_rank": q_rank, "kv_rank": kv_rank, "nope_dim": nope_dim,
                 "rope_dim": rope_dim, "v_dim": v_dim,
                 "rope_theta": rope_theta, "eps": eps, "causal": True,
                 "kernel_initializer": kernel_initializer}
        if rope_dim % 2:
            raise ValueError("latent_attention: rope_dim must be even")
        if rope_scaling:
            kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
            if kind != "yarn":
                raise ValueError("latent_attention: rope_scaling of type "
                                 f"{kind!r} is not built (yarn is)")
            attrs["rope_scaling"] = dict(rope_scaling)
        for attr, given in (("rope_interleave", rope_interleave),
                            ("gated", gated)):
            if given:
                attrs[attr] = True
        return self._add_layer(OperatorType.OP_LATENT_ATTENTION, [input],
                               attrs, input.dtype, name)

    def ssm_mixer(self, input: Tensor, inner_dim: int, state_dim: int,
                  conv_width: int, dt_rank: int, conv_bias: bool,
                  proj_bias: bool, norm_eps: float,
                  kernel_initializer=None,
                  name: Optional[str] = None) -> Tensor:
        """Selective state-space (Mamba-1) mixer with RMS norms on ``dt``,
        ``B`` and ``C`` (ops/ssm.py): ``inner_dim`` channels, each with a
        ``state_dim``-number recurrent state, after a causal depthwise conv
        of ``conv_width``; ``dt`` through a ``dt_rank`` bottleneck. Every
        width is the caller's: no default stands in for one. Under a serving
        context the state and the conv's last inputs are what a slot
        holds."""
        attrs = {"inner_dim": inner_dim, "state_dim": state_dim,
                 "conv_width": conv_width, "dt_rank": dt_rank,
                 "conv_bias": bool(conv_bias), "proj_bias": bool(proj_bias),
                 "norm_eps": norm_eps,
                 "kernel_initializer": kernel_initializer}
        if conv_width < 2:
            raise ValueError("ssm_mixer: conv_width must be at least 2")
        return self._add_layer(OperatorType.OP_SSM_MIXER, [input], attrs,
                               input.dtype, name)

    def gated_delta_mixer(self, input: Tensor, num_heads: int, key_dim: int,
                          value_dim: int, conv_width: int, neg_eigval: bool,
                          norm_eps: float, kernel_initializer=None,
                          name: Optional[str] = None,
                          num_key_heads: Optional[int] = None,
                          gate: str = "silu") -> Tensor:
        """Gated delta-rule (Gated DeltaNet) mixer (ops/gated_delta.py):
        ``num_heads`` heads, each with a ``(key_dim, value_dim)`` matrix
        state decayed by a gate and corrected by a rank-one delta a token,
        after causal depthwise convs of ``conv_width`` on q, k and v;
        ``neg_eigval`` lets the write strength reach 2. Every width is the
        caller's: no default stands in for one. Under a serving context the
        state and the convs' last inputs are what a slot holds.
        ``num_key_heads`` < ``num_heads``: grouped key heads (value head j
        reads key head ``j // (num_heads / num_key_heads)``); ``gate``: the
        output gate's form (``ops.gated_delta.GATES``)."""
        from .ops.gated_delta import GATES

        attrs = {"num_heads": num_heads, "key_dim": key_dim,
                 "value_dim": value_dim, "conv_width": conv_width,
                 "neg_eigval": bool(neg_eigval), "norm_eps": norm_eps,
                 "kernel_initializer": kernel_initializer}
        if conv_width < 2:
            raise ValueError(
                "gated_delta_mixer: conv_width must be at least 2")
        if num_key_heads and num_key_heads != num_heads:
            if num_heads % num_key_heads:
                raise ValueError(
                    f"gated_delta_mixer: {num_heads} value heads are no "
                    f"multiple of {num_key_heads} key heads")
            attrs["num_key_heads"] = int(num_key_heads)
        if gate not in GATES:
            raise ValueError(f"gated_delta_mixer: gate {gate!r} is none of "
                             f"{GATES}")
        if gate != "silu":
            attrs["gate"] = gate
        return self._add_layer(OperatorType.OP_GATED_DELTA_MIXER, [input],
                               attrs, input.dtype, name)

    # ---- elementwise ----------------------------------------------------------
    def _binary(self, op_type, x, y, name=None, inplace_a=False):
        return self._add_layer(op_type, [x, y], {}, x.dtype, name)

    def add(self, x, y, inplace_a=False, name=None):
        return self._binary(OperatorType.OP_EW_ADD, x, y, name, inplace_a)

    def subtract(self, x, y, inplace_a=False, name=None):
        return self._binary(OperatorType.OP_EW_SUB, x, y, name, inplace_a)

    def multiply(self, x, y, inplace_a=False, name=None):
        return self._binary(OperatorType.OP_EW_MUL, x, y, name, inplace_a)

    def divide(self, x, y, inplace_a=False, name=None):
        return self._binary(OperatorType.OP_EW_DIV, x, y, name, inplace_a)

    def max(self, x, y, inplace_a=False, name=None):
        return self._binary(OperatorType.OP_EW_MAX, x, y, name, inplace_a)

    def min(self, x, y, inplace_a=False, name=None):
        return self._binary(OperatorType.OP_EW_MIN, x, y, name, inplace_a)

    def _unary(self, op_type, x, attrs=None, name=None):
        return self._add_layer(op_type, [x], attrs or {}, x.dtype, name)

    def exp(self, x, name=None):
        return self._unary(OperatorType.OP_EXP, x, name=name)

    def log(self, x, name=None):
        return self._unary(OperatorType.OP_LOG, x, name=name)

    def sin(self, x, name=None):
        return self._unary(OperatorType.OP_SIN, x, name=name)

    def cos(self, x, name=None):
        return self._unary(OperatorType.OP_COS, x, name=name)

    def rsqrt(self, x, name=None):
        return self._unary(OperatorType.OP_RSQRT, x, name=name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(OperatorType.OP_POW, x, {"exponent": exponent}, name)

    def scalar_multiply(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OperatorType.OP_SCALAR_MULTIPLY, x,
                           {"scalar": scalar}, name)

    def scalar_add(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OperatorType.OP_SCALAR_ADD, x, {"scalar": scalar},
                           name)

    def scalar_sub(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OperatorType.OP_SCALAR_SUB, x, {"scalar": scalar},
                           name)

    def scalar_true_divide(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OperatorType.OP_SCALAR_TRUE_DIV, x,
                           {"scalar": scalar}, name)

    def relu(self, x, inplace=True, name=None):
        return self._unary(OperatorType.OP_RELU, x, name=name)

    def identity(self, x, name=None):
        return self._unary(OperatorType.OP_IDENTITY, x, name=name)

    def sigmoid(self, x, name=None):
        return self._unary(OperatorType.OP_SIGMOID, x, name=name)

    def tanh(self, x, name=None):
        return self._unary(OperatorType.OP_TANH, x, name=name)

    def elu(self, x, inplace=True, name=None):
        return self._unary(OperatorType.OP_ELU, x, name=name)

    def gelu(self, x, name=None):
        return self._unary(OperatorType.OP_GELU, x, name=name)

    def silu(self, x, name=None):
        return self._unary(OperatorType.OP_SILU, x, name=name)

    def gated_mlp(self, x, intermediate: int, kernel_initializer=None,
                  name=None, limit: Optional[float] = None) -> Tensor:
        """``W_down(silu(W_gate x) * W_up x)``, no biases, as one node
        (ops/linear.py GatedMLPOp). ``limit`` L: the clamped form,
        ``silu(min(g, L)) * clip(u, -L, L)``."""
        attrs = {"intermediate": intermediate,
                 "kernel_initializer": kernel_initializer}
        if limit:
            attrs["limit"] = float(limit)
        return self._unary(OperatorType.OP_GATED_MLP, x, attrs, name)

    def dropout(self, x, rate: float = 0.5, seed: int = 0, name=None):
        return self._unary(OperatorType.OP_DROPOUT, x,
                           {"rate": rate, "seed": seed}, name)

    # ---- shape ops ------------------------------------------------------------
    def flat(self, x, name=None):
        return self._unary(OperatorType.OP_FLAT, x, name=name)

    def softmax(self, x, axis: int = -1, name=None,
                use_pallas: bool = False):
        """use_pallas opts aligned last-axis rows into the Pallas row-softmax
        kernel on TPU (kernels/softmax.py; default jax.nn.softmax — measured
        at parity on v5e, see the kernel docstring)."""
        return self._unary(OperatorType.OP_SOFTMAX, x,
                           {"axis": axis, "use_pallas": use_pallas}, name)

    def reshape(self, x, shape: Sequence[int], name=None):
        return self._unary(OperatorType.OP_RESHAPE, x,
                           {"shape": list(shape)}, name)

    def transpose(self, x, perm: Sequence[int], name=None):
        return self._unary(OperatorType.OP_TRANSPOSE, x,
                           {"perm": list(perm)}, name)

    def reverse(self, x, axis: int, name=None):
        return self._unary(OperatorType.OP_REVERSE, x, {"axis": axis}, name)

    def slice_tensor(self, x, items, name=None):
        """Static getitem: items is a tuple of slice/int/None (torch frontend
        getitem; reference OP_SLICE)."""
        from .ops.tensor_ops import encode_slice_items

        return self._unary(OperatorType.OP_SLICE, x,
                           {"items": encode_slice_items(items)}, name)

    def constant(self, value, dtype: Optional[DataType] = None, name=None):
        """Frozen host tensor as a graph node (traced buffers like
        position_ids; reference analog: non-trainable weight tensors)."""
        import numpy as np

        from .ffconst import jnp_to_dtype

        value = np.asarray(value)
        if dtype is None:
            dtype = jnp_to_dtype(value.dtype)
        return self._add_layer(OperatorType.OP_CONSTANT, [],
                               {"value": value}, dtype, name)

    def sdpa(self, q: Tensor, k: Tensor, v: Tensor,
             attn_mask: Optional[Tensor] = None, dropout: float = 0.0,
             causal: bool = False, scale: Optional[float] = None, name=None):
        """Attention core on pre-projected (batch, heads, seq, head_dim)
        tensors (torch F.scaled_dot_product_attention)."""
        inputs = [q, k, v] + ([attn_mask] if attn_mask is not None else [])
        return self._add_layer(OperatorType.OP_SDPA, inputs,
                               {"dropout": dropout, "causal": causal,
                                "scale": scale}, q.dtype, name)

    def lstm(self, input: Tensor, hidden_size: int,
             initial_state: Optional[Tensor] = None,
             name: Optional[str] = None) -> List[Tensor]:
        """LSTM over (batch, seq, dim) -> [(batch, seq, hidden),
        final_state (batch, 2*hidden)]. Reference: nmt/lstm.cu (cuDNN RNN);
        here a first-class op (ops/recurrent.py)."""
        inputs = [input] + ([initial_state] if initial_state is not None
                            else [])
        return self._add_layer(OperatorType.OP_LSTM, inputs,
                               {"hidden_size": hidden_size},
                               input.dtype, name)

    def concat(self, tensors: List[Tensor], axis: int, name=None):
        return self._add_layer(OperatorType.OP_CONCAT, list(tensors),
                               {"axis": axis}, tensors[0].dtype, name)

    def split(self, x, sizes: Union[int, List[int]], axis: int, name=None):
        if isinstance(sizes, int):
            dim = x.dims[axis % len(x.dims)]
            assert dim % sizes == 0
            sizes = [dim // sizes] * sizes
        outs = self._add_layer(OperatorType.OP_SPLIT, [x],
                               {"sizes": list(sizes), "axis": axis},
                               x.dtype, name)
        return outs if isinstance(outs, list) else [outs]

    def gather(self, x, index: Tensor, dim: int, name=None):
        return self._add_layer(OperatorType.OP_GATHER, [x, index],
                               {"dim": dim}, x.dtype, name)

    def cast(self, x, dtype: DataType, name=None):
        return self._add_layer(OperatorType.OP_CAST, [x],
                               {"target_dtype": dtype}, dtype, name)

    def mean(self, x, dims: Sequence[int], keepdims: bool = False, name=None):
        return self._unary(OperatorType.OP_MEAN, x,
                           {"axes": list(dims), "keepdims": keepdims}, name)

    def reduce_sum(self, x, axes: Sequence[int], keepdims: bool = False,
                   name=None):
        return self._unary(OperatorType.OP_REDUCE_SUM, x,
                           {"axes": list(axes), "keepdims": keepdims}, name)

    def top_k(self, x, k: int, sorted: bool = True, name=None,
              use_pallas: bool = False):
        # use_pallas AFTER name: positional reference-compat signature is
        # top_k(input, k, sorted, name) (flexflow_cffi surface)
        return self._add_layer(OperatorType.OP_TOPK, [x],
                               {"k": k, "sorted": sorted,
                                "use_pallas": use_pallas}, x.dtype, name)

    # ---- MoE (reference: src/ops/moe.cc, group_by.cc, aggregate.cc) -----------
    def group_by(self, input: Tensor, assign: Tensor, n: int,
                 alpha: float = 1.0, name=None) -> List[Tensor]:
        outs = self._add_layer(OperatorType.OP_GROUP_BY, [input, assign],
                               {"n": n, "alpha": alpha}, input.dtype, name)
        return outs if isinstance(outs, list) else [outs]

    def aggregate(self, gate_preds: Tensor, gate_assign: Tensor,
                  true_gate_assign: Tensor, full_gate_grads: Tensor,
                  exp_preds: List[Tensor], n: int, lambda_bal: float = 0.0,
                  name=None) -> Tensor:
        ins = [gate_preds, gate_assign, true_gate_assign, full_gate_grads] + \
            list(exp_preds)
        return self._add_layer(OperatorType.OP_AGGREGATE, ins,
                               {"n": n, "lambda_bal": lambda_bal},
                               exp_preds[0].dtype, name)

    def aggregate_spec(self, gate_preds, gate_assign, true_gate_assign,
                       full_gate_grads, exp_preds: List[Tensor], n: int,
                       lambda_bal: float = 0.0, name=None) -> Tensor:
        ins = [gate_preds, gate_assign, true_gate_assign, full_gate_grads] + \
            list(exp_preds)
        return self._add_layer(OperatorType.OP_AGG_SPEC, ins,
                               {"n": n, "lambda_bal": lambda_bal},
                               exp_preds[0].dtype, name)

    def cache(self, input: Tensor, num_batches: int, score_fn=None, name=None):
        return self._unary(OperatorType.OP_CACHE, input,
                           {"num_batches": num_batches, "score_fn": score_fn},
                           name)

    def moe(self, input: Tensor, num_exp: int, num_select: int,
            expert_hidden_size: int, alpha: float = 2.0,
            lambda_bal: float = 0.04) -> Tensor:
        """Composite MoE layer (reference: FFModel::moe, src/ops/moe.cc:20-45):
        gate dense -> softmax -> top_k -> group_by -> per-expert dense ->
        aggregate."""
        gate = self.dense(input, num_exp, name="moe_gate")
        gate = self.softmax(gate)
        topk_out = self.top_k(gate, num_select)
        topk_values, topk_assign = topk_out[0], topk_out[1]
        grouped = self.group_by(input, topk_assign, num_exp, alpha)
        exp_preds = [
            self.dense(g, expert_hidden_size,
                       activation=ActiMode.AC_MODE_RELU,
                       name=f"moe_expert_{i}")
            for i, g in enumerate(grouped)
        ]
        return self.aggregate(topk_values, topk_assign, topk_assign, gate,
                              exp_preds, num_exp, lambda_bal)

    def experts(self, dispatched: Tensor, out_dim: int,
                activation=ActiMode.AC_MODE_RELU, use_bias: bool = True,
                name=None) -> Tensor:
        """Batched expert FFN over a stacked (n, cap, d) dispatch (TPU-native
        form of the reference's per-expert dense nodes; see ops/moe_ops.py
        ExpertsOp). Expert-parallel shardable over the expert dim."""
        n = dispatched.dims[0]
        return self._unary(OperatorType.OP_EXPERTS, dispatched,
                           {"n": n, "out_dim": out_dim,
                            "activation": activation, "use_bias": use_bias},
                           name)

    def moe_experts(self, input: Tensor, num_exp: int, num_select: int,
                    expert_hidden_size: int, alpha: float = 2.0,
                    lambda_bal: float = 0.04) -> Tensor:
        """MoE layer through the batched Experts op: gate dense -> softmax ->
        top_k -> stacked group_by -> Experts (one bmm) -> aggregate. Same
        semantics as ``moe`` (reference src/ops/moe.cc:20-45) but
        expert-parallel-searchable: the Unity search can shard the expert
        dim (EP), which XLA lowers to a token all-to-all over ICI."""
        gate = self.dense(input, num_exp, name="moe_gate")
        gate = self.softmax(gate)
        topk_out = self.top_k(gate, num_select)
        topk_values, topk_assign = topk_out[0], topk_out[1]
        grouped = self._add_layer(
            OperatorType.OP_GROUP_BY, [input, topk_assign],
            {"n": num_exp, "alpha": alpha, "stacked": True},
            input.dtype, "moe_group_by")
        exp_out = self.experts(grouped, expert_hidden_size,
                               name="moe_experts")
        return self.aggregate(topk_values, topk_assign, topk_assign, gate,
                              [exp_out], num_exp, lambda_bal)

    def routed_experts(self, input: Tensor, num_experts: int, k: int,
                       intermediate: int, held=None, route_norm: bool = True,
                       route_scale: float = 1.0, kernel_initializer=None,
                       name: str = "moe",
                       selection_bias: bool = True,
                       limit: Optional[float] = None) -> Tensor:
        """The dropless routed expert layer (ops/moe_ops.py): router
        (sigmoid scores) -> dispatch by a stable sort on expert id -> grouped products over the
        experts held here -> combine; no token is dropped, whatever the
        routing. ``held=(first, count)`` names the experts of
        ``num_experts`` this device holds (default: all); the router ranks
        all of them and the output is the held experts' partial sum. The
        four nodes are ``<name>router``, ``<name>dispatch``,
        ``<name>experts`` and ``<name>combine``. ``selection_bias=False``
        is a router with no ``expert_bias`` buffer: the 8 largest scores
        are the choice. ``limit``: the experts' gated MLP clamped as
        ``gated_mlp``'s."""
        held = tuple(held) if held is not None else (0, num_experts)
        if not (0 <= held[0] and held[1] >= 1
                and held[0] + held[1] <= num_experts):
            raise ValueError(f"routed_experts: held={held} is no range of "
                             f"{num_experts} experts")
        ids = {"num_experts": num_experts, "held": held}
        weights, chosen = self._add_layer(
            OperatorType.OP_MOE_ROUTER, [input],
            dict(ids, k=k, route_norm=route_norm,
                 route_scale=route_scale,
                 kernel_initializer=kernel_initializer,
                 **({} if selection_bias else {"selection_bias": False})),
            input.dtype, f"{name}router")
        rows, sizes, order = self._add_layer(
            OperatorType.OP_MOE_DISPATCH, [input, chosen], dict(ids),
            input.dtype, f"{name}dispatch")
        out = self._add_layer(
            OperatorType.OP_MOE_ROUTED_EXPERTS, [rows, sizes],
            dict(ids, intermediate=intermediate,
                 kernel_initializer=kernel_initializer,
                 **({"limit": float(limit)} if limit else {})),
            input.dtype, f"{name}experts")
        return self._add_layer(
            OperatorType.OP_MOE_COMBINE, [out, order, weights, chosen],
            dict(ids), input.dtype, f"{name}combine")

    # ======================================================== observability ==
    def routing_stats(self) -> Dict[str, Any]:
        """The routed expert layers' counters over the last ``fit``, summed
        over its steps and fetched with its epoch metrics (no sync of their
        own): per dispatch node ``tokens_per_expert`` (one count per held
        expert), ``pairs_here`` and ``dropped`` (0: the layer drops none),
        ``bounded`` (the steps whose held pairs fit the layer's row bound and
        took the bounded path, ops/moe_ops.py) and ``steps``. Empty for a
        model with no routed layer."""
        return {k: dict(v) for k, v in
                (getattr(self, "_routing_stats", None) or {}).items()}

    def _fold_op_stats(self, stats) -> None:
        """Add one step's op counters (fetched with its metrics) to the
        fit's totals."""
        for name, counters in (stats or {}).items():
            total = self._routing_stats.setdefault(name, {"steps": 0})
            total["steps"] += 1
            for key, value in counters.items():
                value = np.asarray(value).astype(np.int64)
                total[key] = total[key] + value if key in total else value

    def _routing_digest(self) -> Dict[str, Any]:
        """``routing_stats`` as the ints and short strings a span carries
        (the ``epoch_fold`` span's arguments, docs/observability.md): over
        the fit so far, the pairs held here, the pairs dropped, the
        (layer, expert) counters, the most loaded expert's tokens over its
        layer's mean in thousandths (the largest over the layers), the
        held experts' tokens summed over the layers, and the layer-steps
        that took the bounded path beside the layer-steps in all."""
        layers = list(self._routing_stats.values())
        tokens = [layer["tokens_per_expert"] for layer in layers]
        return {
            "moe_pairs_here": int(sum(layer["pairs_here"]
                                      for layer in layers)),
            "moe_dropped": int(sum(layer["dropped"] for layer in layers)),
            "moe_expert_counters": int(sum(t.size for t in tokens)),
            "moe_load_max_permille": int(max(
                1000.0 * t.max() / max(t.mean(), 1e-9) for t in tokens)),
            "moe_tokens_per_expert": ",".join(
                str(int(v)) for v in np.sum(tokens, axis=0)),
            "moe_bounded_steps": int(sum(layer["bounded"]
                                         for layer in layers)),
            "moe_layer_steps": int(sum(layer["steps"] for layer in layers)),
        }

    def _obs_tracer(self):
        """The process tracer, auto-enabled the first time when the config
        asks for a trace file (obs stays a no-op singleton otherwise)."""
        from .obs import enable, get_tracer

        t = get_tracer()
        if not t.enabled and self.config.trace_file:
            t = enable(trace_file=self.config.trace_file)
        return t

    def get_telemetry(self):
        """StepTelemetry of the most recent fit() (None when observability
        was disabled for that run)."""
        return getattr(self, "_telemetry", None)

    def input_stats(self) -> Dict[str, Any]:
        """The input pipeline's counters over the most recent ``fit()``
        (always on, plain adds; reset per fit): ``wait_s`` the steps waited
        for their batch (``dataloader_wait``), ``batches`` handed over,
        ``gather_s`` / ``put_s`` the producer's time in the source iterator
        and in ``device_put`` (``batch_gather`` / ``batch_put``),
        ``copied_bytes`` that reached it as host copies (views count 0).
        wait_s near gather_s + put_s: the producer is the limit; else not."""
        from .data.dataloader import new_input_stats

        return dict(getattr(self, "_input_stats", None) or new_input_stats())

    def _make_telemetry(self, tracer, batch_size: int, phase: str):
        """A StepTelemetry when either sink wants one, else None — the
        None-ness is the hot loop's single instrumentation gate.
        ``_telemetry_requested`` is the in-process opt-in used by callers
        that consume get_telemetry() directly (keras TelemetryCallback).
        It is CONSUMED here (one fit per arm): if the requester dies before
        its cleanup hook, at most one later fit runs instrumented."""
        requested = getattr(self, "_telemetry_requested", False)
        if requested:
            self._telemetry_requested = False
        if not (self.config.telemetry_file or tracer.enabled or requested):
            return None
        from .obs.telemetry import (StepTelemetry, detect_peak_flops,
                                    model_flops_per_step)

        tel = StepTelemetry(batch_size=batch_size, phase=phase)
        try:
            if self.pcg is not None:
                tel.flops_per_step = model_flops_per_step(self.pcg)
        except Exception:
            pass
        peak = detect_peak_flops()  # per chip
        if peak is not None:
            # the step's model FLOPs cover the whole global batch, executed
            # across the chips the step actually runs on — MFU divides by
            # the EXECUTOR MESH's peak (a sub-mesh run must not be judged
            # against idle chips)
            if self.mesh is not None:
                n_chips = int(self.mesh.devices.size)
            else:
                import jax

                n_chips = len(jax.devices())
            peak *= max(n_chips, 1)
        tel.peak_flops = peak
        return tel

    # ============================================================== compile ==
    def compile(self, optimizer: Optional[Optimizer] = None,
                loss_type: LossType = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics: Optional[List[MetricsType]] = None,
                comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
                strategy=None, strategy_fn=None,
                final_tensor: Optional[Tensor] = None) -> None:
        """Traced wrapper over :meth:`_compile_impl` — the whole lowering
        pipeline (PCG build, strategy search, executor + param init) lands as
        one "compile" set-up span (``obs.setup_span``: the profiler's trace,
        the Chrome tracer's, ``obs.setup_walls()``, and the ``phase`` of the
        programs built inside it), its parts as spans of their own inside it.
        The explicit signature is kept
        in sync with ``_compile_impl`` (it IS the public API surface the
        frontends introspect)."""
        tracer = self._obs_tracer()
        with setup_span("compile", tracer=tracer, layers=len(self._layers)):
            self._compile_impl(optimizer, loss_type, metrics, comp_mode,
                               strategy, strategy_fn, final_tensor)
        if tracer.enabled and self.config.trace_file:
            # flush after each top-level phase so compile-only sessions
            # (and crashes later on) still leave a loadable trace
            tracer.write(self.config.trace_file)

    def _compile_impl(self, optimizer: Optional[Optimizer] = None,
                      loss_type: LossType = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                      metrics: Optional[List[MetricsType]] = None,
                      comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
                      strategy=None, strategy_fn=None,
                      final_tensor: Optional[Tensor] = None) -> None:
        """Lower the Layer graph to a PCG, pick a strategy, build the executor
        (reference pipeline: src/runtime/model.cc:2803, SURVEY §3.3).

        final_tensor: anchor the loss/outputs to this tensor instead of the
        graph sink (needed for multi-output frontends, e.g. HF ModelOutput
        dicts where last_hidden_state is not a sink)."""
        from .execution.executor import Executor
        from .parallel.mesh import build_mesh, mesh_for_strategy
        from .parallel.pcg import PCG
        from .parallel.strategy import Strategy, data_parallel_strategy
        from .ops.base import op_class_for
        from .resilience.preflight import (preflight_config,
                                           preflight_strategy)
        from .utils.compile_cache import ensure_compile_cache

        ensure_compile_cache()
        tracer = self._obs_tracer()

        # flag-combination sanity before any expensive work (ISSUE 5;
        # parse-time single-flag checks live in FFConfig.parse_args, this
        # covers programmatic attribute assignment too)
        preflight_config(self.config)
        if optimizer is not None:
            self.optimizer = optimizer
        if self.optimizer is None:
            self.optimizer = SGDOptimizer(self)
        self.loss_type = loss_type
        self.metrics_obj = Metrics(loss_type, metrics or [])
        # each compile decides afresh whether the export slot was consumed
        # by a --search-num-* target-machine strategy
        self._exported_search_target = False
        # ranked fallback candidates from the previous search do not carry
        # over: _run_search repopulates them when this compile searches
        self._search_result = None
        self._strategy_candidates = []

        with setup_span("compile_graph", tracer=tracer):
            # -- create_operators_from_layers (model.cc:2785) -------------------
            pcg = self.create_pcg()

            # final op = last compute node (the reference uses the graph's
            # sink)
            if final_tensor is not None:
                final = pcg.nodes[self._tensor_to_node[final_tensor.guid]]
                self.final_out_idx = final_tensor.owner_idx or 0
            else:
                sinks = [n for n in pcg.sinks()
                         if n.op.op_type != OperatorType.OP_INPUT]
                final = sinks[-1]
                self.final_out_idx = 0
            self.final_guid = final.guid
            repl_labels = final.op.op_type == OperatorType.OP_AGG_SPEC

        # -- mesh + strategy ----------------------------------------------------
        import jax

        if self.config.debug_nans:
            jax.config.update("jax_debug_nans", True)
        devices = jax.devices()
        n_dev = len(devices)
        # elastic restart (resilience/elastic.py): a degraded-topology
        # restore re-plans for the SURVIVING device count, which may be a
        # strict subset of what this host still enumerates
        elastic_n = getattr(self, "_elastic_n_dev", None)
        if elastic_n:
            n_dev = min(int(elastic_n), n_dev)
        if strategy_fn is not None:
            strategy = strategy_fn(pcg)
        if strategy is not None:
            # explicit strategy (hand-written or search output) — the
            # untrusted input: preflight BEFORE building the mesh so an
            # indivisible plan dies with an actionable error, not a
            # mesh-construction assert or an XLA sharding failure
            preflight_strategy(pcg, strategy, n_dev=n_dev,
                               batch_size=self.config.batch_size)
            self.strategy = strategy
            self.mesh = mesh_for_strategy(self.config, strategy)
        elif self.config.import_strategy_file:
            with open(self.config.import_strategy_file) as f:
                self.strategy = Strategy.from_json(f.read(), pcg)
            preflight_strategy(pcg, self.strategy, n_dev=n_dev,
                               batch_size=self.config.batch_size)
            self.mesh = mesh_for_strategy(self.config, self.strategy)
        elif self.config.only_data_parallel or (
                n_dev == 1 and not (self.config.search_num_nodes > 0
                                    or self.config.search_num_workers > 0)):
            # --search-num-* must still reach _run_search on a 1-device host:
            # exporting a strategy for a bigger target machine from a small
            # one is the flags' whole workflow (graph.cc:1892-1897)
            if self.config.mesh_shape:
                # honor an explicit user mesh: batch shards over the first axis
                self.mesh = build_mesh(self.config)
                axes = tuple(self.mesh.axis_names)
                self.strategy = data_parallel_strategy(
                    pcg, int(self.mesh.shape[axes[0]]), axis_names=axes)
            else:
                self.strategy = data_parallel_strategy(pcg, n_dev)
                self.mesh = build_mesh(self.config, mesh_shape=(n_dev,),
                                       axis_names=("data",))
        else:
            # Unity search (SURVEY §7 stage 5); falls back to DP if the
            # search finds nothing better
            self.strategy = self._run_search(pcg, n_dev)
            self.mesh = mesh_for_strategy(self.config, self.strategy)

        with setup_span("compile_graph", tracer=tracer):
            # --static-analysis strict: ShardLint judges EVERY compiled plan
            # (explicit, imported, or searched) before the executor exists —
            # the compile-time analog of cascade stage 0 (ISSUE 7). The
            # default "on" runs analysis only where it replaces dynamic work
            # (cascade, search pruning, pre-serve), keeping plain compiles at
            # zero added cost.
            if (getattr(self.config, "static_analysis", "on") or "on") == \
                    "strict" and self.strategy is not None:
                from .analysis import StaticAnalysisError, analyze_model

                # the SAME full pass the cascade's stage 0 runs (remat plan
                # resolved, donation contract included) — one entry point, so
                # the two paths cannot drift; pcg is passed explicitly
                # because self.pcg binds later in compile
                report = analyze_model(self, pcg=pcg)
                if report.errors:
                    raise StaticAnalysisError(
                        report, context="compile under --static-analysis "
                        "strict")

            if self.config.export_strategy_file and \
                    not getattr(self, "_exported_search_target", False):
                with open(self.config.export_strategy_file, "w") as f:
                    f.write(self.strategy.to_json(pcg))
            if self.config.export_strategy_computation_graph_file:
                with open(self.config.export_strategy_computation_graph_file,
                          "w") as f:
                    f.write(pcg.to_dot(
                        include_costs=self.config.include_costs_dot_graph))

            # -- fusion (model.cc:2965-3040, gated by --fusion) -----------------
            if self.config.perform_fusion:
                from .ops.fused import apply_fusion

                pcg, n_fused, fusion_remap = apply_fusion(
                    pcg, self.strategy, barrier_guids=(self.final_guid,))
                if n_fused:
                    if final_tensor is not None:
                        # the barrier guarantees the anchor is unfused or a
                        # region tail; follow the remap either way
                        new_guid, new_idx = fusion_remap[self.final_guid]
                        self.final_guid = new_guid
                        if new_idx >= 0:
                            self.final_out_idx = new_idx
                        final = pcg.nodes[self.final_guid]
                    else:
                        sinks = [n for n in pcg.sinks()
                                 if n.op.op_type != OperatorType.OP_INPUT]
                        final = sinks[-1]
                        self.final_guid = final.guid
                        self.final_out_idx = 0
                    repl_labels = final.op.op_type == OperatorType.OP_AGG_SPEC

            # -- label tensor (model.cc:3090-3124) ------------------------------
            out_shape = final.out_shapes[self.final_out_idx]
            if loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
                label_shape = (out_shape[0], 1)
                label_dtype = DataType.DT_INT32
            else:
                label_shape = out_shape
                label_dtype = final.out_dtypes[self.final_out_idx]
            self.label_tensor = Tensor(label_shape, label_dtype, name="label",
                                       model=self)

        self.pcg = pcg
        with setup_span("compile_executor", tracer=tracer):
            self.executor = Executor(
                pcg, self.mesh, self.strategy, loss_type, self.metrics_obj,
                self.optimizer, self.config, self.final_guid, label_dtype,
                repl_labels, final_out_idx=self.final_out_idx)
        # ends where they return: the device may still be filling the leaves
        with setup_span("param_init", tracer=tracer):
            self.params = self.executor.init_params(self.config.numpy_seed())
            self.opt_state = self.optimizer.init_state(self.params)

        # searched GPipe pipeline: training routes through PipelineTrainer
        # on a (pp, dp) grid seeded with the SAME initialized params; fit
        # copies the trained weights back so eval/predict/checkpoint see
        # them (reference: OP_PIPELINE is enum-only — this is beyond parity)
        self._pipeline_trainer = None
        if getattr(self.strategy, "pipeline", None):
            from .execution.remat import resolve_stage_remat
            from .parallel.pipeline import PipelineTrainer, resolve_schedule

            pp, pdp, n_micro = self.strategy.pipeline
            # schedule: --schedule flag > searched strategy.schedule >
            # classic gpipe (parallel.pipeline.resolve_schedule)
            sched, v = resolve_schedule(self.config, self.strategy)
            with setup_span("compile_executor", tracer=tracer):
                self._pipeline_trainer = PipelineTrainer(
                    self, pp=pp, dp=pdp, n_micro=n_micro,
                    optimizer=self.optimizer, loss_type=loss_type,
                    init_params=False,  # fit() seeds from the live params
                    # stage remat: --remat flag > searched level > GPipe full
                    remat=resolve_stage_remat(self.config, self.strategy),
                    schedule=sched, virtual_stages=v)

    def create_pcg(self):
        """Layer graph -> PCG (reference: create_operators_from_layers,
        src/runtime/model.cc:2785). Usable standalone for search experiments
        without allocating parameters."""
        from .parallel.pcg import PCG
        from .ops.base import op_class_for

        pcg = PCG()
        tensor_to_out: Dict[int, Tuple[int, int]] = {}
        for t in self._input_tensors:
            node = pcg.add_node(
                op_class_for(OperatorType.OP_INPUT)(
                    t.name, {"shape": t.dims, "dtype": t.dtype}, t.dtype, 0),
                [])
            tensor_to_out[t.guid] = (node.guid, 0)
            self._tensor_to_node[t.guid] = node.guid
        for layer in self._layers:
            op = op_class_for(layer.op_type)(
                layer.name, layer.attrs, layer.data_type,
                num_inputs=len(layer.inputs))
            inputs = [tensor_to_out[t.guid] for t in layer.inputs]
            node = pcg.add_node(op, inputs)
            self._layer_to_node[layer.guid] = node.guid
            for i, t in enumerate(layer.outputs):
                tensor_to_out[t.guid] = (node.guid, i)
                self._tensor_to_node[t.guid] = node.guid
        self.pcg = pcg
        return pcg

    def _run_search(self, pcg, n_dev):
        from .parallel.strategy import data_parallel_strategy
        from .search.unity import SearchResult, unity_search

        # --search-num-nodes/--search-num-workers: search for a TARGET
        # machine that may differ from the one we are running on (reference:
        # graph.cc:1892-1897 overrides numNodes/workersPerNode for the
        # search only — the export-strategy-for-a-bigger-machine workflow)
        n_search = n_dev
        if self.config.search_num_nodes > 0 or \
                self.config.search_num_workers > 0:
            nodes = (self.config.search_num_nodes
                     if self.config.search_num_nodes > 0
                     else self.config.num_nodes)
            workers = (self.config.search_num_workers
                       if self.config.search_num_workers > 0
                       else max(self.config.workers_per_node, 1))
            n_search = max(nodes * workers, 1)
        if n_search != n_dev:
            # searched strategy targets a different chip count: export it
            # (that is what the flags are for), then run data-parallel on
            # the machine we actually have. Without an export file the
            # search would burn its whole budget producing nothing — skip.
            if self.config.export_strategy_file:
                # multi-node target: the machine model carries the DCN
                # factor so the search prices inter-node collectives
                # (reference: EnhancedMachineModel, simulator.h:212-606)
                machine = None
                # unity_search only reads the file when version == 1, so a
                # file set under version 0 must not suppress the detected
                # multi-node model (it would silently drop the host factor)
                file_used = (self.config.machine_model_version == 1
                             and self.config.machine_model_file)
                if nodes > 1 and n_search % nodes == 0 and not file_used:
                    from .search.machine_model import TPUMachineModel

                    # num_hosts at construction so the per-slice torus
                    # invariant (prod == chips per slice) holds
                    machine = TPUMachineModel.detect(n_search,
                                                     num_hosts=nodes)
                target_pcg = pcg.copy()
                strat = unity_search(target_pcg, self.config, n_search,
                                     machine=machine,
                                     protected_guids=(self.final_guid,))
                with open(self.config.export_strategy_file, "w") as f:
                    f.write(strat.to_json(target_pcg))
                self._exported_search_target = True
            else:
                import warnings

                warnings.warn(
                    "--search-num-nodes/--search-num-workers target "
                    f"{n_search} devices but {n_dev} are available and no "
                    "--export-strategy file is set; skipping the target "
                    "search and running data-parallel")
            return data_parallel_strategy(pcg, n_dev)
        # the final (loss-anchored) node must survive graph rewrites so the
        # label tensor and executor anchor stay valid (the reference protects
        # its sink the same way via the output-shape contract).
        # _search_sim: an elastic restart hands the previous search's warm
        # Simulator in so the re-plan reuses its memoized delta-cost tables
        res = unity_search(pcg, self.config, n_dev,
                           protected_guids=(self.final_guid,),
                           return_result=True,
                           sim=getattr(self, "_search_sim", None))
        if isinstance(res, SearchResult):
            # ranked top-K fallback chain (ISSUE 5): kept on the model so
            # the strategy-safety cascade can degrade through runners-up
            # when the winner fails to compile / OOMs / fails the audit
            self._search_result = res
            self._strategy_candidates = list(res.ranked)
            # warm search simulator (ISSUE 8): the drift sentinel's closed
            # loop repairs THIS ruler (selective delta-cost invalidation)
            # and an elastic restart reuses its memoized tables. A new
            # search ruler obsoletes any cached sentinel sim/history from
            # an earlier compile — the loop must repair the sim that
            # ranked the LIVE plan, not a predecessor's
            self._search_sim = res.sim
            self._calibration_sim = None
            self._drift_sentinel = None
            return res.strategy
        return res  # search found nothing: plain data-parallel Strategy

    # ============================================================ training ==
    def _next_rng(self):
        import jax

        self._rng_counter += 1
        return jax.random.PRNGKey(
            self.config.numpy_seed() * 100003 + self._rng_counter)

    def _as_input_list(self, x) -> List[np.ndarray]:
        if isinstance(x, (list, tuple)):
            return [np.asarray(a) for a in x]
        return [np.asarray(x)]

    def _prep_label(self, y: np.ndarray) -> np.ndarray:
        y = np.asarray(y)
        if self.loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
            if y.ndim >= 2 and int(np.prod(y.shape[1:])) > 1:
                return y.astype(np.int32)  # token-level targets (causal LM)
            y = y.reshape(y.shape[0], 1).astype(np.int32)
        return y

    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, callbacks=None,
            recompile_state=None, shuffle: bool = True,
            chaos=None) -> PerfMetrics:
        """Training loop (reference: flexflow_cffi.py:2058-2100 — per batch:
        next_batch -> forward -> zero_gradients -> backward -> update inside a
        Legion trace; here one fused jitted step per batch). ``x`` and ``y``
        are READ IN PLACE while fit runs: with ``shuffle=False`` a batch is a
        view of the caller's array until its transfer ends, never a host copy.

        CacheOps in the graph are threaded as a device-side cache pytree;
        their ``score_fn`` runs host-side every ``num_batches`` steps; the
        scores land in ``self.cache_scores`` (the MoE cache/recompile signal;
        reference: cache.cc:291 + moe.cc:180,204). ``recompile_state`` hooks
        the dynamic recompile check (recompile_on_condition, model.cc:2422).

        Fault tolerance (ISSUE 4, docs/fault_tolerance.md): when the config
        asks for it (``--checkpoint-dir``/``--checkpoint-every``/
        ``--resume``/``--max-bad-steps``) a ResilienceSession wraps the
        loop — periodic async atomic checkpoints, SIGTERM/SIGINT preemption
        flush, exact resume of the data-pipeline cursor, and the divergence
        sentinel that skips non-finite steps and rolls back to the last
        committed checkpoint. ``chaos`` takes a ``resilience.ChaosPlan`` for
        deterministic fault injection (tests). SPMD path only: the GPipe
        pipeline trainer checkpoints only via explicit ``save_checkpoint``."""
        import jax

        assert self.executor is not None, "call compile() first"
        built_from = build_mark()  # what this fit builds: obs/builds.py
        if recompile_state is not None:
            self._recompile_state = recompile_state
            recompile_state.ffmodel = self
        xs = self._as_input_list(x)
        y = self._prep_label(y)
        batch_size = batch_size or self.config.batch_size
        epochs = epochs or self.config.epochs
        from .resilience.preflight import validate_batch

        # fail on a mis-shaped/mis-typed batch HERE, naming the tensor and
        # axis — not as a cryptic XLA error mid-epoch (ISSUE 5 satellite)
        validate_batch(self, xs, y, phase="fit")
        if self._pipeline_trainer is not None:
            if chaos is not None:
                raise ValueError(
                    "chaos injection targets the SPMD fit loop; the GPipe "
                    "pipeline trainer is not covered (see "
                    "docs/fault_tolerance.md)")
            enter("fit")
            try:
                return self._fit_pipeline(xs, y, batch_size, epochs,
                                          shuffle)
            finally:
                leave("fit")
        # strategy-safety cascade (ISSUE 5, docs/strategy_safety.md): when
        # armed (--audit-strategy / --memory-budget-mb / strategy chaos),
        # verify the plan BEFORE the loop — preflight, compile + one probe
        # step, memory budget, parallel-correctness audit — degrading
        # through the search's ranked candidates on failure. May swap
        # self.executor/strategy, so it runs before anything binds them.
        from .resilience.fallback import StrategyCascade

        cascade = StrategyCascade.maybe_create(self, chaos)
        self._last_cascade = cascade
        if cascade is not None:
            cascade.preverify(xs, y, batch_size)
        from .resilience.session import ResilienceSession

        session = None
        if ResilienceSession.wanted(self.config, chaos):
            session = ResilienceSession(self, chaos=chaos)
            session.install_signal_handlers()
        guard = session.guard if session is not None else None
        # guarded mode dispatches through `guard` (which owns its jitted
        # variant); step_fn is the unguarded path's handle only
        step_fn = (None if guard is not None
                   else self.executor.make_train_step())
        from .data.dataloader import (batch_iterator, new_input_stats,
                                      prefetch_iterator)
        from .obs.trace import span, step_span

        self._input_stats = new_input_stats()
        self._routing_stats: Dict[str, Dict[str, Any]] = {}
        in_shardings = [self.executor.batch_sharding(a.ndim) for a in xs]
        label_sharding = self.executor.batch_sharding(y.ndim)

        self._perf = PerfMetrics()
        num_samples = xs[0].shape[0]
        steps_per_epoch = num_samples // batch_size
        epoch0, skip_batches = 0, 0
        step_count = 0
        executed_steps = 0  # actual dispatches: THROUGHPUT must not count
        # steps a preemption/resume skipped (step_count can also rewind on
        # rollback; replayed steps were genuinely executed and do count)
        self._preempted_at_step = None
        if session is not None:
            resumed = session.maybe_resume()
            if resumed is not None:
                step_count, epoch0, skip_batches = resumed
                if steps_per_epoch and skip_batches >= steps_per_epoch:
                    epoch0 += skip_batches // steps_per_epoch
                    skip_batches %= steps_per_epoch
        t0 = time.time()
        loss_val = None
        cache = (self.executor.init_cache()
                 if self.executor.cache_nodes else None)
        # observability: with both sinks off, `telemetry` is None and the hot
        # loop pays two `if x is not None` tests per step — no allocations,
        # no file I/O, no device syncs beyond the pre-existing ones
        tracer = self._obs_tracer()
        telemetry = self._make_telemetry(tracer, batch_size, "train")
        self._telemetry = telemetry
        if telemetry is not None:
            telemetry.build_mark = built_from
        if cascade is not None:
            # counters are final after preverify; the final strategy the
            # cascade settled on lands in the telemetry record
            cascade.merge_telemetry(telemetry)
        last_batch = None
        if self.config.profiling:
            self.profile_operators()
            t0 = time.time()  # per-op measurement must not skew THROUGHPUT
        # closed-loop calibration (ISSUE 8, docs/calibration.md): with
        # --profile-ops, ONE ProfiledStep pass per fit times every distinct
        # op shape on device, streams OpRecords to the JSONL profile +
        # tracer, feeds the drift sentinel, and (with --auto-recalibrate)
        # repairs the simulator's per-key calibration in place. A plain fit
        # pays one getattr.
        from .obs.drift import CalibrationLoop

        calib = CalibrationLoop.maybe_create(self)
        if calib is not None:
            calib.run_pass(xs, batch_size, telemetry, step=step_count)
            t0 = time.time()  # profiled pass must not skew THROUGHPUT
        # Legion Prof analog (-lg:prof_logfile): XLA trace of the whole loop,
        # viewable in TensorBoard/Perfetto (SURVEY §5 tracing subsystem)
        tracing = bool(self.config.profiler_trace_dir)
        if tracing:
            jax.profiler.start_trace(self.config.profiler_trace_dir)
        # the phase of the programs built from here to fit's end; no wrapper
        # around fit() sets it: a frame above the step costs build time
        # (obs/builds.py: enter)
        enter("fit")
        try:
            epoch = epoch0
            preempted = False
            while epoch < epochs:
                with span("epoch", tracer=tracer, index=epoch) as ep:
                    # shuffled epochs by default (the reference's loaders shuffle);
                    # the shuffled path stages batches through the native C++
                    # double-buffered BatchPipeline (data/dataloader.py).
                    # start_batch replays an interrupted epoch's tail: the same
                    # seed reproduces the shuffle, the cursor skips what the
                    # restored checkpoint already consumed
                    with span("fit_epoch_setup", tracer=tracer):
                        it = batch_iterator(xs + [y], batch_size, shuffle=shuffle,
                                            seed=self.config.numpy_seed() + epoch,
                                            start_batch=skip_batches)
                        batch_in_epoch = skip_batches
                        skip_batches = 0
                        epoch_metrics = []  # device-side; folded at epoch end
                        recompiled = False
                        rolled_back = False
                    for batch in prefetch_iterator(
                            it, in_shardings + [label_sharding],
                            stats=self._input_stats):
                        bx, by = batch[:-1], batch[-1]
                        if session is not None and session.chaos is not None:
                            bx = session.chaos.poison_batch(step_count, bx)
                            session.chaos.maybe_preempt(step_count)
                        if telemetry is not None:
                            t_step = time.perf_counter()
                        step_ok = True
                        with step_span("train_step", step_count,
                                       tracer=tracer) as step_sp:
                            if guard is not None:
                                rng = self._next_rng()
                                if cache is not None:
                                    outs, step_ok = guard(
                                        self.params, self.opt_state, bx, by, rng,
                                        cache)
                                    (self.params, self.opt_state, loss_val, m,
                                     fresh) = outs
                                else:
                                    outs, step_ok = guard(
                                        self.params, self.opt_state, bx, by, rng)
                                    (self.params, self.opt_state, loss_val,
                                     m) = outs
                                    fresh = None
                            elif cache is not None:
                                (self.params, self.opt_state, loss_val, m,
                                 fresh) = step_fn(self.params, self.opt_state, bx,
                                                  by, self._next_rng(), cache)
                            else:
                                (self.params, self.opt_state, loss_val,
                                 m) = step_fn(self.params, self.opt_state, bx, by,
                                              self._next_rng())
                                fresh = None
                            if cache is not None and step_ok:
                                self._score_caches(cache, fresh, step_count)
                                cache.update(fresh)
                            step_count += 1
                            batch_in_epoch += 1
                            executed_steps += 1
                            if step_ok:
                                # a guarded bad step left params untouched; its
                                # NaN metrics must not poison the epoch fold
                                epoch_metrics.append(m)
                            loss_f = None
                            if telemetry is not None:
                                # observability is opt-in: the per-step sync it
                                # costs is what buys true step walls + the
                                # compile split; the sync sits inside the span
                                jax.block_until_ready(loss_val)
                                wall = time.perf_counter() - t_step
                                loss_f = float(loss_val) if step_ok else None
                                telemetry.record_step(wall, loss_f)
                                step_sp.set_metadata(
                                    step=step_count,
                                    **({} if loss_f is None else {"loss": loss_f}))
                                last_batch = (bx, by)
                        if not step_ok:
                            session.record_fault(step_count - 1)
                            if guard.should_rollback:
                                step_count, epoch, skip_batches = \
                                    session.rollback()
                                cache = (self.executor.init_cache()
                                         if self.executor.cache_nodes else None)
                                epoch_metrics = []  # poisoned partials discarded
                                rolled_back = True
                                break
                        if session is not None:
                            session.on_step(step_count, epoch, batch_in_epoch,
                                            steps_per_epoch)
                            if session.preempted:
                                # preemption grace window: flush a final
                                # committed checkpoint, then stop cleanly
                                self._preempted_at_step = step_count
                                session.note_preemption(step_count)
                                session.final_checkpoint(step_count, epoch,
                                                         batch_in_epoch,
                                                         steps_per_epoch)
                                preempted = True
                                break
                        if self._recompile_state is not None and \
                                self.recompile_on_condition(self._recompile_state):
                            # executor rebuilt: refresh the jitted step and cache,
                            # then RE-RUN this epoch on the new shardings (the break
                            # abandons the rest of its batches)
                            if guard is not None:
                                guard.executor = self.executor
                                guard.rebuild()
                            else:
                                step_fn = self.executor.make_train_step()
                            cache = (self.executor.init_cache()
                                     if self.executor.cache_nodes else None)
                            recompiled = True
                            break
                        if self.config.profiling and \
                                step_count % max(self.config.print_freq, 1) == 0:
                            # legacy stdout line, byte-identical to the pre-obs
                            # print so existing scripts keep parsing it
                            print(f"step {step_count}: loss="
                                  f"{float(loss_val) if loss_f is None else loss_f:.4f}")
                    # fold whatever the epoch produced (also the partial pre-recompile
                    # batches — their steps trained the old graph but still count);
                    # ONE host transfer for the whole epoch instead of a blocking
                    # int()/float() per scalar per step
                    if epoch_metrics:
                        with span("epoch_fold", tracer=tracer) as fold:
                            for m in jax.device_get(epoch_metrics):
                                self._perf.update(m)
                                self._fold_op_stats(m.get("op_stats"))
                            if self._routing_stats:
                                fold.set_metadata(**self._routing_digest())
                    if telemetry is not None and not (rolled_back or preempted
                                                      or recompiled):
                        loss_f = (float(loss_val) if loss_val is not None
                                  else None)
                        telemetry.record_epoch(loss_f)
                        if loss_f is not None:
                            ep.set_metadata(loss=loss_f)
                if rolled_back:
                    continue  # re-enter at the restored epoch/batch cursor
                if preempted:
                    break
                if recompiled:
                    in_shardings = [self.executor.batch_sharding(a.ndim)
                                    for a in xs]
                    label_sharding = self.executor.batch_sharding(y.ndim)
                    continue  # restart the SAME epoch
                if self.config.profiling:
                    print(f"epoch {epoch}: loss={float(loss_val):.4f}")
                epoch += 1
            if loss_val is not None:
                with span("fit_sync", tracer=tracer):
                    jax.block_until_ready(loss_val)
        except BaseException:
            leave("fit")
            raise
        finally:
            if tracing:
                jax.profiler.stop_trace()
            if session is not None:
                session.close(telemetry)
        try:  # still fit: the memory analysis below builds the step too
            elapsed = time.time() - t0
            self._last_fit_time = elapsed
            self._last_fit_samples = executed_steps * batch_size
            if elapsed > 0:
                throughput = self._last_fit_samples / elapsed
                if tracer.enabled:
                    tracer.counter("throughput_samples_per_sec",
                                   round(throughput, 2))
                if self.config.profiling:
                    # legacy stdout line (kept verbatim for script
                    # compatibility)
                    print(f"THROUGHPUT = {throughput:.2f} samples/s")
            if self.config.profiling:
                n_built, build_s, by_name = built_since(built_from)
                if n_built:  # a fit after the first that prints: it recompiled
                    print(f"BUILT = {n_built} programs in {build_s:.2f} s: "
                          f"{by_name}")
            if telemetry is not None:
                telemetry.finalize()
                if self.config.telemetry_file and last_batch is not None:
                    from .obs.telemetry import capture_memory_analysis

                    telemetry.device_memory = capture_memory_analysis(
                        self.executor, self.params, self.opt_state,
                        *last_batch)
                if self.config.telemetry_file:
                    telemetry.write(self.config.telemetry_file)
            if tracer.enabled and self.config.trace_file:
                tracer.write(self.config.trace_file)
        finally:
            leave("fit")
        return self._perf

    def _param_stamp(self):
        """Identity snapshot of the param arrays. Holds REFERENCES (not raw
        ids) so CPython id reuse after a free can never fake a match."""
        return {(ln, wn): a for ln, ws in self.params.items()
                for wn, a in ws.items()}

    def _params_match_stamp(self) -> bool:
        old = getattr(self, "_pipeline_param_stamp", None)
        if old is None:
            return False
        new = self._param_stamp()
        return old.keys() == new.keys() and \
            all(new[k] is old[k] for k in new)

    def _fit_pipeline(self, xs, y, batch_size, epochs, shuffle) -> PerfMetrics:
        """GPipe training loop for a searched pipeline strategy: batches go
        through PipelineTrainer.train_step; the trained stage params are
        copied back into the Executor's pytree afterwards so
        eval/predict/checkpoint operate on the trained weights."""
        import jax

        from .data.dataloader import batch_iterator
        from .obs.trace import span, step_span

        tr = self._pipeline_trainer
        # seed from the CURRENT executor params when they changed since the
        # last pipeline sync (post-compile weight edits: copy_torch_weights,
        # Layer.set_weights). Unchanged params keep the trainer's optimizer
        # state across fit() calls, like the SPMD path's opt_state.
        if tr.params is None or not self._params_match_stamp():
            tr.load_params(self.params)
        # the microbatch count was chosen for config.batch_size at search
        # time; re-derive it for the batch size actually passed
        if batch_size % tr.dp != 0:
            raise ValueError(
                f"pipeline strategy needs batch_size % dp == 0 "
                f"(batch {batch_size}, dp {tr.dp})")
        micro_ok = [m for m in (2 * tr.pp, tr.pp, 2, 1)
                    if batch_size % m == 0 and
                    (batch_size // m) % tr.dp == 0 and
                    # interleaved advances microbatches in rounds of pp
                    (tr.schedule != "interleaved" or m % tr.pp == 0)]
        if not micro_ok:
            raise ValueError(
                f"pipeline schedule {tr.schedule!r} found no microbatch "
                f"count for batch_size {batch_size} (pp={tr.pp}, "
                f"dp={tr.dp}); use a batch divisible by pp*dp")
        tr.n_micro = micro_ok[0]
        loss_key = {
            LossType.LOSS_CATEGORICAL_CROSSENTROPY: "cce_loss",
            LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
                "sparse_cce_loss",
            LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE: "mse_loss",
            LossType.LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE: "mse_loss",
        }.get(self.loss_type, "sparse_cce_loss")
        self._perf = PerfMetrics()
        tracer = self._obs_tracer()
        telemetry = self._make_telemetry(tracer, batch_size, "train_pipeline")
        self._telemetry = telemetry
        t0 = time.time()
        step = 0
        loss = None
        for epoch in range(epochs):
            it = batch_iterator(xs + [y], batch_size, shuffle=shuffle,
                                seed=self.config.numpy_seed() + epoch)
            with span("epoch", tracer=tracer, index=epoch):
                for batch in it:
                    bx, by = batch[:-1], batch[-1]
                    t_step = time.perf_counter()
                    with step_span("train_step", step,
                                   tracer=tracer) as step_sp:
                        loss = tr.train_step(list(bx), by, rng_seed=step)
                        step += 1
                        # loss-only metrics: train_step returns the scalar
                        # loss (accuracy-style metrics need the eval path)
                        loss_f = float(loss)
                        if telemetry is not None:
                            wall = time.perf_counter() - t_step
                            telemetry.record_step(wall, loss_f)
                            step_sp.set_metadata(step=step, loss=loss_f)
                    self._perf.update({
                        "train_all": by.shape[0],
                        loss_key: loss_f * by.shape[0]})
                    if self.config.profiling and \
                            step % max(self.config.print_freq, 1) == 0:
                        print(f"step {step}: loss={loss_f:.4f}")
                if telemetry is not None:
                    telemetry.record_epoch(float(loss) if loss is not None
                                           else None)
        for lname, ws in tr.export_params().items():
            for wname, arr in ws.items():
                cur = self.params[lname][wname]
                self.params[lname][wname] = jax.device_put(
                    np.asarray(arr, dtype=np.asarray(cur).dtype),
                    cur.sharding if hasattr(cur, "sharding") else None)
        # record the sync point: a following fit() without external weight
        # edits reuses the trainer's params AND optimizer state
        self._pipeline_param_stamp = self._param_stamp()
        self._last_fit_time = time.time() - t0
        self._last_fit_samples = step * batch_size
        if self._last_fit_time > 0:
            throughput = self._last_fit_samples / self._last_fit_time
            if tracer.enabled:
                tracer.counter("throughput_samples_per_sec",
                               round(throughput, 2))
            if self.config.profiling:
                print(f"THROUGHPUT = {throughput:.2f} samples/s")
        if telemetry is not None:
            telemetry.finalize()
            if self.config.telemetry_file:
                telemetry.write(self.config.telemetry_file)
        if tracer.enabled and self.config.trace_file:
            tracer.write(self.config.trace_file)
        return self._perf

    def eval(self, x=None, y=None, batch_size: Optional[int] = None
             ) -> PerfMetrics:
        """reference: flexflow_cffi.py:2102."""
        import jax

        xs = self._as_input_list(x)
        y = self._prep_label(y)
        batch_size = batch_size or self.config.batch_size
        from .resilience.preflight import validate_batch

        validate_batch(self, xs, y, phase="eval")
        estep = self.executor.make_eval_step()
        from .data.dataloader import batch_iterator

        tracer = self._obs_tracer()
        perf = PerfMetrics()
        t_eval = time.perf_counter()
        n_batches = 0
        loss_val = None
        enter("eval")
        try:
            for batch in batch_iterator(xs + [y], batch_size,
                                        drop_remainder=False):
                bx, by = batch[:-1], batch[-1]
                loss_val, m = estep(self.params, bx, by)
                # one host transfer per batch instead of one per metric
                # scalar
                perf.update(jax.device_get(m))
                n_batches += 1
        finally:
            leave("eval")
        if tracer.enabled:
            tracer.complete("eval", time.perf_counter() - t_eval,
                            batches=n_batches,
                            loss=(float(loss_val) if loss_val is not None
                                  else None))
            if self.config.trace_file:
                # eval-only / inference workloads must still get their
                # trace file — fit() is not the only exit point
                tracer.write(self.config.trace_file)
        return perf

    def predict(self, x, batch_size: Optional[int] = None) -> np.ndarray:
        """Batched inference forward (ISSUE 6 satellite). Two hot-path
        fixes over the per-batch loop this replaces: the final non-full
        batch from ``batch_iterator(drop_remainder=False)`` is PADDED to
        the full batch size (repeating the last row) and trimmed
        host-side — one jit specialization instead of a second compile for
        the tail shape — and results stay on device until ONE
        ``jax.device_get`` at the end instead of an ``np.asarray`` device
        sync per batch (the same batching PerfMetrics got in PR 1)."""
        import jax

        xs = self._as_input_list(x)
        batch_size = batch_size or self.config.batch_size
        from .resilience.preflight import validate_batch

        validate_batch(self, xs, None, phase="predict")
        fwd = self.executor.make_forward()
        from .data.dataloader import batch_iterator

        # static rows-per-sample of the final output (nmt-style graphs
        # flatten (b, t) -> b*t rows; trimming must drop whole samples)
        final = self.pcg.nodes[self.final_guid]
        out_rows = final.out_shapes[self.executor.final_out_idx][0]
        in_rows = self.pcg.input_nodes()[0].out_shapes[0][0]
        per_sample = out_rows // in_rows if in_rows and \
            out_rows % in_rows == 0 else None
        outs = []
        tail_rows = None
        for batch in batch_iterator(xs, batch_size, drop_remainder=False):
            nb = batch[0].shape[0]
            if nb < batch_size:
                if per_sample is None:
                    # output rows don't divide per sample: a padded batch
                    # could not be trimmed — pay the tail recompile
                    outs.append(fwd(self.params, batch))
                    continue
                pad = batch_size - nb
                batch = [np.concatenate([a, np.repeat(a[-1:], pad, axis=0)],
                                        axis=0) for a in batch]
                tail_rows = nb
            outs.append(fwd(self.params, batch))
        host = [np.asarray(o) for o in jax.device_get(outs)]
        if tail_rows is not None:
            host[-1] = host[-1][:tail_rows * per_sample]
        return np.concatenate(host, axis=0)

    def generate(self, prompts, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 max_inflight: Optional[int] = None,
                 max_decode_len: Optional[int] = None) -> List[List[int]]:
        """Autoregressive generation through the serving engine (ISSUE 6,
        docs/serving.md): prefill/decode split with a KV-cache pytree and
        continuous batching over ``--max-inflight`` decode slots. Greedy
        when ``temperature <= 0``; otherwise top-k filtered sampling (the
        Pallas top-k kernel where eligible). ``prompts`` is a list of
        token-id sequences; returns the generated continuations in
        submission order. The engine (and its compiled prefill/decode
        steps) is cached on the model across calls."""
        from .serving.engine import ServingEngine

        eng = getattr(self, "_serving_engine", None)
        if eng is None or eng.executor is not self.executor or \
                (max_inflight and eng.n_slots != max_inflight) or \
                (max_decode_len and
                 eng.requested_max_decode_len != max_decode_len):
            # eos_id stays per-call (threaded below), never baked into the
            # cached engine — a prior call's EOS must not truncate later
            # calls that didn't ask for one
            eng = ServingEngine(self, n_slots=max_inflight,
                                max_decode_len=max_decode_len)
            self._serving_engine = eng
        return eng.generate(prompts, max_new_tokens=max_new_tokens,
                            temperature=temperature, top_k=top_k,
                            eos_id=eos_id, seed=seed)

    # ---- manual-loop API parity (model.cc:2415-2469) --------------------------
    def init_operators(self) -> None:
        pass  # op state is created lazily by jit; kept for API parity

    def init_layers(self) -> None:
        pass  # reference name (flexflow_cffi.py init_layers); same no-op

    def forward(self, seq_length: Optional[int] = None) -> None:
        self._ensure_staged_batch()
        assert self._staged.get("batch") is not None, \
            "bind a batch first via next_batch/set_batch/set_tensor"
        fwd = self.executor.make_forward()
        xs, _ = self._staged["batch"]
        self._staged["logits"] = fwd(self.params, xs)

    def zero_gradients(self) -> None:
        self._staged.pop("grads", None)

    def backward(self, seq_length: Optional[int] = None) -> None:
        self._ensure_staged_batch()
        assert self._staged.get("batch") is not None, \
            "bind a batch first via next_batch/set_batch/set_tensor"
        if self._staged.get("label_placeholder"):
            raise RuntimeError(
                "backward() needs a real label batch: stage one via "
                "label_tensor.set_tensor(...) or set_batch(x, y) — refusing "
                "to train against the zero placeholder")
        import jax

        xs, y = self._staged["batch"]

        from .ops.base import OpContext

        def loss_fn(params):
            fwdvals = self.executor.forward_outputs(
                params, self.executor._bind_inputs(xs),
                OpContext(training=True, rng=self._next_rng(), mesh=self.mesh))
            logits = fwdvals[self.final_guid][self.executor.final_out_idx]
            return loss_value(self.loss_type, logits, y,
                              self.executor.repl_labels)

        self._staged["loss"], self._staged["grads"] = jax.value_and_grad(
            loss_fn)(self.params)

    def update(self) -> None:
        grads = self._staged.get("grads")
        assert grads is not None, "call backward() first"
        self.params, self.opt_state = self.optimizer.update(
            self.params, grads, self.opt_state)

    def set_batch(self, x, y) -> None:
        import jax

        xs = [jax.device_put(np.asarray(a)) for a in self._as_input_list(x)]
        self._staged["batch"] = (xs, jax.device_put(self._prep_label(y)))
        self._staged["label_placeholder"] = False  # y is a real label

    def _stage_tensor_value(self, tensor, np_array) -> None:
        """Tensor.set_tensor host staging (reference:
        ParallelTensorBase::set_tensor, parallel_tensor.cc:698). Staging only
        marks the batch dirty; composition + device_put happen lazily in the
        next forward/backward so the attach loop's set_tensor(input) +
        set_tensor(label) pair costs ONE host->device transfer per batch."""
        per = self._staged.setdefault("per_tensor", {})
        per[tensor.guid] = np.asarray(np_array)
        self._staged["per_tensor_dirty"] = True

    def _ensure_staged_batch(self) -> None:
        if not self._staged.get("per_tensor_dirty"):
            return
        per = self._staged.get("per_tensor", {})
        if not all(t.guid in per for t in self._input_tensors):
            return  # forward() will assert if nothing was ever bound
        xs = [per[t.guid] for t in self._input_tensors]
        placeholder = False
        if self.label_tensor is not None and self.label_tensor.guid in per:
            y = per[self.label_tensor.guid]
        elif self.label_tensor is not None:
            # forward-only staging: a zero placeholder keeps forward()
            # usable, but backward() refuses to train on it (below)
            y = np.zeros(self.label_tensor.dims,
                         dtype=dtype_to_jnp(self.label_tensor.dtype))
            placeholder = True
        else:
            return
        self.set_batch(xs, y)
        self._staged["label_placeholder"] = placeholder
        self._staged["per_tensor_dirty"] = False

    def _activation_value(self, tensor) -> np.ndarray:
        """get_tensor on an activation output: recompute forward on the
        staged batch and return that layer's output (reference analog:
        inline-mapping an output region, flexflow_cffi.py:601-658)."""
        from .ops.base import OpContext

        self._ensure_staged_batch()
        assert self._staged.get("batch") is not None, \
            f"bind a batch before reading activation {tensor.name}"
        xs, _ = self._staged["batch"]
        guid = self._tensor_to_node.get(tensor.guid)
        import jax

        # constant key: a read-only getter must not advance the training
        # rng stream (rng is unused under training=False anyway)
        vals = self.executor.forward_outputs(
            self.params, self.executor._bind_inputs(xs),
            OpContext(training=False, rng=jax.random.PRNGKey(0),
                      mesh=self.mesh))
        if guid not in vals:
            raise KeyError(
                f"{tensor.name}: its op was fused away; re-compile with "
                "--disable-fusion to inline-read intermediate activations")
        return np.asarray(vals[guid][tensor.owner_idx])

    def _staged_tensor_value(self, tensor) -> np.ndarray:
        per = self._staged.get("per_tensor", {})
        if tensor.guid in per:
            return np.asarray(per[tensor.guid])
        if self.label_tensor is not None and tensor is self.label_tensor:
            return np.zeros(self.label_tensor.dims,
                            dtype=dtype_to_jnp(self.label_tensor.dtype))
        raise KeyError(f"{tensor.name}: no value staged; call set_tensor")

    def reset_metrics(self) -> None:
        """reference: flexflow_cffi.py:1968."""
        self._perf = PerfMetrics()

    # ---- recompilation (reference: RecompileState, model.cc:2422) -------------
    def profile_operators(self, max_ops: int = 8) -> None:
        """Per-op timing printout behind ``--profiling`` (reference:
        FFConfig::profiling gating per-op kernel timing prints in every
        kernel wrapper, model.cc:110,155). The ``max_ops`` heaviest distinct
        op shapes (by analytical cost) are measured standalone via the
        simulator's microbench (the cudaEvent analog) and printed once —
        bounded because each measurement pays a jit compile."""
        if getattr(self, "_per_op_profiled", False) or self.pcg is None:
            return
        self._per_op_profiled = True
        from .search.machine_model import TPUMachineModel
        from .search.simulator import OpSharding, Simulator

        sim = Simulator(TPUMachineModel.detect(1))
        distinct = {}
        for node in self.pcg.compute_nodes():
            in_shapes = [self.pcg.nodes[g].out_shapes[i]
                         for g, i in node.inputs]
            key = sim._op_key(node, in_shapes)
            if key not in distinct:
                est = sim.op_cost(node, in_shapes, OpSharding()).forward_time
                distinct[key] = (est, node, in_shapes)
        heaviest = sorted(distinct.values(), key=lambda x: -x[0])[:max_ops]
        tracer = self._obs_tracer()
        # legacy stdout block kept verbatim; the same measurements also land
        # as machine-readable tracer events
        print("PER-OP PROFILE (fwd, measured standalone, "
              f"top {len(heaviest)} by estimated cost):")
        for _est, node, in_shapes in heaviest:
            try:
                t = sim.measure_operator_cost(node, in_shapes)
            except Exception:
                continue
            if tracer.enabled:
                tracer.event("per_op_profile", op=node.name,
                             op_type=node.op.op_type.name,
                             forward_us=round(t * 1e6, 1))
            print(f"  {node.name:24s} {node.op.op_type.name:28s} "
                  f"{t * 1e6:10.1f} us")

    def _score_caches(self, cache, fresh, step_count: int) -> None:
        """Host-side cache scoring (reference: cache.cc score tasks): every
        ``num_batches`` steps run each CacheOp's score_fn(cached, fresh)."""
        for node in self.executor.cache_nodes:
            nb = max(int(node.op.attrs.get("num_batches", 1) or 1), 1)
            if (step_count + 1) % nb:
                continue
            score_fn = node.op.attrs.get("score_fn")
            if score_fn is None:
                continue
            self.cache_scores[node.name] = float(score_fn(
                np.asarray(cache[node.name]), np.asarray(fresh[node.name])))

    def recompile_on_condition(self, recompile_state) -> bool:
        if recompile_state.trigger():
            recompile_state.alter(self)
            from .execution.recompile import recompile

            recompile(self)
            return True
        return False

    # ================================================== weights / dataloaders ==
    def create_data_loader(self, batch_tensor: Tensor, full_array: np.ndarray):
        from .data.dataloader import SingleDataLoader

        return SingleDataLoader(self, batch_tensor, full_array)

    def _locate_weight(self, tensor: Tensor) -> Tuple[str, str]:
        layer = tensor.owner_layer
        assert layer is not None and tensor.owner_idx < 0, \
            f"{tensor.name} is not a weight tensor"
        wname = tensor.name.split(".")[-1]
        return layer.name, wname

    def _get_weight_by_tensor(self, tensor: Tensor) -> np.ndarray:
        node_name, wname = self._locate_weight(tensor)
        return np.asarray(self.params[node_name][wname])

    def _set_weight_by_tensor(self, tensor: Tensor, arr: np.ndarray) -> None:
        import jax

        node_name, wname = self._locate_weight(tensor)
        cur = self.params[node_name][wname]
        arr = np.asarray(arr, dtype=np.asarray(cur).dtype)
        assert arr.shape == cur.shape, (arr.shape, cur.shape)
        self.params[node_name][wname] = jax.device_put(
            arr, cur.sharding if hasattr(cur, "sharding") else None)

    # ================================================================= misc ==
    def get_layers(self) -> Dict[int, Layer]:
        return {i: l for i, l in enumerate(self._layers)}

    def get_layer_by_id(self, layer_id: int) -> Layer:
        return self._layers[layer_id]

    def get_layer_by_name(self, name: str) -> Optional[Layer]:
        for l in self._layers:
            if l.name == name:
                return l
        return None

    def get_tensor_by_id(self, id: int) -> Tensor:
        """Weight tensors in declaration order (reference:
        flexflow_cffi.py:2179 — parameter id over the whole model)."""
        weights = [w for l in self._layers for w in l.weights]
        return weights[id]

    def get_perf_metrics(self) -> PerfMetrics:
        return self._perf

    def __repr__(self) -> str:
        return f"FFModel({len(self._layers)} layers)"
