"""A hybrid decoder of gated delta-rule layers with grouped key heads and one
latent-attention layer closing every period, sandwich norms with a
zero-centred gated gain, and a sigmoid-routed top-k expert layer with a
shared expert under a clamped SwiGLU: the ``gigachat3_5`` family
(GigaChat3.5-432B-A28B,
https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B), built for the serving
path.

    h0 = E[ids]
    layer i:  a = Mix_i(N1(h));   h <- h + N2(a)       Mix_i = MLA where i is in
              m = FFN_i(N3(h));   h <- h + N4(m)       full_attention_layers, else GDN
    logits = N_f(h) W_head                              (untied head)

``N``: ``x / rms(x) * 2 sigmoid(w)``, ``w`` stored about zero
(``ops.normalization.norm_gain``, form ``NORM_GAIN``). ``GDN``:
``FFModel.gated_delta_mixer`` with ``linear_num_key_heads`` key heads under
``linear_num_value_heads`` value heads of ``(linear_key_head_dim,
linear_value_head_dim)`` matrix state each, write strength in (0, 1), the
output gate ``2 sigmoid`` over a zero-centred RMS norm. ``MLA``:
``FFModel.latent_attention`` with YaRN frequencies, ``m^2`` on the softmax
scale, neighbour-paired rotary columns and a sigmoid gate a head on the
core's output. ``FFN``: a gated MLP of ``intermediate`` for the first
``num_dense_layers`` layers; after them a shared expert plus the dropless
routed layer (sigmoid scores in float32 over all ``num_experts``, the
``num_experts_per_tok`` largest, no groups, no selection bias, normalised
over the chosen, times ``route_scale``); every gated MLP clamped at
``swiglu_limit``.

What a request leaves in the engine is of TWO kinds in one graph: a latent
layer's ``kv_rank + rope_dim`` row a token in the paged pool, and a
delta-rule layer's matrix state and conv tails a slot.

``held_experts=(first, count)`` and ``vocab_size`` make the model ONE
DEVICE'S SHARE of an expert- and vocabulary-parallel deployment, as
models/pangu.py has it. The published model's two multi-token-prediction
blocks are further blocks after the last layer and are not built
(ROADMAP.md, Reach R4).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from ..ffconst import DataType
from ..model import FFModel

#: the block norm's gain form (``norm_type`` ZeroCenteredGatedNorm,
#: ``layernorm_gating_weight`` 2): see ``ops.normalization.norm_gain``
NORM_GAIN = "sigmoid2"


@dataclasses.dataclass
class GigaChatConfig:
    hidden: int
    num_layers: int
    full_attention_layers: Sequence[int]
    num_dense_layers: int
    # latent attention
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    rope_scaling: Optional[dict]
    # the delta-rule mixer
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    # the MLPs
    intermediate: int
    moe_intermediate: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    route_scale: float
    swiglu_limit: float
    vocab_size: int
    rms_norm_eps: float
    linear_norm_eps: float = 1e-6
    route_norm: bool = True
    rope_interleave: bool = True
    gated_attention: bool = True
    held_experts: Optional[Tuple[int, int]] = None  # None: all of them
    batch_size: int = 1
    seq_len: int = 1024   # the graph's nominal sequence; serving re-shapes

    def __post_init__(self):
        self.held_experts = tuple(self.held_experts) if self.held_experts \
            else (0, self.num_experts)
        self.full_attention_layers = tuple(
            int(i) for i in self.full_attention_layers)
        out = [i for i in self.full_attention_layers
               if not 0 <= i < self.num_layers]
        if out:
            raise ValueError(
                f"build_gigachat: full_attention_layers {out} lie outside "
                f"the {self.num_layers} layers")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"build_gigachat: {self.linear_num_value_heads} value heads "
                f"are no multiple of {self.linear_num_key_heads} key heads")

    @staticmethod
    def tiny(batch_size: int = 2, **over) -> "GigaChatConfig":
        """CI-sized: one leading dense delta-rule layer, then one period of
        three delta-rule layers and one latent layer over experts."""
        kw = dict(batch_size=batch_size, seq_len=32, hidden=64, num_layers=5,
                  full_attention_layers=(4,), num_dense_layers=1,
                  num_heads=4, q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                  v_dim=16, rope_theta=10000.0,
                  rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                                "beta_slow": 1, "mscale": 1,
                                "mscale_all_dim": 1,
                                "original_max_position_embeddings": 16},
                  linear_num_key_heads=2, linear_num_value_heads=4,
                  linear_key_head_dim=16, linear_value_head_dim=16,
                  linear_conv_kernel_dim=4, intermediate=96,
                  moe_intermediate=32, num_experts=16, num_experts_per_tok=4,
                  num_shared_experts=1, route_scale=2.5, swiglu_limit=10.0,
                  vocab_size=128, rms_norm_eps=1e-6)
        kw.update(over)
        return GigaChatConfig(**kw)


def build_gigachat(ff: FFModel, cfg: GigaChatConfig):
    """Returns (input_ids, logits (b, s, vocab) over the held vocabulary
    rows). Node names: ``embed``, ``l<i>_norm{1..4}``, ``l<i>_gdn`` or
    ``l<i>_mla``, ``l<i>_mlp`` (dense) or ``l<i>_moeshared`` + ``l<i>_moe{
    router, dispatch, experts, combine}``, ``norm_f``, ``lm_head``."""
    eps, limit = cfg.rms_norm_eps, cfg.swiglu_limit
    norm = lambda x, name: ff.rms_norm(x, eps=eps, gain=NORM_GAIN, name=name)
    ids = ff.create_tensor((cfg.batch_size, cfg.seq_len),
                           dtype=DataType.DT_INT32, name="input_ids")
    t = ff.embedding(ids, cfg.vocab_size, cfg.hidden, name="embed")
    for i in range(cfg.num_layers):
        h = norm(t, f"l{i}_norm1")
        if i in cfg.full_attention_layers:
            a = ff.latent_attention(
                h, embed_dim=cfg.hidden, num_heads=cfg.num_heads,
                q_rank=cfg.q_rank, kv_rank=cfg.kv_rank, nope_dim=cfg.nope_dim,
                rope_dim=cfg.rope_dim, v_dim=cfg.v_dim,
                rope_theta=cfg.rope_theta, eps=eps,
                rope_scaling=cfg.rope_scaling,
                rope_interleave=cfg.rope_interleave,
                gated=cfg.gated_attention, name=f"l{i}_mla")
        else:
            a = ff.gated_delta_mixer(
                h, num_heads=cfg.linear_num_value_heads,
                num_key_heads=cfg.linear_num_key_heads,
                key_dim=cfg.linear_key_head_dim,
                value_dim=cfg.linear_value_head_dim,
                conv_width=cfg.linear_conv_kernel_dim, neg_eigval=False,
                norm_eps=cfg.linear_norm_eps, gate="sigmoid2_zero_centered",
                name=f"l{i}_gdn")
        t = ff.add(t, norm(a, f"l{i}_norm2"))
        h = norm(t, f"l{i}_norm3")
        if i < cfg.num_dense_layers:
            m = ff.gated_mlp(h, cfg.intermediate, limit=limit,
                             name=f"l{i}_mlp")
        else:
            m = ff.routed_experts(
                h, cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate, held=cfg.held_experts,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                selection_bias=False, limit=limit, name=f"l{i}_moe")
            if cfg.num_shared_experts:
                shared = ff.gated_mlp(
                    h, cfg.moe_intermediate * cfg.num_shared_experts,
                    limit=limit, name=f"l{i}_moeshared")
                m = ff.add(shared, m)
        t = ff.add(t, norm(m, f"l{i}_norm4"))
    t = norm(t, "norm_f")
    logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
    return ids, logits


def gigachat_delta_mixer_params(cfg: GigaChatConfig) -> int:
    """``w_q``, ``w_k`` at the key heads' width, ``w_v``, ``w_g``, ``w_o``
    at the value heads', ``w_a`` and ``w_b``, the conv taps, ``a_log``,
    ``dt_bias`` and the head norm's gain."""
    d, hk, hv, dk, dv, k = (
        cfg.hidden, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
        cfg.linear_key_head_dim, cfg.linear_value_head_dim,
        cfg.linear_conv_kernel_dim)
    return (2 * d * hk * dk + 3 * d * hv * dv + 2 * d * hv
            + (2 * hk * dk + hv * dv) * k + 2 * hv + dv)


def gigachat_latent_params(cfg: GigaChatConfig) -> int:
    """The five latent matrices, the two latent norms and the gate."""
    h = cfg.num_heads
    return (cfg.hidden * cfg.q_rank
            + cfg.q_rank * h * (cfg.nope_dim + cfg.rope_dim)
            + cfg.hidden * (cfg.kv_rank + cfg.rope_dim)
            + cfg.kv_rank * h * (cfg.nope_dim + cfg.v_dim)
            + h * cfg.v_dim * cfg.hidden + cfg.q_rank + cfg.kv_rank
            + (cfg.hidden * h * cfg.v_dim if cfg.gated_attention else 0))


def gigachat_expert_part_params(cfg: GigaChatConfig) -> int:
    """A layer's held and shared experts and its router."""
    expert = 3 * cfg.hidden * cfg.moe_intermediate
    return (cfg.num_shared_experts + cfg.held_experts[1]) * expert \
        + cfg.hidden * cfg.num_experts


def gigachat_param_count(cfg: GigaChatConfig) -> int:
    """Parameters held here: the held experts and vocabulary rows alone,
    four block norms a layer, the last norm, an untied head."""
    d = cfg.hidden
    n_full = len(set(cfg.full_attention_layers))
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    return (n_full * gigachat_latent_params(cfg)
            + (cfg.num_layers - n_full) * gigachat_delta_mixer_params(cfg)
            + cfg.num_layers * 4 * d
            + n_dense * 3 * d * cfg.intermediate
            + (cfg.num_layers - n_dense) * gigachat_expert_part_params(cfg)
            + 2 * cfg.vocab_size * d + d)
