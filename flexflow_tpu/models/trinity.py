"""A sliding/global, grouped-query, gated-attention decoder with a
sigmoid-routed dropless expert layer and a shared expert: the ``afmoe``
family (Trinity-Mini, https://huggingface.co/arcee-ai/Trinity-Mini), on the
training path.

    h0 = E[ids] * sqrt(hidden)                        (``mup``)
    layer i:  a = post_attn_norm(Attn_i(input_norm(h)));   h <- h + a
              m = post_mlp_norm(MLP_i(pre_mlp_norm(h)));   h <- h + m
    logits = final_norm(h) W_head;  softmax

``Attn``: grouped-query causal attention with an RMS norm per head on q and
k and a sigmoid gate on the core's output; ``sliding`` layers see a window
of keys and carry rotary positions, ``full`` layers see every earlier key
and carry no position signal. ``MLP``: a gated (SwiGLU) MLP for the first
``num_dense_layers`` layers, after them a shared expert plus the dropless
routed layer (``FFModel.routed_experts``). No biases anywhere.

``held_experts=(first, count)`` and ``vocab_size`` make the model ONE
DEVICE'S SHARE of an expert- and vocabulary-parallel deployment: the router
ranks all ``num_experts`` and normalises over all the chosen, this device
adds the chosen experts it holds, and embedding and head hold ``vocab_size``
rows of the vocabulary. Nothing stands in for the other devices.

Serving is not supported: the KV pool and ``flash_decode`` serve grouped K/V
heads (since PR 44: serving/kvcache.py, the grouped layout), but the serving
programs know neither sliding windows nor rotary positions, and
``MultiHeadAttentionOp`` raises on either under a serving context
(ROADMAP.md, Reach R3 (a)).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from ..ffconst import DataType
from ..model import FFModel


@dataclasses.dataclass
class TrinityConfig:
    batch_size: int = 1
    seq_len: int = 8192
    hidden: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    window: int = 2048
    rope_theta: float = 10000.0
    layer_types: Sequence[str] = ("sliding_attention",) * 3 \
        + ("full_attention",)
    num_dense_layers: int = 1
    intermediate: int = 6144
    moe_intermediate: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    route_norm: bool = True
    score_func: str = "sigmoid"
    held_experts: Optional[Tuple[int, int]] = None  # None: all of them
    vocab_size: int = 200192
    rms_norm_eps: float = 1e-5
    mup: bool = True

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
        if bad:
            raise ValueError(f"TrinityConfig.layer_types: unknown {bad}")
        if self.score_func != "sigmoid":
            raise ValueError(f"TrinityConfig.score_func: {self.score_func!r}"
                             " (the routed layer scores with sigmoid alone)")
        self.held_experts = tuple(self.held_experts) if self.held_experts \
            else (0, self.num_experts)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


def build_trinity(ff: FFModel, cfg: TrinityConfig):
    """Returns the softmax over the held vocabulary rows, (b, s, vocab): the
    graph's last tensor, which the sparse categorical cross-entropy takes
    token by token. Node names: ``embed``, ``l<i>_{norm1..4, attn}``,
    ``l<i>_mlp`` (dense) or ``l<i>_moeshared`` + ``l<i>_moe{router,
    dispatch, experts, combine}``, ``norm_f``, ``lm_head``."""
    ids = ff.create_tensor((cfg.batch_size, cfg.seq_len),
                           dtype=DataType.DT_INT32, name="input_ids")
    t = ff.embedding(ids, cfg.vocab_size, cfg.hidden, name="embed")
    if cfg.mup:
        t = ff.scalar_multiply(t, float(np.sqrt(cfg.hidden)),
                               name="embed_scale")
    for i, kind in enumerate(cfg.layer_types):
        sliding = kind == "sliding_attention"
        h = ff.rms_norm(t, eps=cfg.rms_norm_eps, name=f"l{i}_norm1")
        a = ff.multihead_attention(
            h, h, h, embed_dim=cfg.hidden, num_heads=cfg.num_heads,
            kdim=cfg.head_dim, vdim=cfg.head_dim, bias=False, causal=True,
            num_kv_heads=cfg.num_kv_heads,
            window=cfg.window if sliding else None,
            rope_theta=cfg.rope_theta if sliding else None,
            qk_norm=cfg.rms_norm_eps, gated=True, name=f"l{i}_attn")
        a = ff.rms_norm(a, eps=cfg.rms_norm_eps, name=f"l{i}_norm2")
        t = ff.add(t, a)
        h = ff.rms_norm(t, eps=cfg.rms_norm_eps, name=f"l{i}_norm3")
        if i < cfg.num_dense_layers:
            m = ff.gated_mlp(h, cfg.intermediate, name=f"l{i}_mlp")
        else:
            m = ff.routed_experts(
                h, cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate, held=cfg.held_experts,
                route_norm=cfg.route_norm,
                route_scale=cfg.route_scale, name=f"l{i}_moe")
            if cfg.num_shared_experts:
                shared = ff.gated_mlp(
                    h, cfg.moe_intermediate * cfg.num_shared_experts,
                    name=f"l{i}_moeshared")
                m = ff.add(shared, m)
        m = ff.rms_norm(m, eps=cfg.rms_norm_eps, name=f"l{i}_norm4")
        t = ff.add(t, m)
    t = ff.rms_norm(t, eps=cfg.rms_norm_eps, name="norm_f")
    logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
    return ff.softmax(logits)


def trinity_param_count(cfg: TrinityConfig) -> int:
    """Parameters held here: the held experts and vocabulary rows alone."""
    h, d = cfg.hidden, cfg.head_dim
    attn = 3 * h * cfg.num_heads * d + 2 * h * cfg.num_kv_heads * d + 2 * d
    dense = 3 * h * cfg.intermediate
    expert = 3 * h * cfg.moe_intermediate
    moe = cfg.num_shared_experts * expert + cfg.held_experts[1] * expert \
        + h * cfg.num_experts + cfg.num_experts  # router and its bias buffer
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    return cfg.num_layers * (attn + 4 * h) + n_dense * dense \
        + (cfg.num_layers - n_dense) * moe + 2 * cfg.vocab_size * h + h


def trinity_attention_pairs(cfg: TrinityConfig) -> int:
    """Unmasked (query, key) pairs of one sequence, summed over layers."""
    s, w = cfg.seq_len, cfg.window
    full = s * (s + 1) // 2
    band = full if w >= s else w * (w + 1) // 2 + (s - w) * w
    return sum(band if kind == "sliding_attention" else full
               for kind in cfg.layer_types)


def trinity_train_flops_per_token(cfg: TrinityConfig) -> float:
    """Forward + backward (3x forward) model FLOPs of one trained token on
    this device: 6 x the matrix parameters a token meets here (attention,
    the dense MLP, the shared expert, the router, in expectation
    ``k * held / num_experts`` routed experts, the held head) plus
    attention's unmasked pairs; recomputation is not counted."""
    h, d = cfg.hidden, cfg.head_dim
    attn = 3 * h * cfg.num_heads * d + 2 * h * cfg.num_kv_heads * d
    expert = 3 * h * cfg.moe_intermediate
    routed = cfg.num_experts_per_tok * cfg.held_experts[1] / cfg.num_experts
    moe = (cfg.num_shared_experts + routed) * expert + h * cfg.num_experts
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    matmul = cfg.num_layers * attn + n_dense * 3 * h * cfg.intermediate \
        + (cfg.num_layers - n_dense) * moe + h * cfg.vocab_size
    pairs = trinity_attention_pairs(cfg) / cfg.seq_len
    return 6.0 * matmul + 12.0 * cfg.num_heads * d * pairs
