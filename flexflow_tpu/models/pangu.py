"""A latent-attention decoder with sandwich norms, a sigmoid-routed top-k
expert layer and a shared expert: the ``pangu_ultra_moe`` family
(openPangu-Ultra-MoE-718B,
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B), built
for the serving path.

    h0 = E[ids]
    layer i:  a = MLA_i(norm1(h));   h <- h + norm2(a)      (sandwich)
              m = MLP_i(norm3(h));   h <- h + norm4(m)
    logits = norm_f(h) W_head                               (untied head)

``MLA``: multi-head latent attention (ops/latent_attention.py) — what a
token leaves in the KV pool is one ``kv_rank + rope_dim`` row for all heads.
``MLP``: a gated (SwiGLU) MLP of width ``intermediate`` for the first
``num_dense_layers`` layers; after them a shared expert plus the dropless
routed layer (``FFModel.routed_experts``): sigmoid scores in float32, the
``num_experts_per_tok`` largest chosen (no selection bias, no expert groups),
weights ``route_scale * s_i / sum_chosen s``. RMS norms (eps
``rms_norm_eps``), no bias anywhere.

``held_experts=(first, count)`` and ``vocab_size`` make the model ONE
DEVICE'S SHARE of an expert- and vocabulary-parallel deployment, as
models/trinity.py has it: the router ranks all ``num_experts``, this device
adds the chosen experts it holds, embedding and head hold ``vocab_size``
rows. Nothing stands in for the other devices. The multi-token-prediction
module of the published model is a further block after the last layer and is
not built (ROADMAP.md).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..ffconst import DataType
from ..model import FFModel


@dataclasses.dataclass
class PanguConfig:
    batch_size: int = 1
    seq_len: int = 1024   # the graph's nominal sequence; serving re-shapes
    hidden: int = 7680
    num_heads: int = 128
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 25600000.0
    num_layers: int = 61
    num_dense_layers: int = 3
    intermediate: int = 18432
    moe_intermediate: int = 2048
    num_experts: int = 256
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.5
    route_norm: bool = True
    held_experts: Optional[Tuple[int, int]] = None  # None: all of them
    vocab_size: int = 153600
    rms_norm_eps: float = 1e-5
    sandwich_norm: bool = True

    def __post_init__(self):
        self.held_experts = tuple(self.held_experts) if self.held_experts \
            else (0, self.num_experts)

    @staticmethod
    def tiny(batch_size: int = 2, **over) -> "PanguConfig":
        """CI-sized: every mechanism at toy widths."""
        kw = dict(batch_size=batch_size, seq_len=32, hidden=64, num_heads=4,
                  q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
                  rope_theta=10000.0, num_layers=3, num_dense_layers=1,
                  intermediate=96, moe_intermediate=32, num_experts=16,
                  num_experts_per_tok=4, vocab_size=128)
        kw.update(over)
        return PanguConfig(**kw)


def build_pangu(ff: FFModel, cfg: PanguConfig):
    """Returns (input_ids, logits (b, s, vocab) over the held vocabulary
    rows). Node names: ``embed``, ``l<i>_norm{1..4}``, ``l<i>_mla``,
    ``l<i>_mlp`` (dense) or ``l<i>_moeshared`` + ``l<i>_moe{router,
    dispatch, experts, combine}``, ``norm_f``, ``lm_head``."""
    eps = cfg.rms_norm_eps
    ids = ff.create_tensor((cfg.batch_size, cfg.seq_len),
                           dtype=DataType.DT_INT32, name="input_ids")
    t = ff.embedding(ids, cfg.vocab_size, cfg.hidden, name="embed")
    for i in range(cfg.num_layers):
        h = ff.rms_norm(t, eps=eps, name=f"l{i}_norm1")
        a = ff.latent_attention(
            h, embed_dim=cfg.hidden, num_heads=cfg.num_heads,
            q_rank=cfg.q_rank, kv_rank=cfg.kv_rank, nope_dim=cfg.nope_dim,
            rope_dim=cfg.rope_dim, v_dim=cfg.v_dim,
            rope_theta=cfg.rope_theta, eps=eps, name=f"l{i}_mla")
        if cfg.sandwich_norm:
            a = ff.rms_norm(a, eps=eps, name=f"l{i}_norm2")
        t = ff.add(t, a)
        h = ff.rms_norm(t, eps=eps, name=f"l{i}_norm3")
        if i < cfg.num_dense_layers:
            m = ff.gated_mlp(h, cfg.intermediate, name=f"l{i}_mlp")
        else:
            m = ff.routed_experts(
                h, cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate, held=cfg.held_experts,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                selection_bias=False, name=f"l{i}_moe")
            if cfg.num_shared_experts:
                shared = ff.gated_mlp(
                    h, cfg.moe_intermediate * cfg.num_shared_experts,
                    name=f"l{i}_moeshared")
                m = ff.add(shared, m)
        if cfg.sandwich_norm:
            m = ff.rms_norm(m, eps=eps, name=f"l{i}_norm4")
        t = ff.add(t, m)
    t = ff.rms_norm(t, eps=eps, name="norm_f")
    logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
    return ids, logits


def _mla_params(cfg: PanguConfig) -> int:
    h = cfg.num_heads
    return (cfg.hidden * cfg.q_rank
            + cfg.q_rank * h * (cfg.nope_dim + cfg.rope_dim)
            + cfg.hidden * (cfg.kv_rank + cfg.rope_dim)
            + cfg.kv_rank * h * (cfg.nope_dim + cfg.v_dim)
            + h * cfg.v_dim * cfg.hidden)


def pangu_param_count(cfg: PanguConfig) -> int:
    """Parameters held here: the held experts and vocabulary rows alone;
    norm gains (four a layer under the sandwich, two latent) included."""
    d = cfg.hidden
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    expert = 3 * d * cfg.moe_intermediate
    moe = (cfg.num_shared_experts + cfg.held_experts[1]) * expert \
        + d * cfg.num_experts
    norms = (4 if cfg.sandwich_norm else 2) * d + cfg.q_rank + cfg.kv_rank
    return cfg.num_layers * (_mla_params(cfg) + norms) \
        + n_dense * 3 * d * cfg.intermediate \
        + (cfg.num_layers - n_dense) * moe + 2 * cfg.vocab_size * d + d


def _matmul_params_a_token(cfg: PanguConfig, mla: int) -> float:
    """Matrix parameters one token meets here outside the attention core,
    with ``mla`` those of one attention node: in expectation ``k * held /
    num_experts`` routed experts."""
    d = cfg.hidden
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    routed = cfg.num_experts_per_tok * cfg.held_experts[1] / cfg.num_experts
    moe = (cfg.num_shared_experts + routed) * 3 * d * cfg.moe_intermediate \
        + d * cfg.num_experts
    return cfg.num_layers * mla + n_dense * 3 * d * cfg.intermediate \
        + (cfg.num_layers - n_dense) * moe + d * cfg.vocab_size


def pangu_prefill_flops_per_token(cfg: PanguConfig, context: int) -> float:
    """Forward FLOPs of one prompt token that sees ``context`` keys, the
    materialised form: every matrix it meets, and per layer and head a
    ``nope + rope`` score and a ``v`` sum a key. The keys' up-projection
    (``kv_rank -> heads x (nope + v)``) is counted once a token, as a
    whole-prompt prefill pays it; a chunked prefill pays it again a chunk."""
    core = cfg.num_heads * (cfg.nope_dim + cfg.rope_dim + cfg.v_dim)
    return 2.0 * _matmul_params_a_token(cfg, _mla_params(cfg)) \
        + 2.0 * cfg.num_layers * core * context


def pangu_decode_flops_per_token(cfg: PanguConfig, context: int) -> float:
    """Forward FLOPs of one decoded token over ``context`` cached rows, the
    absorbed form: ``W_kvb`` is met once for the query and once for the
    output (its parameters, as in the matrix count), and per layer and head
    a ``kv_rank + rope`` score and a ``kv_rank`` sum a key."""
    core = cfg.num_heads * (2 * cfg.kv_rank + cfg.rope_dim)
    return 2.0 * _matmul_params_a_token(cfg, _mla_params(cfg)) \
        + 2.0 * cfg.num_layers * core * context
