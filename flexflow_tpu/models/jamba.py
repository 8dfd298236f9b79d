"""A hybrid decoder of selective state-space (Mamba-1) layers with an
attention layer every ``attn_layer_period``: the ``jamba`` family
(AI21-Jamba2-3B, https://huggingface.co/ai21labs/AI21-Jamba2-3B), built for
the serving path.

    h0 = E[ids]                                   (no position signal anywhere)
    layer i:  u = RMS(h);   h <- h + Mix_i(u)     Mix_i = Attn if i % period == offset
              v = RMS(h);   h <- h + W_down(silu(W_gate v) * W_up v)    else Mamba
    logits = RMS(h) W_head                        (untied head: a stated departure)

``Mamba``: ``FFModel.ssm_mixer`` (ops/ssm.py) — inner width ``mamba_expand *
hidden``, a ``mamba_d_state``-number state a channel, a causal depthwise conv
of ``mamba_d_conv``, ``dt`` through ``mamba_dt_rank``, RMS norms on ``dt``,
``B`` and ``C``. ``Attn``: causal attention with ``num_kv_heads`` K/V heads
under ``num_heads`` query heads, no bias, no rotary. Every layer is followed
by a dense gated MLP (the family's routed layers are ``num_experts`` > 1,
which this builder refuses: the published model has 1). RMS norms, no bias.

The published model ties the head to the embedding; the graph has no way to
share a weight between two nodes, so the head is a matrix of its own
(``gpt2.py`` has the same departure): ``vocab * hidden`` parameters more.
"""
from __future__ import annotations

import dataclasses

from ..ffconst import DataType
from ..model import FFModel


@dataclasses.dataclass
class JambaConfig:
    hidden: int
    num_layers: int
    attn_layer_period: int
    attn_layer_offset: int
    num_heads: int
    num_kv_heads: int
    intermediate: int
    mamba_expand: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_dt_rank: int
    mamba_conv_bias: bool
    mamba_proj_bias: bool
    vocab_size: int
    rms_norm_eps: float
    num_experts: int = 1
    batch_size: int = 1
    seq_len: int = 1024   # the graph's nominal sequence; serving re-shapes

    def __post_init__(self):
        if self.num_experts != 1:
            raise NotImplementedError(
                "build_jamba: the family's routed MLP layers (num_experts "
                f"{self.num_experts}) are not built; the dense form is")
        if self.hidden % self.num_heads or \
                self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"build_jamba: {self.num_heads} heads over hidden "
                f"{self.hidden} with {self.num_kv_heads} K/V heads")

    @property
    def inner(self) -> int:
        return self.mamba_expand * self.hidden

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    @staticmethod
    def tiny(batch_size: int = 2, **over) -> "JambaConfig":
        """CI-sized: a 4-layer period of 3 mixers and 1 one-K/V-head
        attention layer, twice."""
        kw = dict(batch_size=batch_size, seq_len=32, hidden=64, num_layers=8,
                  attn_layer_period=4, attn_layer_offset=2, num_heads=4,
                  num_kv_heads=1, intermediate=96, mamba_expand=2,
                  mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4,
                  mamba_conv_bias=True, mamba_proj_bias=False,
                  vocab_size=128, rms_norm_eps=1e-6)
        kw.update(over)
        return JambaConfig(**kw)


def build_jamba(ff: FFModel, cfg: JambaConfig):
    """Returns (input_ids, logits (b, s, vocab)). Node names: ``embed``,
    ``l<i>_norm1``, ``l<i>_ssm`` or ``l<i>_attn``, ``l<i>_norm2``,
    ``l<i>_mlp``, ``norm_f``, ``lm_head``."""
    eps = cfg.rms_norm_eps
    ids = ff.create_tensor((cfg.batch_size, cfg.seq_len),
                           dtype=DataType.DT_INT32, name="input_ids")
    t = ff.embedding(ids, cfg.vocab_size, cfg.hidden, name="embed")
    for i in range(cfg.num_layers):
        h = ff.rms_norm(t, eps=eps, name=f"l{i}_norm1")
        if cfg.is_attention(i):
            a = ff.multihead_attention(
                h, h, h, embed_dim=cfg.hidden, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads
                if cfg.num_kv_heads != cfg.num_heads else 0,
                bias=False, causal=True, name=f"l{i}_attn")
        else:
            a = ff.ssm_mixer(
                h, inner_dim=cfg.inner, state_dim=cfg.mamba_d_state,
                conv_width=cfg.mamba_d_conv, dt_rank=cfg.mamba_dt_rank,
                conv_bias=cfg.mamba_conv_bias,
                proj_bias=cfg.mamba_proj_bias, norm_eps=eps,
                name=f"l{i}_ssm")
        t = ff.add(t, a)
        h = ff.rms_norm(t, eps=eps, name=f"l{i}_norm2")
        t = ff.add(t, ff.gated_mlp(h, cfg.intermediate, name=f"l{i}_mlp"))
    t = ff.rms_norm(t, eps=eps, name="norm_f")
    logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
    return ids, logits


def jamba_mixer_params(cfg: JambaConfig) -> int:
    d, e, n, k, r = (cfg.hidden, cfg.inner, cfg.mamba_d_state,
                     cfg.mamba_d_conv, cfg.mamba_dt_rank)
    bias = (e if cfg.mamba_conv_bias else 0) \
        + (2 * e + d if cfg.mamba_proj_bias else 0)
    return (d * 2 * e + e * k + e * (r + 2 * n) + r + 2 * n + r * e + e
            + e * n + e + e * d + bias)


def jamba_attention_params(cfg: JambaConfig) -> int:
    hd = cfg.hidden // cfg.num_heads
    return cfg.hidden * hd * (2 * cfg.num_heads + 2 * cfg.num_kv_heads)


def jamba_param_count(cfg: JambaConfig, tied: bool = False) -> int:
    """Parameters held: as built (an untied head), or ``tied`` as published."""
    d = cfg.hidden
    n_attn = sum(cfg.is_attention(i) for i in range(cfg.num_layers))
    per_layer = 3 * d * cfg.intermediate + 2 * d
    return (cfg.num_layers * per_layer
            + n_attn * jamba_attention_params(cfg)
            + (cfg.num_layers - n_attn) * jamba_mixer_params(cfg)
            + (1 if tied else 2) * cfg.vocab_size * d + d)
