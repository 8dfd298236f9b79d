"""A hybrid decoder of gated delta-rule (Gated DeltaNet) layers with a
full-attention layer closing every period: the ``olmo_hybrid`` family
(Olmo-Hybrid-7B, https://huggingface.co/allenai/Olmo-Hybrid-7B), built for
the serving path.

    h0 = E[ids]                                   (no position signal anywhere)
    layer i:  h <- h + RMS(Mix_i(h))              Mix_i = Attn where layer_types[i] is
              h <- h + RMS(W_down(silu(W_gate h) * W_up h))    "full_attention", else GDN
    logits = RMS(h) W_head                        (untied head, as published)

``GDN``: ``FFModel.gated_delta_mixer`` (ops/gated_delta.py) —
``linear_num_key_heads`` heads with a ``(linear_key_head_dim,
linear_value_head_dim)`` matrix state each, causal depthwise convs of
``linear_conv_kernel_dim`` on q, k and v, a write strength that reaches 2
(``linear_allow_neg_eigval``). ``Attn``: causal multi-head attention, as many
K/V heads as query heads, no bias, NO positions (``rope_theta`` null), an RMS
norm over the whole q and k width. RMS norms, no bias anywhere.

The config does not say where a block's norms sit; ``NORM_PLACEMENT`` is the
OLMo 2 / OLMo 3 family's (a norm on each sublayer's OUTPUT, none on its
input, one before the head). A PR with the model's source at hand changes
that one line (and the same line of the plain reference); the two placements
cost the same.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

from ..ffconst import DataType
from ..model import FFModel

#: "post": ``h + RMS(sublayer(h))`` (OLMo 2 / 3); "pre": ``h + sublayer(RMS(h))``
NORM_PLACEMENT = "post"

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass
class OlmoHybridConfig:
    hidden: int
    layer_types: Sequence[str]
    num_heads: int
    head_dim: int
    intermediate: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool
    vocab_size: int
    rms_norm_eps: float
    batch_size: int = 1
    seq_len: int = 1024   # the graph's nominal sequence; serving re-shapes

    def __post_init__(self):
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown or not self.layer_types:
            raise ValueError(
                f"build_olmo_hybrid: layer_types holds {LINEAR!r} and "
                f"{FULL!r}; got {sorted(unknown) or 'no layer'}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                "build_olmo_hybrid: more value heads than key heads "
                f"({self.linear_num_value_heads} over "
                f"{self.linear_num_key_heads}) is not built; the published "
                "model has as many of one as of the other")
        if self.num_heads * self.head_dim != self.hidden:
            raise ValueError(
                f"build_olmo_hybrid: {self.num_heads} heads of "
                f"{self.head_dim} are not hidden {self.hidden}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @staticmethod
    def tiny(batch_size: int = 2, **over) -> "OlmoHybridConfig":
        """CI-sized: two periods of three delta-rule layers and one
        full-attention layer."""
        kw = dict(batch_size=batch_size, seq_len=32, hidden=64,
                  layer_types=[LINEAR, LINEAR, LINEAR, FULL] * 2,
                  num_heads=2, head_dim=32, intermediate=96,
                  linear_num_key_heads=2, linear_num_value_heads=2,
                  linear_key_head_dim=16,
                  linear_value_head_dim=32, linear_conv_kernel_dim=4,
                  linear_allow_neg_eigval=True, vocab_size=128,
                  rms_norm_eps=1e-6)
        kw.update(over)
        return OlmoHybridConfig(**kw)


def build_olmo_hybrid(ff: FFModel, cfg: OlmoHybridConfig):
    """Returns (input_ids, logits (b, s, vocab)). Node names: ``embed``,
    ``l<i>_gdn`` or ``l<i>_attn``, ``l<i>_norm1``, ``l<i>_mlp``,
    ``l<i>_norm2``, ``norm_f``, ``lm_head``."""
    eps = cfg.rms_norm_eps

    def sublayer(t, i, which, make):
        norm = lambda x: ff.rms_norm(x, eps=eps, name=f"l{i}_norm{which}")
        if NORM_PLACEMENT == "post":
            return ff.add(t, norm(make(t)))
        return ff.add(t, make(norm(t)))

    ids = ff.create_tensor((cfg.batch_size, cfg.seq_len),
                           dtype=DataType.DT_INT32, name="input_ids")
    t = ff.embedding(ids, cfg.vocab_size, cfg.hidden, name="embed")
    for i, kind in enumerate(cfg.layer_types):
        if kind == FULL:
            mix = lambda h, i=i: ff.multihead_attention(
                h, h, h, embed_dim=cfg.hidden, num_heads=cfg.num_heads,
                bias=False, causal=True, qk_norm=eps, qk_norm_whole=True,
                name=f"l{i}_attn")
        else:
            mix = lambda h, i=i: ff.gated_delta_mixer(
                h, num_heads=cfg.linear_num_key_heads,
                key_dim=cfg.linear_key_head_dim,
                value_dim=cfg.linear_value_head_dim,
                conv_width=cfg.linear_conv_kernel_dim,
                neg_eigval=cfg.linear_allow_neg_eigval, norm_eps=eps,
                name=f"l{i}_gdn")
        t = sublayer(t, i, 1, mix)
        t = sublayer(t, i, 2, lambda h, i=i: ff.gated_mlp(
            h, cfg.intermediate, name=f"l{i}_mlp"))
    t = ff.rms_norm(t, eps=eps, name="norm_f")
    logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
    return ids, logits


def olmo_hybrid_mixer_params(cfg: OlmoHybridConfig) -> int:
    d, h, dk, dv, k = (cfg.hidden, cfg.linear_num_key_heads,
                       cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                       cfg.linear_conv_kernel_dim)
    return (2 * d * h * dk + 2 * d * h * dv + 2 * d * h + h * dv * d
            + (2 * h * dk + h * dv) * k + 2 * h + dv)


def olmo_hybrid_attention_params(cfg: OlmoHybridConfig) -> int:
    """q, k, v, o and the two whole-width norm gains."""
    return 4 * cfg.hidden * cfg.hidden + 2 * cfg.hidden


def olmo_hybrid_param_count(cfg: OlmoHybridConfig) -> int:
    """Parameters held: as built and as published (an untied head)."""
    d = cfg.hidden
    n_full = sum(kind == FULL for kind in cfg.layer_types)
    per_layer = 3 * d * cfg.intermediate + 2 * d
    return (cfg.num_layers * per_layer
            + n_full * olmo_hybrid_attention_params(cfg)
            + (cfg.num_layers - n_full) * olmo_hybrid_mixer_params(cfg)
            + 2 * cfg.vocab_size * d + d)
