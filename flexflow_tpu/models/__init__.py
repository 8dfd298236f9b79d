"""Model zoo matching the reference's example apps (SURVEY §2.5):
AlexNet/CIFAR-10, ResNet-50, ResNeXt-50, InceptionV3, Transformer, BERT-Large,
GPT-2 (decoder-only causal LM), DLRM, XDL, MLP_Unify, CANDLE-Uno, MoE,
NMT (LSTM seq2seq); and, with no reference analog, the sliding/global
grouped-query decoder with a dropless routed expert layer (trinity.py) and
the latent-attention decoder with sandwich norms and a routed top-8 layer,
built for the serving path (pangu.py), and the hybrid decoder of selective
state-space layers with a one-K/V-head attention layer every period
(jamba.py), built for the serving path too, and the hybrid decoder of gated
delta-rule layers with a full-attention layer closing every period
(olmo_hybrid.py), for the serving path as well, and the hybrid decoder that
holds both cache kinds in one graph: delta-rule layers with grouped key
heads, one latent-attention layer closing every period, all over a routed
expert layer under a clamped SwiGLU (gigachat.py), for the serving path."""
from .bert import BertConfig, build_bert, bert_param_count  # noqa: F401
from .gpt2 import (GPT2Config, build_gpt2,  # noqa: F401
                   gpt2_param_count, gpt2_train_flops_per_step)
from .vision import (build_alexnet, build_alexnet_cifar10,  # noqa: F401
                     build_resnet50, build_resnext50, build_inception_v3)
from .dlrm import build_dlrm  # noqa: F401
from .transformer import (TransformerConfig, build_transformer,  # noqa: F401
                          build_moe_mlp)
from .misc import (build_mlp_unify, build_xdl,  # noqa: F401
                   build_candle_uno)
from .nmt import NMTConfig, build_nmt  # noqa: F401
from .trinity import (TrinityConfig, build_trinity,  # noqa: F401
                      trinity_param_count, trinity_train_flops_per_token)
from .pangu import (PanguConfig, build_pangu,  # noqa: F401
                    pangu_param_count, pangu_prefill_flops_per_token,
                    pangu_decode_flops_per_token)
from .jamba import (JambaConfig, build_jamba,  # noqa: F401
                    jamba_param_count)
from .olmo_hybrid import (OlmoHybridConfig, build_olmo_hybrid,  # noqa: F401
                          olmo_hybrid_param_count)
from .gigachat import (GigaChatConfig, build_gigachat,  # noqa: F401
                       gigachat_param_count)
