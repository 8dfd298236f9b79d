"""A training builder for the rehearsal's language-model cell: GPT-2 as
``build_gpt2`` builds it, with the softmax the loss is taken on as the
graph's last tensor (what the train driver asks of a builder). A PR that
brings a real language-model training cell names such a builder of the
program in its configuration's file."""
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2  # noqa: F401


def build_gpt2_lm(ff, cfg):
    _ids, logits = build_gpt2(ff, cfg)
    return ff.softmax(logits)
