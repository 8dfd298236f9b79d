"""The yardstick's arithmetic for a gated delta-rule (Gated DeltaNet) mixer,
beside ``flops.py``, ``moe_flops.py``, ``mla_flops.py`` and ``ssm_flops.py``
and for the same reason: closed forms of the shapes and of what the program
COUNTED, kept with the benchmark so that a later edit of the program cannot
move them.

With ``H`` heads (``linear_num_key_heads``), ``d_k`` / ``d_v`` the key /
value head widths, ``K`` the conv width: a slot carries ``H`` float32
matrices of ``(d_k, d_v)`` and ``K - 1`` inputs of the three convs (``2 H d_k
+ H d_v`` channels) in the model's dtype, per delta-rule layer.

The prefill's recurrence in its chunked form (``C = 64`` tokens a chunk) is
six products on the matrix unit a chunk and head — ``K K^T`` and ``Q K^T``
(``C x d_k x C``), ``K S`` and ``Q S`` (``C x d_k x d_v``), ``P U`` (``C x C
x d_v``) and ``K^T U`` (``d_k x C x d_v``) — counted once each at two
operations a multiply-add, whatever passes the float32 products take on a
bf16 unit; the unit-lower-triangular solve runs on the vector unit and is
not counted. Its least traffic is ``q``, ``k``, ``v``, ``g`` and ``beta``
read and ``o`` written a token, in float32 (the rule's own precision), and a
sequence's final state written once.
"""
from __future__ import annotations

CHUNK = 64


def dims(config: dict):
    """(H, d_k, d_v, K) of the configuration's delta-rule layers."""
    return (int(config["linear_num_key_heads"]),
            int(config["linear_key_head_dim"]),
            int(config["linear_value_head_dim"]),
            int(config["linear_conv_kernel_dim"]))


def mixer_layers(config: dict) -> int:
    """Layers that are delta-rule mixers."""
    return sum(kind == "linear_attention" for kind in config["layer_types"])


def slot_state_bytes(config: dict, itemsize: int = 2) -> int:
    """Recurrent state ONE slot holds over every delta-rule layer: the
    float32 matrices and the three conv tails in the model's dtype."""
    h, dk, dv, k = dims(config)
    return mixer_layers(config) * (
        h * dk * dv * 4 + (2 * h * dk + h * dv) * (k - 1) * itemsize)


def rule_flops(tokens: float, config: dict) -> float:
    """Matrix-unit operations of the chunked rule over ``tokens`` tokens,
    every delta-rule layer: six products a chunk and head."""
    h, dk, dv, _k = dims(config)
    per_token_head = 2.0 * (3 * dk * dv + 2 * CHUNK * dk + CHUNK * dv)
    return tokens * mixer_layers(config) * h * per_token_head


def rule_bytes(tokens: float, sequences: float, config: dict) -> float:
    """Least traffic of the rule over ``tokens`` tokens of ``sequences``
    sequences, every delta-rule layer, float32."""
    h, dk, dv, _k = dims(config)
    return 4.0 * mixer_layers(config) * (
        tokens * h * (2 * dk + 2 * dv + 2) + sequences * h * dk * dv)
