"""The yardstick's arithmetic for a hybrid graph that holds a latent pool and
a delta-rule state side by side over a routed layer (the ``gigachat*``
cells), beside ``delta_flops.py``, ``mla_flops.py`` and ``moe_flops.py`` and
for the same reason: closed forms of the shapes and of what the program
COUNTED, kept with the benchmark so that a later edit of the program cannot
move them. What differs from those files' forms is the delta-rule mixer's
GROUPED key heads (``delta_flops.dims`` reads the key-head count for every
width): ``H_k`` key heads (``linear_num_key_heads``) under ``H_v`` value
heads (``linear_num_value_heads``), the state, ``v``, ``g`` and ``beta`` a
VALUE head, ``q`` and ``k`` a KEY head.

The chunked rule's matrix-unit operations (``C = 64`` tokens a chunk), the
least a grouped rule needs: ``K K^T`` and ``Q K^T`` (``C x d_k x C``) once a
KEY head (value heads that share a key head share both), ``K S`` and ``Q S``
(``C x d_k x d_v``), ``P U`` (``C x C x d_v``) and ``K^T U`` (``d_k x C x
d_v``) a VALUE head — two operations a multiply-add, whatever passes a
float32 product takes on a bf16 unit; the triangular solve is on the vector
unit and is not counted. Its least traffic: ``q`` and ``k`` read at the key
heads' width, ``v``, ``g``, ``beta`` read and ``o`` written at the value
heads', float32, and a sequence's final state written once.
"""
from __future__ import annotations

from benchmark import mla_flops

CHUNK = 64


def delta_dims(config: dict):
    """(H_k, H_v, d_k, d_v, K) of the configuration's delta-rule layers."""
    return (int(config["linear_num_key_heads"]),
            int(config["linear_num_value_heads"]),
            int(config["linear_key_head_dim"]),
            int(config["linear_value_head_dim"]),
            int(config["linear_conv_kernel_dim"]))


def latent_layers(config: dict) -> int:
    return len(set(config["full_attention_layers"]))


def delta_layers(config: dict) -> int:
    """Layers that are delta-rule mixers: all but the latent ones."""
    return int(config["num_hidden_layers"]) - latent_layers(config)


def slot_state_bytes(config: dict, itemsize: int = 2) -> int:
    """Recurrent state ONE slot holds over every delta-rule layer: a float32
    matrix a VALUE head and the three conv tails (keys at the key heads'
    width) in the model's dtype."""
    hk, hv, dk, dv, k = delta_dims(config)
    return delta_layers(config) * (
        hv * dk * dv * 4 + (2 * hk * dk + hv * dv) * (k - 1) * itemsize)


def rule_flops(tokens: float, config: dict) -> float:
    """Matrix-unit operations of the chunked grouped rule over ``tokens``
    tokens, every delta-rule layer."""
    hk, hv, dk, dv, _k = delta_dims(config)
    per_token = 2.0 * (hk * 2 * CHUNK * dk
                       + hv * (3 * dk * dv + CHUNK * dv))
    return tokens * delta_layers(config) * per_token


def rule_bytes(tokens: float, sequences: float, config: dict) -> float:
    """Least traffic of the grouped rule over ``tokens`` tokens of
    ``sequences`` sequences, every delta-rule layer, float32."""
    hk, hv, dk, dv, _k = delta_dims(config)
    return 4.0 * delta_layers(config) * (
        tokens * (2 * hk * dk + 2 * hv * dv + 2 * hv)
        + sequences * hv * dk * dv)


def latent_row_bytes(config: dict, itemsize: int = 2) -> int:
    """A stored latent row, padding included (576 numbers on 640 lanes)."""
    return mla_flops.latent_row_bytes(int(config["kv_lora_rank"]),
                                      int(config["qk_rope_head_dim"]),
                                      itemsize)


def absorbed_read_flops(rows: float, config: dict) -> float:
    """Score and weighted sum of every head's absorbed query over ``rows``
    (row, layer) pairs the program counted (``latent_rows_read``)."""
    return mla_flops.absorbed_decode_flops(
        rows, int(config["num_attention_heads"]),
        int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"]))


def expert_flops(rows: float, config: dict) -> float:
    return mla_flops.expert_forward_flops(
        rows, int(config["hidden_size"]),
        int(config["moe_intermediate_size"]))


def expert_bytes(rows: float, experts_live: float, config: dict) -> float:
    """The three matrices of every held expert that GOT A ROW and the
    routed rows in and out."""
    return mla_flops.expert_forward_bytes(
        rows, experts_live, int(config["hidden_size"]),
        int(config["moe_intermediate_size"]))
