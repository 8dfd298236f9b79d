"""The yardstick's arithmetic for a selective state-space (Mamba-1) mixer,
beside ``flops.py``, ``moe_flops.py`` and ``mla_flops.py`` and for the same
reason: closed forms of the shapes and of what the program COUNTED, kept with
the benchmark so that a later edit of the program cannot move them.

With ``E`` the inner width (``mamba_expand * hidden_size``), ``N`` the state
size, ``K`` the conv width: a slot carries an ``(N, E)`` float32 state and
``K - 1`` conv inputs of ``E`` in the model's dtype, per mixer layer. The
recurrence of one token and layer reads ``x`` and ``dt`` (``E`` each) and
``B`` and ``C`` (``N`` each) and writes ``y`` (``E``), in float32 — the
scan's own precision, whatever implements it; a sequence's final state is
written once. The recurrence multiplies and adds on the vector unit, for
which ``peaks.json`` has no published figure: its shares are of the memory
roofline alone.
"""
from __future__ import annotations


def inner_width(config: dict) -> int:
    return int(config["mamba_expand"]) * int(config["hidden_size"])


def mixer_layers(config: dict) -> int:
    """Layers that are mixers: all but ``i % period == offset``."""
    period, offset = (int(config["attn_layer_period"]),
                      int(config["attn_layer_offset"]))
    return sum(1 for i in range(int(config["num_hidden_layers"]))
               if i % period != offset)


def slot_state_bytes(config: dict, itemsize: int = 2) -> int:
    """Recurrent state ONE slot holds over every mixer layer: the float32
    state and the conv tail in the model's dtype."""
    e = inner_width(config)
    return mixer_layers(config) * (
        e * int(config["mamba_d_state"]) * 4
        + e * (int(config["mamba_d_conv"]) - 1) * itemsize)


def scan_bytes(tokens: float, sequences: float, config: dict) -> float:
    """Least traffic of the recurrence over ``tokens`` real tokens of
    ``sequences`` sequences, every mixer layer: ``x``, ``dt``, ``B``, ``C``
    read and ``y`` written a token, the final state written a sequence."""
    e, n = inner_width(config), int(config["mamba_d_state"])
    return 4.0 * mixer_layers(config) * (
        tokens * (3 * e + 2 * n) + sequences * e * n)


def scan_flops(tokens: float, config: dict) -> float:
    """Vector-unit operations of the recurrence (no matrix unit): per token,
    layer and state element ``dt * A``, ``exp``, ``* S``, ``dtx * B``, ``+``,
    ``* C``, ``+``."""
    return 7.0 * tokens * mixer_layers(config) * inner_width(config) \
        * int(config["mamba_d_state"])
