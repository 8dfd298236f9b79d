"""The yardstick's arithmetic that no configuration owns: the table of peaks
and the attention kernels' FLOPs and bytes. Closed forms of the shapes alone,
kept here so that a later edit of the program cannot move them.
Recomputation is never counted. A model's FLOPs per token are in its
``reference/<config>.py``.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind raises."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no device_kind {device_kind!r} "
            f"(knows {[k for k in table if not k.startswith('_')]}); add it "
            f"with its source, never a default")
    return table[device_kind]


# ------------------------------------------------------------------ kernels
def flash_fwd_flops(b: int, h: int, sq: int, sk: int, d: int,
                    causal: bool = False) -> float:
    """QK^T and PV: 4 * b * h * sq * sk * d, halved under a causal mask."""
    f = 4.0 * b * h * sq * sk * d
    return f / 2 if causal else f


def flash_bwd_flops(b: int, h: int, sq: int, sk: int, d: int,
                    causal: bool = False) -> float:
    """dV, dP, dQ, dK: four products the algorithm needs (the recomputed
    QK^T is recomputation and is not counted): 8 * b * h * sq * sk * d."""
    f = 8.0 * b * h * sq * sk * d
    return f / 2 if causal else f


def flash_fwd_bytes(b: int, h: int, sq: int, sk: int, d: int,
                    itemsize: int = 2) -> float:
    """Read q, k, v once, write o once and the f32 log-sum-exp."""
    return itemsize * b * h * d * (2 * sq + 2 * sk) + 4.0 * b * h * sq


def flash_bwd_bytes(b: int, h: int, sq: int, sk: int, d: int,
                    itemsize: int = 2) -> float:
    """Read q, k, v, o, do and the log-sum-exp; write dq, dk, dv."""
    return itemsize * b * h * d * (4 * sq + 4 * sk) + 4.0 * b * h * sq


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, 'compute'|'bandwidth'): the larger of FLOPs over the
    peak rate and bytes over the peak bandwidth. The published peak is the
    bound even where head_dim 64 fills half of the MXU's 128-deep
    contraction — the share then reads at most ~0.5 and the metric's file
    says so."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bandwidth")
