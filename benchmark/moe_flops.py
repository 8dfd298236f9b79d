"""The yardstick's arithmetic for a routed expert layer's grouped matrix
products, beside ``flops.py`` and for the same reason: closed forms of the
shapes and of the rows the program COUNTED as routed here, kept with the
benchmark so that a later edit of the program cannot move them.
Recomputation is never counted."""
from __future__ import annotations


def expert_matmul_flops(rows: float, hidden: int, intermediate: int) -> float:
    """Forward + backward of a gated MLP over ``rows`` routed rows: three
    products of 2 * rows * hidden * intermediate forward, and twice that
    backward (towards the rows and towards the weights):
    6 * rows * 3 * hidden * intermediate."""
    return 6.0 * rows * 3 * hidden * intermediate


def expert_matmul_bytes(rows: float, experts: int, hidden: int,
                        intermediate: int, itemsize: int = 2) -> float:
    """The least traffic: the held experts' three matrices once, the routed
    rows in once and out once."""
    return float(itemsize) * (3 * experts * hidden * intermediate
                              + 2 * rows * hidden)
