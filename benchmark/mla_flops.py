"""The yardstick's arithmetic for a latent-attention decode read, beside
``flops.py`` and ``moe_flops.py`` and for the same reason: closed forms of
the shapes and of the keys the program COUNTED as live, kept with the
benchmark so that a later edit of the program cannot move them.

The absorbed form scores every head's ``[q~ | q_r]`` (kv_rank + rope) against
a cached row and sums the row's first kv_rank numbers: per (key, layer)
``2 * heads * (kv_rank + rope)`` and ``2 * heads * kv_rank`` FLOPs — the
model's, not the kernel's (which multiplies the row's zero padding too). The
row is moved once a (key, layer), whatever the head count, padding included.
"""
from __future__ import annotations


def latent_row_bytes(kv_rank: int, rope_dim: int, itemsize: int = 2) -> int:
    """A stored row: ``kv_rank + rope_dim`` padded to whole 128-lane tiles."""
    return -(-(kv_rank + rope_dim) // 128) * 128 * itemsize


def live_keys(kv_bytes_read: float, kv_rank: int, rope_dim: int,
              itemsize: int = 2) -> float:
    """(key, layer) pairs the program counted (``ServingStats.
    kv_bytes_read``: occupied blocks of the live slots, every layer)."""
    return kv_bytes_read / latent_row_bytes(kv_rank, rope_dim, itemsize)


def absorbed_decode_flops(keys: float, heads: int, kv_rank: int,
                          rope_dim: int) -> float:
    """Score and weighted sum of ``heads`` query rows over ``keys`` (key,
    layer) pairs."""
    return 2.0 * heads * keys * ((kv_rank + rope_dim) + kv_rank)


def expert_forward_flops(rows: float, hidden: int, intermediate: int) -> float:
    """Forward of a gated MLP over ``rows`` routed rows: three products."""
    return 2.0 * rows * 3 * hidden * intermediate


def expert_forward_bytes(rows: float, experts_read: float, hidden: int,
                         intermediate: int, itemsize: int = 2) -> float:
    """The least traffic of a decode step's grouped products: the three
    matrices of every expert that GOT A ROW (``experts_read``: counted by
    the program, summed over layers and steps), the routed rows in and
    out."""
    return float(itemsize) * (3 * experts_read * hidden * intermediate
                              + 2 * rows * hidden)


def chunk_pairs(start: int, tokens: int) -> float:
    """(query position, key) pairs of one prefill chunk of ``tokens`` rows
    from position ``start``, one layer: row ``i`` sees ``start + i + 1``."""
    return float(tokens) * start + tokens * (tokens + 1) / 2.0
