"""Shared routing/tiling helpers for the Pallas kernels (softmax, top-k,
and the paged KV pool's reader and writer)."""
from __future__ import annotations

from typing import Optional

import jax

DEFAULT_BLOCK_ROWS = 8


def on_tpu() -> bool:
    """Is the process's first device a TPU? The one routing query behind
    every kernel gate; a failing device query propagates."""
    return jax.devices()[0].platform == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode defaults on for the CPU test mesh and is refused on
    a TPU: on the chip every Pallas call compiles through Mosaic."""
    if interpret is None:
        return not on_tpu()
    if interpret and on_tpu():
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend; kernels "
            "compile through Mosaic on the chip")
    return interpret


def pick_block_rows(rows: int, dim: int) -> int:
    """Largest row block dividing ``rows`` whose f32 working set stays
    within a conservative VMEM budget — wide rows otherwise OOM the 16 MiB
    scoped vmem (observed at 64 x 32768 in the softmax backward, where
    input + probs + grad tiles are live at once)."""
    budget = 4 * 2 ** 20  # bytes per tile
    cap = max(budget // max(dim * 4, 1), 1)
    for b in (64, 32, 16, DEFAULT_BLOCK_ROWS, 4, 2, 1):
        if b <= cap and rows % b == 0:
            return b
    return 1
