"""Weight initializers.

Analog of the reference's initializer hierarchy (src/runtime/initializer.cc:349,
kernels in initializer_kernel.cu). Each initializer is a small object with
``__call__(key, shape, dtype) -> jnp.ndarray`` so weight creation is a pure jax
function that can be jitted with output shardings (giving sharded init for free,
where the reference launches per-shard Legion tasks).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


class Initializer:
    def __call__(self, key, shape: Sequence[int], dtype):
        raise NotImplementedError

    def _seeded(self, key):
        """Mix the initializer's own seed into the executor-provided key so
        two initializers with different seeds give different weights (the
        reference seeds each initializer task with its own seed,
        initializer.cc)."""
        seed = getattr(self, "seed", 0)
        if not seed:
            return key
        import jax

        return jax.random.fold_in(key, seed)


class GlorotUniformInitializer(Initializer):
    """Xavier/Glorot uniform (reference: initializer.cc GlorotUniform).
    ``stacked``: the leading dims index independent matrices (one per
    expert), not a receptive field: the fans are the last two dims alone."""

    def __init__(self, seed: int = 0, stacked: bool = False):
        self.seed = seed
        self.stacked = stacked

    @staticmethod
    def _fans(shape: Tuple[int, ...]) -> Tuple[int, int]:
        if len(shape) < 1:
            return 1, 1
        if len(shape) == 1:
            return shape[0], shape[0]
        if len(shape) == 2:
            return shape[0], shape[1]
        # conv kernels (H, W, Cin, Cout)
        receptive = int(np.prod(shape[:-2]))
        return shape[-2] * receptive, shape[-1] * receptive

    def __call__(self, key, shape, dtype):
        import jax

        fan_in, fan_out = self._fans(tuple(shape[-2:] if self.stacked
                                           else shape))
        limit = float(np.sqrt(6.0 / max(fan_in + fan_out, 1)))
        return jax.random.uniform(self._seeded(key), tuple(shape), dtype,
                                  -limit, limit)


class ZeroInitializer(Initializer):
    def __call__(self, key, shape, dtype):
        import jax.numpy as jnp

        return jnp.zeros(tuple(shape), dtype)


class ConstantInitializer(Initializer):
    def __init__(self, value: float):
        self.value = value

    def __call__(self, key, shape, dtype):
        import jax.numpy as jnp

        return jnp.full(tuple(shape), self.value, dtype)


class UniformInitializer(Initializer):
    def __init__(self, seed: int = 0, min_val: float = 0.0, max_val: float = 1.0):
        self.seed = seed
        self.min_val = min_val
        self.max_val = max_val

    def __call__(self, key, shape, dtype):
        import jax

        return jax.random.uniform(self._seeded(key), tuple(shape), dtype,
                                  self.min_val, self.max_val)


class NormInitializer(Initializer):
    def __init__(self, seed: int = 0, mean: float = 0.0, stddev: float = 1.0):
        self.seed = seed
        self.mean = mean
        self.stddev = stddev

    def __call__(self, key, shape, dtype):
        import jax

        return self.mean + self.stddev * jax.random.normal(
            self._seeded(key), tuple(shape), dtype)


DefaultWeightInitializer = GlorotUniformInitializer
DefaultBiasInitializer = ZeroInitializer
