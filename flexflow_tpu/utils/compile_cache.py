"""Where XLA's persistent compilation cache lives.

``FFModel.compile`` — which every ``ServingEngine``, example and benchmark
cell has behind it — and the script that jits before it builds a model
(``chip_smoke.py``) call :func:`ensure_compile_cache` before
the first compile, so a second process, or a second run on the same machine,
loads BERT-Large's train step and every serving bucket instead of compiling
them again.
"""
from __future__ import annotations

import os

# the checkout root (the directory that holds pyproject.toml): one fixed
# place, because a cache directory that moves between runs never hits
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return the directory
    in force. Where ``JAX_COMPILATION_CACHE_DIR`` is set nothing is set in
    code — JAX reads the variable itself and the cache is there and
    nowhere else; otherwise the cache goes to ``.jax_cache/`` in the
    checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir != CHECKOUT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
