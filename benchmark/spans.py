"""The benchmark's own host spans, written into the profiler's trace from the
benchmark's files around the calls into each layer (spans inside the program
are a later issue). ``span(name)`` costs one ``TraceAnnotation`` — a no-op
when no trace is being taken."""
from __future__ import annotations

NAMES = set()
WINDOW = "bench_window"


def span(name: str, **kwargs):
    import jax

    NAMES.add(name)
    return jax.profiler.TraceAnnotation(name, **kwargs)
