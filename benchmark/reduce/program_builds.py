"""The program's own record of what it built: ``flexflow_tpu.obs.builds()``
(one record per build of a jitted program: name, phase, the seconds tracing,
lowering and in the backend, hit or miss in the compile cache) and
``flexflow_tpu.obs.setup_walls()`` (the wall of each set-up span), read at the
end of a ``--trace 1`` run for the per-layer metrics that move ``setup_s``.

Only the program's OWN builds count: a record whose ``phase`` is ``None`` was
built under no set-up span and no entry point of the program — the reference's
programs, the driver's small checks, the step's text lowered a second time —
and is the caller's. Where the program has no registry (a checkout from
before it), or reading it goes wrong, every reader built on this reports
nothing: they are additions to a run that is judged on other numbers.
"""
from __future__ import annotations

from typing import List, Optional


def own() -> Optional[List]:
    """The program's own builds so far, or None where it keeps no record."""
    try:
        from flexflow_tpu.obs import builds
    except Exception:  # no registry (ImportError) or an obs that is broken
        return None
    try:
        return [b for b in builds() if b.phase is not None]
    except Exception as e:
        print(f"[bench] program builds: nothing read ({e!r})", flush=True)
        return None


def total(field) -> Optional[float]:
    """Sum of ``field(record)`` over the program's own builds."""
    recs = own()
    return None if recs is None else float(sum(field(b) for b in recs))


def setup_host_s() -> Optional[float]:
    """The outermost set-up spans' walls less the seconds of the builds that
    began inside a set-up span: what the set-up spent outside tracing,
    lowering, loading and compiling programs."""
    recs = own()
    if recs is None:
        return None
    try:
        from flexflow_tpu.obs import setup_walls

        walls = setup_walls(outermost=True)
    except Exception as e:
        print(f"[bench] set-up walls: nothing read ({e!r})", flush=True)
        return None
    inside = sum(b.trace_s + b.lower_s + b.backend_s for b in recs
                 if b.phase in walls)
    return sum(walls.values()) - inside
