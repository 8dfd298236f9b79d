"""What this process built: one record per build of a jitted program.

Always on, process-wide, fed by ``jax.monitoring`` listeners that are
registered when this module is imported (with ``flexflow_tpu.obs``, before
the first program of the process is built). JAX reports three stages of a
build, each on the thread that builds — tracing the function into a jaxpr,
lowering the jaxpr to a module, and the backend stage, which either loads the
executable from the persistent compile cache or compiles it — and, inside the
backend stage, what the cache did. A record is appended when the backend
stage ends:

* ``name``: the module's name as the lowering and backend stages report it
  (``jit_step``, ``jit_decode``, ``jit_prefill``, ``jit_prefill_chunk``,
  ``jit_write`` for ``execution/executor.py``'s ``PROGRAM_NAMES``; whatever
  JAX calls the others). The trace stage reports the bare function name
  (``step``) and is joined to the lowering that follows it on its thread.
* ``phase``: where the program was when the build began — the innermost open
  set-up span (``obs.setup_span``: ``param_init``, ``kv_pool_alloc``, ...),
  else the entry point that was running (``fit``, ``eval``, ``serve``), else
  ``None``: a program of the caller's, not of this package.
* ``start`` / ``end`` (``time.time()``, as JAX stamps them), ``trace_s``,
  ``lower_s``, ``backend_s``.
* ``cache``: ``hit`` (loaded; ``load_s`` is the retrieval), ``miss`` (the
  cache was asked and had no entry: compiled, whether or not the result was
  then written) or ``off`` (no cache directory, or the cache was not asked).

A jitted function called inside another's trace (the routed layer's
``_shared_program``) is traced inside its caller's trace stage and has no
lowering or backend stage of its own: it makes no record, and its seconds are
counted once, in its caller's ``trace_s``. ``.lower()`` without ``.compile()``
and ``jax.eval_shape`` end before the backend stage and make no record either.

A listener is a dictionary update on each of a few hundred events a process;
a window that builds nothing calls none.
"""
from __future__ import annotations

import re
import threading
from collections import Counter
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.monitoring as _monitoring

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class Build(NamedTuple):
    name: str
    phase: Optional[str]
    start: float
    end: float
    trace_s: float
    lower_s: float
    backend_s: float
    cache: str
    load_s: float


_RECORDS: List[Build] = []
# per thread: ``depth`` of open trace and lowering stages, and the build in
# progress
# (``pending``: a dict of the fields known so far, from its first stage on)
_LOCAL = threading.local()
# the set-up spans open on this thread are ``_LOCAL.spans`` (a list);
# the entry points that are running, process-wide (the last one counts)
_ENTRIES: List[str] = []


# ------------------------------------------------------------------ phases
def current_phase() -> Optional[str]:
    spans = getattr(_LOCAL, "spans", None)
    if spans:
        return spans[-1]
    return _ENTRIES[-1] if _ENTRIES else None


def push_span(name: str) -> int:
    """A set-up span opens on this thread; the number open above it."""
    spans = getattr(_LOCAL, "spans", None)
    if spans is None:
        spans = _LOCAL.spans = []
    spans.append(name)
    return len(spans) - 1


def pop_span() -> None:
    _LOCAL.spans.pop()


def enter(name: str) -> None:
    """An entry point of the package (``fit``, ``eval``, ``serve``) starts:
    called once at its own entry, never per step or tick, and paired with
    ``leave`` in a ``finally`` of the entry point's own body — not through a
    decorator: a wrapper's frame above the calls that build the step cost
    0.6-0.9 s of a 19 s first step on the chip (PERF.md section 6, PR 39)."""
    _ENTRIES.append(name)


def leave(name: str) -> None:
    """The entry point ends. Serve loops of one process end in any order:
    the newest entry of that name goes."""
    for i in range(len(_ENTRIES) - 1, -1, -1):
        if _ENTRIES[i] == name:
            del _ENTRIES[i]
            return


# --------------------------------------------------------------- listeners
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")


def _module_name(kw) -> str:
    """JAX's ``fun_name`` as the module built from it is called (``jit(step)``
    -> ``jit_step``: ``jax._src.interpreters.mlir.sanitize_name``), which is
    what a device trace's ``XLA Modules`` line shows."""
    return _NOT_IN_A_MODULE_NAME.sub("_", str(kw.get("fun_name", "?"))
                                     ).rstrip("_")


def _begin(name: str, start: float) -> Dict[str, Any]:
    return {"name": name, "phase": current_phase(), "start": start,
            "trace_s": 0.0, "lower_s": 0.0, "cache": "off", "load_s": 0.0}


def _on_stage_start(event: str, start: float, **kw) -> None:
    """JAX's scalar event at the start of a stage (``value`` = its start).
    ``depth`` counts the trace and lowering stages open on this thread: a
    trace stage that opens inside one (a jitted function called by the
    traced one; the sub-functions a lowering traces) belongs to it."""
    depth = getattr(_LOCAL, "depth", 0)
    if event == TRACE:
        _LOCAL.depth = depth + 1
        if depth == 0:
            _LOCAL.pending = _begin(_module_name(kw), start)
        return
    if event != LOWER and event != BACKEND:
        return
    name = _module_name(kw)
    pending = getattr(_LOCAL, "pending", None)
    # the stage continues the build in progress where the names agree
    # (``step`` then ``jit_step`` then ``jit_step``); else that one ended
    # early (eval_shape, a lowering never compiled) and a new build begins
    if pending is None or not name.endswith(pending["name"]):
        outer = pending if depth else None
        pending = _LOCAL.pending = _begin(name, start)
        if outer is not None:
            # a whole build inside an open stage (a function called on
            # concrete values while its caller is traced): the caller's
            # build waits, and its stage holds these seconds too
            pending["outer"] = outer
    pending["name"] = name
    if event == LOWER:
        _LOCAL.depth = depth + 1


def _on_stage_end(event: str, start: float, end: float, **kw) -> None:
    if event == TRACE or event == LOWER:
        depth = _LOCAL.depth = getattr(_LOCAL, "depth", 1) - 1
        pending = getattr(_LOCAL, "pending", None)
        if pending is not None and (depth == 0 or event == LOWER):
            pending["trace_s" if event == TRACE else "lower_s"] = end - start
    elif event == BACKEND:
        pending = getattr(_LOCAL, "pending", None)
        if pending is not None:
            _LOCAL.pending = pending.pop("outer", None)
            _RECORDS.append(Build(end=end, backend_s=end - start, **pending))


def _on_cache_event(event: str, **kw) -> None:
    pending = getattr(_LOCAL, "pending", None)
    if pending is None:
        return
    if event == CACHE_ASKED and jax.config.jax_compilation_cache_dir:
        pending["cache"] = "miss"  # until a hit says otherwise
    elif event == CACHE_HIT:
        pending["cache"] = "hit"


def _on_duration(event: str, seconds: float, **kw) -> None:
    pending = getattr(_LOCAL, "pending", None)
    if event == CACHE_LOAD and pending is not None:
        pending["load_s"] = seconds


_LISTENERS = (
    (_monitoring.register_scalar_listener,
     _monitoring.unregister_scalar_listener, _on_stage_start),
    (_monitoring.register_event_time_span_listener,
     _monitoring.unregister_event_time_span_listener, _on_stage_end),
    (_monitoring.register_event_listener,
     _monitoring.unregister_event_listener, _on_cache_event),
    (_monitoring.register_event_duration_secs_listener,
     _monitoring.unregister_event_duration_listener, _on_duration),
)


def _register() -> None:
    for register, _, listener in _LISTENERS:
        register(listener)


def _unregister() -> None:
    """For the test that shows the listeners change no result."""
    for _, unregister, listener in _LISTENERS:
        unregister(listener)


_register()


# --------------------------------------------------------------------- API
def builds() -> Tuple[Build, ...]:
    """Every build of this process so far, in the order they ended."""
    return tuple(_RECORDS)


def build_mark() -> int:
    """A position in ``builds()``: what ``build_totals(since=...)`` counts
    from."""
    return len(_RECORDS)


def build_totals(since: int = 0) -> Dict[str, Any]:
    """The builds from ``since`` (a ``build_mark()``) on: how many, by name,
    the seconds tracing, lowering, loading from the cache (``load_s``, of the
    hits) and compiling (``compile_s``: the backend stage of the builds that
    did not hit), and the cache's hits and misses."""
    recs = _RECORDS[since:]
    return {
        "builds": len(recs),
        "by_name": dict(Counter(r.name for r in recs)),
        "trace_s": sum(r.trace_s for r in recs),
        "lower_s": sum(r.lower_s for r in recs),
        "load_s": sum(r.load_s for r in recs),
        "compile_s": sum(r.backend_s for r in recs if r.cache != "hit"),
        "hits": sum(1 for r in recs if r.cache == "hit"),
        "misses": sum(1 for r in recs if r.cache == "miss"),
    }


def built_since(mark: int) -> Tuple[int, float, Dict[str, int]]:
    """What a run's summary says of the builds since its ``build_mark()``:
    (``programs_built``, ``build_s`` — their seconds tracing, lowering,
    loading and compiling —, ``by_name``)."""
    t = build_totals(mark)
    return (t["builds"],
            t["trace_s"] + t["lower_s"] + t["load_s"] + t["compile_s"],
            t["by_name"])
