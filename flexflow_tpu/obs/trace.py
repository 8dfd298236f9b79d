"""Structured span/event tracer with Chrome trace-event export, and the
program's hot-loop spans on the profiler's clock.

The observability analog of the reference's Legion Prof integration
(``-lg:prof``) plus the per-op ``--profiling`` kernel-timing prints: nested
spans for compile / train-step / epoch / eval / search phases, instant
events and counters, exported as Chrome trace-event JSON
(Perfetto-loadable, ``chrome://tracing``) and optionally streamed to a JSONL
event sink as spans complete.

``span`` / ``step_span`` (below, with the ``SPANS`` registry) are what the two
hot host loops — ``FFModel.fit`` with its input pipeline, and the serve tick —
are instrumented with: ``jax.profiler`` annotations, so they land in the
profiler's trace on the device's clock whenever a profiler session runs
(``--profiler-trace-dir``, ``obs.start_trace``) and cost about a microsecond
when none does; with the Chrome ``Tracer`` enabled the same call also records
its complete event. ``setup_span`` is the same call for the phases of the
set-up (``compile()``, the engine's construction): it also adds its wall to
``setup_walls()`` and names the ``phase`` of the programs built inside it
(``obs/builds.py``); ``timed_span`` is what both share with the input
pipeline's counters.

Disabled-by-default design: the module-level singleton starts as a
``NoopTracer`` whose ``span()`` returns one shared, reusable null context
manager — entering it allocates nothing, so instrumented hot loops pay a
single attribute load + truth test when tracing is off. Nothing here runs
inside jitted code; all timestamps are host wall-clock (``time.perf_counter``
against the tracer's epoch).
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from types import MappingProxyType
from typing import Any, Dict, Mapping, Optional

# (the package's attribute ``builds`` is the function, not the module)
from .builds import pop_span, push_span


def atomic_write_json(path: str, obj) -> str:
    """Write ``obj`` as JSON via a same-directory temp file + rename, so a
    killed process never leaves a truncated artifact. The pid in the temp
    name keeps two concurrent writers from clobbering each other's staging
    file. Shared by every JSON artifact this subsystem emits."""
    path = os.path.abspath(path)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, default=str)
    os.replace(tmp, path)
    return path


class _NullSpan:
    """Allocation-free context manager returned by the disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NoopTracer:
    """Disabled tracer: every method is a no-op and ``span`` returns the one
    shared null context manager (no per-call allocation in hot loops)."""

    enabled = False
    events: tuple = ()

    def span(self, name: str, **args):
        return _NULL_SPAN

    def event(self, name: str, **args) -> None:
        pass

    def complete(self, name: str, wall_s: float, **args) -> None:
        pass

    def span_at(self, name: str, ts_us: float, dur_us: float,
                tid=None, **args) -> None:
        pass

    def event_at(self, name: str, ts_us: float, tid=None, **args) -> None:
        pass

    def counter(self, name: str, value) -> None:
        pass

    def to_chrome_trace(self) -> Dict[str, Any]:
        return {"traceEvents": []}

    def write(self, path: Optional[str] = None) -> None:
        pass

    def close(self) -> None:
        pass


class _Span:
    """One live span; appended to the tracer as a complete ('ph': 'X') event
    on exit. Nesting is expressed by timestamp containment, which is how the
    Chrome trace format renders stacks for same-tid complete events."""

    __slots__ = ("tracer", "name", "args", "t0")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0.0

    def __enter__(self):
        self.t0 = self.tracer._now_us()
        self.tracer._enter_span()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = self.tracer._now_us()
        depth = self.tracer._exit_span()
        self.tracer._emit({
            "name": self.name, "cat": "flexflow", "ph": "X",
            "ts": round(self.t0, 3), "dur": round(end - self.t0, 3),
            "pid": self.tracer.pid, "tid": threading.get_ident(),
            "args": dict(self.args, depth=depth) if self.args
            else {"depth": depth},
        })
        return False


class Tracer:
    """Thread-safe span/event recorder.

    * ``span(name, **args)``: context manager; emits a complete ('X') event.
    * ``event(name, **args)``: instant ('i') event.
    * ``counter(name, value)``: 'C' event Perfetto plots as a time series.
    * ``to_chrome_trace()`` / ``write(path)``: Chrome trace-event JSON.
    * ``jsonl_file``: when set, every emitted event is also appended to this
      file as one JSON object per line (the machine-readable event sink).
    """

    enabled = True

    # in-memory event cap: a multi-day fit with tracing on emits one event
    # per step — unbounded growth would eat host RAM and make every
    # trace-file rewrite slower. Oldest events roll off (the JSONL sink,
    # when set, still has them all); dropped count lands in otherData.
    DEFAULT_MAX_EVENTS = 500_000

    def __init__(self, trace_file: Optional[str] = None,
                 jsonl_file: Optional[str] = None, pid: int = 0,
                 max_events: int = DEFAULT_MAX_EVENTS):
        import collections

        self._lock = threading.Lock()
        self._local = threading.local()
        self.events = collections.deque(maxlen=max_events)
        self.dropped_events = 0
        self.trace_file = trace_file
        self.jsonl_file = jsonl_file
        self._jsonl_fh = None
        self.pid = pid
        self._t0 = time.perf_counter()

    # -- clock / span-stack internals -------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _enter_span(self) -> int:
        d = getattr(self._local, "depth", 0)
        self._local.depth = d + 1
        return d

    def _exit_span(self) -> int:
        d = getattr(self._local, "depth", 1) - 1
        self._local.depth = d
        return d

    @property
    def depth(self) -> int:
        """Current nesting depth on the calling thread."""
        return getattr(self._local, "depth", 0)

    def _emit(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if self.events.maxlen is not None and \
                    len(self.events) == self.events.maxlen:
                self.dropped_events += 1  # deque drops the oldest
            self.events.append(ev)
            if self.jsonl_file is not None:
                if self._jsonl_fh is None:
                    # line-buffered: the sink is tail-able mid-run and
                    # survives a crash without losing buffered events
                    self._jsonl_fh = open(self.jsonl_file, "a", buffering=1)
                self._jsonl_fh.write(json.dumps(ev, default=str) + "\n")

    # -- public recording API ---------------------------------------------
    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def event(self, name: str, **args) -> None:
        self._emit({"name": name, "cat": "flexflow", "ph": "i", "s": "t",
                    "ts": round(self._now_us(), 3), "pid": self.pid,
                    "tid": threading.get_ident(), "args": args})

    def complete(self, name: str, wall_s: float, **args) -> None:
        """Retroactive complete ('X') event ending now and lasting
        ``wall_s`` — for hot loops that time a phase themselves and report
        it afterwards instead of holding a span open."""
        end = self._now_us()
        self._emit({"name": name, "cat": "flexflow", "ph": "X",
                    "ts": round(max(end - wall_s * 1e6, 0.0), 3),
                    "dur": round(wall_s * 1e6, 3), "pid": self.pid,
                    "tid": threading.get_ident(), "args": args})

    def span_at(self, name: str, ts_us: float, dur_us: float,
                tid=None, **args) -> None:
        """Complete ('X') event at an EXPLICIT timestamp (µs). The
        request tracer (obs/reqtrace.py) uses this to export spans on
        the scheduler's injectable clock — deterministic under a fake
        clock — instead of the tracer's own perf_counter epoch; such
        spans carry their own time base (one pid lane per source), so
        nesting is judged within a lane, never across lanes."""
        self._emit({"name": name, "cat": "flexflow", "ph": "X",
                    "ts": round(float(ts_us), 3),
                    "dur": round(max(float(dur_us), 0.0), 3),
                    "pid": self.pid,
                    "tid": threading.get_ident() if tid is None else tid,
                    "args": args})

    def event_at(self, name: str, ts_us: float, tid=None, **args) -> None:
        """Instant ('i') event at an explicit timestamp (µs) — the
        ``event()`` analog of :meth:`span_at`."""
        self._emit({"name": name, "cat": "flexflow", "ph": "i", "s": "t",
                    "ts": round(float(ts_us), 3), "pid": self.pid,
                    "tid": threading.get_ident() if tid is None else tid,
                    "args": args})

    def counter(self, name: str, value) -> None:
        self._emit({"name": name, "cat": "flexflow", "ph": "C",
                    "ts": round(self._now_us(), 3), "pid": self.pid,
                    "tid": threading.get_ident(),
                    "args": {name: value}})

    # -- export ------------------------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        with self._lock:
            events = list(self.events)
            dropped = self.dropped_events
        other: Dict[str, Any] = {"tracer": "flexflow_tpu.obs"}
        if dropped:
            other["dropped_oldest_events"] = dropped
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": other}

    def write(self, path: Optional[str] = None) -> str:
        path = path or self.trace_file
        if not path:
            raise ValueError("no trace file path given")
        return atomic_write_json(path, self.to_chrome_trace())

    def close(self) -> None:
        with self._lock:
            if self._jsonl_fh is not None:
                self._jsonl_fh.close()
                self._jsonl_fh = None
        if self.trace_file:
            self.write(self.trace_file)


# ------------------------------------------------------------- the singleton
_TRACER = NoopTracer()


def get_tracer():
    """The process-wide tracer (NoopTracer unless ``enable()`` was called)."""
    return _TRACER


def set_tracer(tracer) -> None:
    global _TRACER
    _TRACER = tracer


def enable(trace_file: Optional[str] = None,
           jsonl_file: Optional[str] = None) -> Tracer:
    """Install (and return) a live Tracer as the process singleton. If one is
    already installed it is returned unchanged, so a config-driven enable and
    an explicit user enable compose."""
    global _TRACER
    if not _TRACER.enabled:
        _TRACER = Tracer(trace_file=trace_file, jsonl_file=jsonl_file)
    return _TRACER


def disable():
    """Swap the singleton back to the NoopTracer; returns the previous tracer
    (so a caller can still ``write()`` it). JSONL sinks are closed."""
    global _TRACER
    prev = _TRACER
    if prev.enabled:
        with prev._lock:
            if prev._jsonl_fh is not None:
                prev._jsonl_fh.close()
                prev._jsonl_fh = None
    _TRACER = NoopTracer()
    return prev


# ------------------------------------------- hot-loop spans, profiler's clock
#: Every span of the two hot host loops and of the set-up before them: name
#: -> what it brackets. ``span()`` refuses a name that is not here, so the
#: registry, docs/observability.md (scripts/check_trace_events.py reads this
#: mapping) and the benchmark's readers (benchmark/reduce/program_spans.py
#: imports it) cannot drift apart.
SPANS: Mapping[str, str] = MappingProxyType({
    # set-up (``setup_span``): each also adds its wall to ``setup_walls()``
    # and is the ``phase`` of the programs built inside it (obs/builds.py)
    "compile": "the whole of FFModel.compile()",
    "compile_graph": "compile(): layers -> PCG, fusion, the label tensor",
    "search": "the Unity search (inside compile() where it searches)",
    "compile_executor": "compile(): Executor(...) and, for a searched "
                        "pipeline, its trainer",
    "param_init": "compile(): executor.init_params + optimizer.init_state, "
                  "up to where they return (the device may still be busy)",
    "engine_build": "ServingEngine.__init__",
    "kv_pool_alloc": "_ensure_state_bootstrap / _ensure_state: the KV pool "
                     "and the decode state, once an engine",
    # FFModel.fit, main thread
    "epoch": "one pass of fit's epoch loop, set-up to fold",
    "fit_epoch_setup": "top of the epoch loop to the first q.get(): "
                       "batch_iterator(), producer thread start",
    "dataloader_wait": "one q.get() of prefetch_iterator's consumer: the "
                       "step waited for its batch",
    "train_step": "the step_fn/guard call and the cache update after it "
                  "(the dispatch; with telemetry on also its sync)",
    "epoch_fold": "device_get(epoch_metrics) + PerfMetrics.update at the "
                  "end of an epoch",
    "fit_sync": "fit's final block_until_ready(loss)",
    # prefetch_iterator's producer thread
    "batch_gather": "one next() of the source iterator: shuffled, the "
                    "gather of one batch's rows into a copy; unshuffled, "
                    "slicing views of the set (copies nothing)",
    "batch_put": "one device_put_batch(): host -> device, as sharded",
    "prefetch_backpressure": "the producer blocked on a full queue: it is "
                             "keeping up",
    # the serve tick (_ServeLoop / _AsyncServeLoop)
    "serve_tick": "one tick(); kind = prefill | prefill_chunk | decode | "
                  "idle; pipelined=1 when a step was in flight at entry; a "
                  "decode tick of a graph with routed expert layers carries "
                  "moe_pairs_here, moe_experts_live, moe_load_max_permille, "
                  "moe_bounded_steps, moe_layer_steps (counted on the "
                  "device, fetched with its tokens); one of a graph with a "
                  "recurrent node recurrent_state_bytes (slot-major state "
                  "the step read plus wrote) and recurrent_slots_live; one "
                  "of a graph with a latent-attention node latent_rows_read "
                  "(the pool rows its latent reads folded); a "
                  "prefill tick prefill_rows (the bucket's rows, padding "
                  "included) and prefill_rows_real",
    "tick_dispatch": "tick entry -> the device call is issued: deadline "
                     "sweep, next_action(), building ids",
    "prefill": "the bucket's prefill call up to and including the "
               "sampler's device_get",
    "prefill_chunk": "one chunk's call (and, on the last chunk, the "
                     "sampler's device_get; with routed expert layers that "
                     "fetch brings moe_bounded_steps, moe_layer_steps of the "
                     "chunks since the last one)",
    "slot_write": "_write_slot / _set_slot_meta: placing a prompt's KV "
                  "rows and arming the slot",
    "decode_dispatch": "_dispatch_decode + _sample: the decode step and "
                       "the sampler are issued",
    "fetch_tokens": "the host blocked on a decode step's tokens",
    "tick_bookkeep": "device return -> end of tick: commits, recycling, "
                     "trie insert",
    "tick_overlap": "async loop: host work after a decode step was issued, "
                    "hidden behind it (sampler, the previous step's commit)",
})


class _TracedSpan:
    """A profiler annotation and the Chrome ``Tracer``'s span of the same
    name, entered and left together (only built while a Tracer is on)."""

    __slots__ = ("_ann", "_span")

    def __init__(self, ann, span: _Span):
        self._ann = ann
        self._span = span

    def __enter__(self):
        self._ann.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        return self._ann.__exit__(*exc)

    def set_metadata(self, **args) -> None:
        self._ann.set_metadata(**args)
        self._span.args.update(args)


def _program_span(cls_name: str, name: str, tracer, args):
    SPANS[name]  # KeyError: register the span (and document it) first
    import jax.profiler  # loaded with jax; nothing new at start-up

    ann = getattr(jax.profiler, cls_name)(name, **args)
    t = _TRACER if tracer is None else tracer
    return _TracedSpan(ann, t.span(name, **args)) if t.enabled else ann


def span(name: str, tracer=None, **args):
    """Context manager over ``jax.profiler.TraceAnnotation(name, **args)``:
    a span on the profiler's clock, beside the device's ops, while a profiler
    session runs — the session is the switch — and a no-op of about a
    microsecond otherwise. With a Chrome ``Tracer`` enabled (``tracer``, or
    the process singleton) it records that tracer's complete event too.
    ``name`` must be in ``SPANS``; ``args`` are ints and short strings. What
    is known only inside the span goes in through ``set_metadata(**args)``
    of the entered object."""
    return _program_span("TraceAnnotation", name, tracer, args)


def step_span(name: str, step_num: int, tracer=None, **args):
    """``span`` over ``jax.profiler.StepTraceAnnotation``: xprof's step-time
    view groups device time by these."""
    return _program_span("StepTraceAnnotation", name, tracer,
                         dict(args, step_num=step_num))


# ------------------------------------- spans that also count their own wall
class _TimedSpan:
    """A span that adds its wall to ``table[key]``. The clock is read inside
    the annotation's own two edges, so the sum never exceeds the spans' in a
    trace, however the thread is scheduled."""

    __slots__ = ("_inner", "_table", "_key", "_t0")

    def __init__(self, inner, table, key):
        self._inner = inner
        self._table = table
        self._key = key

    def __enter__(self):
        self._inner.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._table[self._key] += time.perf_counter() - self._t0
        return self._inner.__exit__(*exc)

    def set_metadata(self, **args) -> None:
        self._inner.set_metadata(**args)


def timed_span(name: str, table, key, tracer=None, **args):
    """``span(name, **args)`` that also adds its wall to ``table[key]`` (a
    plain add; ``FFModel.input_stats()``'s seconds are these)."""
    return _TimedSpan(span(name, tracer, **args), table, key)


#: The set-up's spans, outermost first.
SETUP_SPANS = ("compile", "engine_build", "kv_pool_alloc", "compile_graph",
               "search", "compile_executor", "param_init")
_SETUP_WALLS = dict.fromkeys(SETUP_SPANS, 0.0)
_SETUP_WALLS_OUTERMOST = dict.fromkeys(SETUP_SPANS, 0.0)


class _SetupSpan(_TimedSpan):
    """A ``_TimedSpan`` into the process-wide set-up table that is also the
    ``phase`` of the programs built inside it (obs/builds.py)."""

    __slots__ = ("_outermost",)

    def __enter__(self):
        self._outermost = push_span(self._key) == 0
        return super().__enter__()

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._t0
        self._table[self._key] += wall
        if self._outermost:
            _SETUP_WALLS_OUTERMOST[self._key] += wall
        pop_span()
        return self._inner.__exit__(*exc)


def setup_span(name: str, tracer=None, **args):
    """``span(name, **args)`` for a phase of the set-up (``SETUP_SPANS``):
    it also adds its wall to ``setup_walls()`` and names the ``phase`` of
    every program built before it closes. Never inside a step or a tick."""
    if name not in _SETUP_WALLS:
        raise KeyError(name)
    return _SetupSpan(span(name, tracer, **args), _SETUP_WALLS, name)


def in_setup_span(name: str):
    """Decorator: the whole call is one ``setup_span(name)``."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with setup_span(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate


def setup_walls(outermost: bool = False) -> Dict[str, float]:
    """Seconds this process spent inside each set-up span so far, by name
    (always on, plain adds). ``outermost=True`` counts a span only where no
    other set-up span was open around it, so the values add up to the
    set-up's wall with nothing counted twice."""
    return dict(_SETUP_WALLS_OUTERMOST if outermost else _SETUP_WALLS)
