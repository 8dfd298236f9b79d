"""The program's own spans in a traced run: where the host's time went, and
under which of them the device sat idle.

The program marks its two hot host loops — ``FFModel.fit`` with its input
pipeline, and the serve tick — with ``flexflow_tpu.obs.span`` /
``step_span``: ``jax.profiler`` annotations whose names are the registry
``flexflow_tpu.obs.SPANS``. In a ``--trace 1`` run they sit on the host
threads' lines of the ``.xplane.pb`` (``run['trace_file']``), on the clock
of the device's ``XLA Ops``. This module reads them once per process, from
the trace as ``xplane.load`` keeps it (parsed once, shared with
``xplane.reduce_trace``, the busy intervals too):

* the **main thread** is the line that holds the benchmark's ``bench_window``
  span; its program spans, clipped to the window, are what idle time is
  attributed to. Spans on other lines (the dataloader's producer thread) are
  summed, never used for attribution: a device waits for the thread that
  dispatches to it.
* the device's idle gaps are recomputed as ``xplane._reduce_device`` does
  (union of the ``XLA Ops`` intervals inside the window, on the least busy
  chip), and each gap goes to the **leaf** span that covers most of it
  (``xplane.attribute_gaps``; of equal covers the innermost). A leaf is any
  program span but the ones that only enclose others (``ENCLOSING``). A gap
  counts as attributed when leaf spans cover more than half of it; otherwise
  it falls to the enclosing span over it, or to ``host_untraced``.

Where the program has no such spans (a checkout from before they existed has
no ``obs.SPANS``), or the run has no trace, ``read`` returns None and every
reader built on it reports nothing. The readers are additions to a run that
is judged on other numbers, and are applied to programs older than they are:
whatever goes wrong in here — a registry that does not import, a trace that
does not load — is logged and reads as "no spans", and never costs the run
its result line.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmark.reduce import xplane

WINDOW = "bench_window"
# spans that bracket a whole iteration and hold the others: naming one says
# nothing about what the host did, so they are not leaves
ENCLOSING = ("serve_tick", "epoch")
PRODUCER = ("batch_gather", "batch_put", "prefetch_backpressure")
UNTRACED = "host_untraced"

Span = Tuple[str, float, float, dict]  # name, start ns, end ns, arguments

_CACHE: Dict[str, Optional[dict]] = {}


def registry():
    """The program's span registry, or None where it has none."""
    try:
        from flexflow_tpu.obs import SPANS
    except Exception:  # no registry (ImportError) or an obs that is broken
        return None
    return SPANS


def collect(trace: xplane.Trace, names
            ) -> Tuple[Optional[Tuple[float, float]], List[Span], List[Span]]:
    """(window, main-thread spans, other threads' spans) of a loaded trace;
    the window is None where no ``bench_window`` span was recorded."""
    want = set(names)
    lines = []
    window, main = None, None
    for line in trace.host_lines:
        found: List[Span] = []
        for name, s, t, event in line:
            if name == WINDOW:
                window = (s, t) if window is None else (
                    min(window[0], s), max(window[1], t))
                main = found
            elif name in want:
                found.append((name, s, t, dict(event.stats)))
        lines.append(found)
    others = [sp for found in lines if found is not main for sp in found]
    return window, list(main or []), others


def clip_spans(spans: List[Span], window) -> List[Span]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi), a) for n, s, e, a in spans
            if min(e, hi) > max(s, lo)]


def device_idle(trace: xplane.Trace, window
                ) -> Tuple[Optional[int], List[xplane.Interval]]:
    """(chip, its idle gaps inside the window) for the least busy chip, as
    ``xplane.reduce_trace`` judges the idle share; (None, []) where no chip
    ran an op."""
    best = None
    for chip in trace.chips:
        busy, idle = trace.busy_idle(chip, window)
        if not busy:
            continue
        if best is None or xplane.total(busy) < best[1]:
            best = (chip, xplane.total(busy), idle)
    if best is None:
        return None, []
    return best[0], best[2]


def attribute(gaps, main: List[Span]) -> List[Tuple[float, str, bool]]:
    """Per gap: (its seconds, the span it goes to, whether leaf spans cover
    more than half of it)."""
    return _attribute(gaps, main)[0]


def _attribute(gaps, main: List[Span]):
    """``attribute`` and the number of (gap, span) pairs it looked at: a
    bisect into the leaves' disjoint cover for the share that has a name,
    then one sweep of the named gaps over the leaves and one of the others
    over the enclosing spans (``xplane._sweep``)."""
    leaves = [(n, s, e) for n, s, e, _ in main if n not in ENCLOSING]
    outer = [(n, s, e) for n, s, e, _ in main if n in ENCLOSING]
    leaf_cover = xplane.union((s, e) for _, s, e in leaves)
    ends = [e for _, e in leaf_cover]
    compared = 0
    named = []
    for lo, hi in gaps:
        # the cover's intervals that reach into the gap, in their order:
        # what ``clip(leaf_cover, gap)`` keeps of the whole list
        k = bisect.bisect_right(ends, lo)
        part = []
        while k < len(leaf_cover) and leaf_cover[k][0] < hi:
            part.append(leaf_cover[k])
            k += 1
        compared += len(part)
        named.append(2 * xplane.total(xplane.clip(part, (lo, hi))) > hi - lo)
    names: List[Optional[str]] = [None] * len(gaps)
    for flag, spans in ((True, leaves), (False, outer)):
        index = [i for i, f in enumerate(named) if f is flag]
        got, n = xplane._sweep([gaps[i] for i in index], spans)
        compared += n
        for i, name in zip(index, got):
            names[i] = name
    return [((g[1] - g[0]) * 1e-9, name, flag)
            for g, name, flag in zip(gaps, names, named)], compared


def reduce_spans(window, main: List[Span], others: List[Span], gaps) -> dict:
    """Seconds per span name on the main thread and on the other threads,
    the ``serve_tick`` walls by kind, and the idle seconds by the span they
    were attributed to."""
    main, others = clip_spans(main, window), clip_spans(others, window)
    main_s, other_s = defaultdict(float), defaultdict(float)
    main_n = defaultdict(int)
    for n, s, e, _ in main:
        main_s[n] += (e - s) * 1e-9
        main_n[n] += 1
    for n, s, e, _ in others:
        other_s[n] += (e - s) * 1e-9
    ticks = defaultdict(list)
    for n, s, e, args in main:
        if n == "serve_tick":
            ticks[str(args.get("kind", "unknown"))].append((e - s) * 1e-9)
    idle_by, idle_s, named_s = defaultdict(float), 0.0, 0.0
    for seconds, name, named in attribute(gaps, main):
        idle_by[name] += seconds
        idle_s += seconds
        named_s += seconds if named else 0.0
    return {"window_s": (window[1] - window[0]) * 1e-9,
            "main_s": dict(main_s), "main_n": dict(main_n),
            "other_s": dict(other_s), "tick_walls_s": dict(ticks),
            "idle_s": idle_s, "idle_named_s": named_s,
            "idle_by_span": sorted(idle_by.items(), key=lambda kv: -kv[1])}


def describe(out: dict) -> List[str]:
    """The reduction as the two lines a traced run logs: the idle seconds by
    the span they went to (with the producer threads' sums), and the wall of
    every span on the window's thread (with the ticks by kind)."""
    share = 1e2 / out["window_s"]
    idle = ", ".join(f"{n} {s:.4f} s ({s * share:.2f}% of the window)"
                     for n, s in out["idle_by_span"])
    producer = ", ".join(f"{n} {out['other_s'].get(n, 0.0):.4f} s"
                         for n in PRODUCER)
    walls = ", ".join(f"{n} {s:.4f} s ({out['main_n'][n]})" for n, s in sorted(
        out["main_s"].items(), key=lambda kv: -kv[1]))
    ticks = "".join(f"; {kind} ticks: {len(v)}, {sum(v):.4f} s, median "
                    f"{1e3 * statistics.median(v):.2f} ms"
                    for kind, v in sorted(out["tick_walls_s"].items()))
    return [f"idle by program span: {idle}; idle {out['idle_s']:.4f} s of "
            f"{out['window_s']:.3f} s on chip {out['chip']}; producer "
            f"threads: {producer}",
            f"program spans on the window's thread: {walls}{ticks}"]


def read(run: dict) -> Optional[dict]:
    """``reduce_spans`` of the run's trace, once per process (logged as
    ``describe`` puts it); None where there is no trace, no registry in the
    program, no window span, or no program span on the window's thread."""
    path = run.get("trace_file")
    names = registry()
    if not path or names is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = None  # a failure below is not tried again
        try:
            _CACHE[path] = _reduce_file(path, names)
        except Exception as e:  # see the module's docstring: never the run's
            print(f"[bench] program spans: nothing read ({e!r})", flush=True)
    return _CACHE[path]


def _reduce_file(path: str, names) -> Optional[dict]:
    trace = xplane.load(path)
    window, main, others = collect(trace, names)
    if window is None or not main:
        return None
    chip, gaps = device_idle(trace, window)
    out = reduce_spans(window, main, others, gaps)
    out["chip"] = chip
    for line in describe(out):
        print(f"[bench] {line}", flush=True)
    return out


def tick_walls(run: dict) -> Optional[Dict[str, List[float]]]:
    """kind -> the walls (s) of a serving run's ``serve_tick`` spans inside
    the window; None where it is no serving run or has no such span."""
    if run.get("kind") != "serve":
        return None
    spans = read(run)
    return (spans or {}).get("tick_walls_s") or None


def per_step_ms(run: dict, name: str, thread: str) -> Optional[float]:
    """Milliseconds per train step of the spans called ``name`` inside the
    window, on the main thread (``main_s``) or the others (``other_s``)."""
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    spans = read(run)
    if spans is None or name not in spans[thread]:
        return None
    return 1e3 * spans[thread][name] / run["steps"]
