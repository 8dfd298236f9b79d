"""From a profiler trace (``.xplane.pb``) to device busy/idle, kernel time,
collectives exposed or hidden, and idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` only. What one trace looked like by
hand under jax 0.9.0 / libtpu 0.0.34 on a v5e (``describe`` prints it):

* a chip is the plane ``/device:TPU:<n>``; ``#Chip<n> ...`` planes and
  ``/device:CUSTOM:Megascale Trace`` hold nothing we read;
* its line ``XLA Modules`` has one event per program launch, named
  ``jit_<function>(<fingerprint>)``; ``XLA Ops`` has one event per executed
  HLO instruction, **named by the instruction's whole text**
  (``%flash_attention_fwd.2 = (...) custom-call(...),
  custom_call_target="tpu_custom_call", ...``); ``Async XLA Ops`` holds the
  start-to-done spans of asynchronous copies and collectives, which overlap
  the ``XLA Ops``;
* a Mosaic kernel is an ``XLA Ops`` event whose text has
  ``custom_call_target="tpu_custom_call"``; its instruction name is the
  ``pl.pallas_call(name=...)`` plus ``.<n>``;
* the ``jax.named_scope`` path is **not** in the trace (no ``op_name``
  metadata, no ``tf_op`` stat): it is joined from the compiled program's text
  by instruction name (``scope_map``);
* host threads are lines of the plane ``/host:CPU``;
  ``jax.profiler.TraceAnnotation`` spans sit on their thread's line under
  their own name, on the same clock as the device lines (ns from the start
  of the session).

Busy time is the union of the ``XLA Ops`` intervals. Every op is exactly one
of kernel / collective / xla, so the three sum to the op time; the union can
only be smaller where ops overlap (they do not on one TensorCore).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)(-start|-done)?$")
_INSTR = re.compile(r"^%?([\w\-.]+)\s*=\s*.*?\s([\w\-]+)\(")
_NODE = re.compile(r"(?<![A-Za-z0-9_])([a-z]+)\d+_([a-z][a-z0-9]*?)(?:_\d+)?(?![A-Za-z0-9_])")


# ------------------------------------------------------- interval arithmetic
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge to disjoint, sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that no interval of ``b`` covers (both disjoint and
    sorted, as ``union`` returns them)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """Idle intervals of ``window`` given the disjoint busy intervals."""
    return subtract([window], clip(busy, window))


def exposed(collectives: Iterable[Interval],
            compute: Iterable[Interval]) -> float:
    """Seconds (in the intervals' unit) of collective time during which no
    other operation runs."""
    return total(subtract(union(collectives), union(compute)))


def attribute_gaps(gaps: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """Per gap, the name of the host span that covers most of it; of spans
    that cover it equally the shortest (the innermost), of those the first in
    ``spans``. ``host_untraced`` where none touches it."""
    return _sweep(gaps, spans)[0]


def attribute_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]
                  ) -> str:
    """``attribute_gaps`` for one gap."""
    return attribute_gaps([gap], spans)[0]


def _sweep(gaps, spans) -> Tuple[List[str], int]:
    """``attribute_gaps`` and the number of (gap, span) pairs it looked at.

    One sweep over gaps and spans, both in order of their start: a gap's
    candidates are the spans that began before it ends and had not ended
    when it began. A span that ended before a gap began has ended before
    every later one and is dropped, so where spans nest and gaps are short
    (a host thread's annotations, a device's idle gaps) a gap meets the few
    spans open around it and not the whole list: the cost follows gaps plus
    spans, not their product (a traced run of a program with a faster step
    holds more of both). The winner is the one a loop over all spans picks
    (``tests/reduce_oracle.py`` keeps that loop), digit for digit: the cover
    is the same difference of the same floats."""
    by_start = sorted(range(len(spans)), key=lambda j: spans[j][1])
    names = ["host_untraced"] * len(gaps)
    active: List[int] = []
    nxt = compared = 0
    for i in sorted(range(len(gaps)), key=lambda i: gaps[i][0]):
        lo, hi = gaps[i]
        while nxt < len(by_start) and spans[by_start[nxt]][1] < hi:
            active.append(by_start[nxt])
            nxt += 1
        best_key, best_j, still = (0.0, 0.0), -1, []
        compared += len(active)
        for j in active:
            name, s, e = spans[j]
            if e <= lo:
                continue
            still.append(j)
            cover = min(e, hi) - max(s, lo)
            if cover <= 0:
                continue
            key = (cover, -(e - s))
            if key > best_key or (key == best_key and j < best_j):
                names[i], best_key, best_j = name, key, j
        active = still
    return names, compared


# ------------------------------------------------------------------- naming
def instruction(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event's text."""
    m = _INSTR.match(text)
    if not m:
        head = text.split(" = ")[0].lstrip("%")
        return head, head.rstrip("0123456789.")
    return m.group(1), m.group(2)


def kernel_name(instr_name: str) -> str:
    """``flash_attention_fwd.3`` -> ``flash_attention_fwd``; also
    ``copy.18454.remat`` -> ``copy`` (XLA's clone and remat suffixes)."""
    return re.sub(r"(\.(\d+|remat\d*|clone\d*))+$", "", instr_name)


def classify(text: str) -> Tuple[str, str]:
    """('kernel'|'collective'|'xla', group name) of an ``XLA Ops`` event."""
    name, opcode = instruction(text)
    if 'custom_call_target="tpu_custom_call"' in text:
        return "kernel", kernel_name(name)
    coll = COLLECTIVE.match(opcode) or COLLECTIVE.match(kernel_name(name))
    if coll:
        return "collective", coll.group(1)
    return "xla", opcode


def scope_map(compiled_text: str) -> Dict[str, str]:
    """instruction name -> ``jax.named_scope`` group, read from a compiled
    program's text (``op_name="jit(step)/.../l3_fc1/dot_general"`` ->
    ``l_fc1``: the executor's per-node scope with the layer index dropped).
    Instructions without a node scope are left out."""
    out: Dict[str, str] = {}
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w\-.]+)\s*=", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        if not op:
            continue
        scope = node_scope(op.group(1))
        if scope:
            out[m.group(1)] = scope
    return out


def node_scope(op_name: str) -> Optional[str]:
    """The executor names each graph node's scope ``<layer><i>_<what>_<n>``
    (``l3_fc1_24``, ``h7_attn_52``), bare or inside ``jvp(...)`` /
    ``transpose(jvp(...))``; take the last path element that holds one and
    drop the layer index and the node number (``h7_attn_52`` -> ``h_attn``)."""
    for part in reversed(op_name.split("/")):
        m = _NODE.search(part)
        if m:
            return f"{m.group(1)}_{m.group(2)}"
    return None


# ---------------------------------------------------------------- reduction
def _event(e) -> Tuple[str, float, float]:
    start = e.start_ns
    return e.name, float(start), float(start + e.duration_ns)


def _events(line) -> List[Tuple[str, float, float]]:
    return [_event(e) for e in line.events]


class Trace:
    """One ``.xplane.pb``, read once: every host thread's events and, per
    chip that ran an op, its three lines as ``(name, start ns, end ns)``
    lists. ``reduce_trace`` and ``program_spans`` both work on this, so the
    file is parsed and its events are walked once per process, whatever
    reads it."""

    def __init__(self, data):
        self.data = data
        # per host line: (name, start, end, the event) in the line's order
        self.host_lines = [
            [_event(e) + (e,) for e in line.events]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines]
        self.chips: Dict[int, Dict[str, List[Tuple[str, float, float]]]] = {}
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if not m:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            self.chips[int(m.group(1))] = {
                key: _events(lines[name]) if name in lines else []
                for key, name in (("ops", "XLA Ops"),
                                  ("async_ops", "Async XLA Ops"),
                                  ("modules", "XLA Modules"))}
        self._busy_idle: Dict[Tuple[int, Interval], Tuple[list, list]] = {}

    def busy_idle(self, chip: int, window: Interval
                  ) -> Tuple[List[Interval], List[Interval]]:
        """The disjoint intervals inside ``window`` in which an op ran on
        ``chip``, and the gaps between them (kept: both reductions ask for
        the same window)."""
        key = (chip, window)
        if key not in self._busy_idle:
            busy = union(clip([(s, e) for _, s, e in self.chips[chip]["ops"]],
                              window))
            self._busy_idle[key] = (busy, gaps(busy, window))
        return self._busy_idle[key]


_TRACES: Dict[str, Trace] = {}


def load(path: str) -> Trace:
    """The trace at ``path``, parsed on the first call and kept."""
    if path not in _TRACES:
        import jax

        _TRACES[path] = Trace(jax.profiler.ProfileData.from_file(path))
    return _TRACES[path]


def host_spans(trace: Trace, names: Iterable[str]
               ) -> List[Tuple[str, float, float]]:
    want = set(names)
    return [(n, s, e) for line in trace.host_lines for n, s, e, _ in line
            if n in want]


def reduce_trace(path: str, span_names: Iterable[str] = (),
                 window_span: Optional[str] = None,
                 scopes: Optional[Dict[str, str]] = None,
                 top: int = 10) -> dict:
    """Reduce one trace. Times in the result are seconds.

    ``window_span`` names the host span that brackets the measured window
    (the trace's own start and stop are outside it); without it the window
    runs from the first to the last device op. ``scopes`` is ``scope_map`` of
    the programs that ran, for the breakdown's grouping.
    """
    trace = load(path)
    spans = host_spans(trace, set(span_names) | ({window_span} if window_span
                                                  else set()))
    window = None
    if window_span:
        ws = [(s, e) for n, s, e in spans if n == window_span]
        if ws:
            window = (min(s for s, _ in ws), max(e for _, e in ws))
    spans = [s for s in spans if s[0] != window_span]
    devices = {}
    for chip, lines in trace.chips.items():
        ops = lines["ops"]
        if not ops:
            continue
        w = window or (min(s for _, s, _ in ops), max(e for _, _, e in ops))
        devices[chip] = _reduce_device(
            ops, lines["async_ops"], lines["modules"], w, spans,
            scopes or {}, top, *trace.busy_idle(chip, w))
    if not devices:
        return {"devices": {}, "n_devices": 0}
    # the chip that was least busy is the one the idle share is judged by
    worst = min(devices, key=lambda d: devices[d]["busy_s"])
    out = dict(devices[worst])
    out["devices"] = {d: {"busy_s": v["busy_s"], "window_s": v["window_s"]}
                      for d, v in devices.items()}
    out["n_devices"] = len(devices)
    out["busy_mean_s"] = sum(v["busy_s"] for v in devices.values()
                             ) / len(devices)
    out["worst_device"] = worst
    return out


def _reduce_device(ops, async_ops, modules, window, spans, scopes, top, busy,
                   idle):
    lo, hi = window
    by_class = {"kernel": defaultdict(float), "collective": defaultdict(float),
                "xla": defaultdict(float)}
    groups = defaultdict(float)
    coll_iv, other_iv = [], []
    # an op's text is read once: (kind, its class's group, the breakdown's
    # group), and the text comes back in every step
    named: Dict[str, Tuple[str, str, str]] = {}
    module_of = _module_lookup(modules)
    per_module = defaultdict(lambda: {"count": 0, "busy_s": 0.0,
                                      "kernel_s": defaultdict(float),
                                      "collective_s": 0.0, "xla_s": 0.0})
    for text, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if text not in named:
            kind, group = classify(text)
            name = instruction(text)[0]
            # the node's scope where the compiled text gave one, else the
            # instruction's stem (``convert_reduce_fusion.3`` -> that fusion)
            named[text] = (kind, group, group if kind != "xla" else
                           scopes.get(name) or f"xla:{kernel_name(name)}")
        kind, group, shown = named[text]
        dur = (e - s) * 1e-9
        by_class[kind][group] += dur
        (coll_iv if kind == "collective" else other_iv).append((s, e))
        mod = per_module[module_of(s)]
        mod["busy_s"] += dur
        groups[shown] += dur
        if kind == "kernel":
            mod["kernel_s"][group] += dur
        elif kind == "collective":
            mod["collective_s"] += dur
        else:
            mod["xla_s"] += dur
    collective: Dict[str, bool] = {}
    for text, s, e in async_ops:
        if text not in collective:
            collective[text] = classify(text)[0] == "collective"
        if collective[text] and min(e, hi) > max(s, lo):
            coll_iv.append((max(s, lo), min(e, hi)))
    for name, s, e in modules:
        if lo <= s < hi:
            per_module[_module_name(name)]["count"] += 1
    names = attribute_gaps(idle, spans)
    by_span = defaultdict(float)
    for g, name in zip(idle, names):
        by_span[name] += (g[1] - g[0]) * 1e-9
    longest = sorted(range(len(idle)),
                     key=lambda i: idle[i][0] - idle[i][1])[:5]
    return {
        "busy_s": total(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "kernel_s": dict(by_class["kernel"]),
        "collective_s": dict(by_class["collective"]),
        "collective_union_s": total(union(coll_iv)) * 1e-9,
        "collective_exposed_s": exposed(coll_iv, other_iv) * 1e-9,
        "xla_s": sum(by_class["xla"].values()),
        "modules": {k: {"count": v["count"], "busy_s": v["busy_s"],
                        "kernel_s": dict(v["kernel_s"]),
                        "collective_s": v["collective_s"],
                        "xla_s": v["xla_s"]}
                    for k, v in per_module.items()},
        "device_ops": sorted(([k, v] for k, v in groups.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in by_span.items()),
                            key=lambda kv: -kv[1])[:top],
        "longest_gaps": [[names[i], (idle[i][1] - idle[i][0]) * 1e-9]
                         for i in longest],
    }


def step_xla_ms(run: dict, kind: str) -> Optional[float]:
    """Milliseconds per step of the step program's plain XLA ops (neither
    Mosaic kernel nor collective) in a run of ``kind``; the two
    ``*xla_ops_ms_per_step`` readers differ only in the kind they read."""
    if run.get('kind') != kind:
        return None
    mod = run['trace']['modules'].get(run['step_module'])
    if not mod or not run.get('steps'):
        return None
    return 1e3 * mod['xla_s'] / run['steps']


def _module_name(event_name: str) -> str:
    """``jit_step(9651596145807829782)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _module_lookup(modules):
    """A function from a time to the name of the program running then."""
    import bisect

    mods = sorted((s, e, _module_name(n)) for n, s, e in modules)
    starts = [m[0] for m in mods]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1]:
            return mods[i][2]
        return "<no module>"

    return find


# ------------------------------------------------------------------ by hand
def describe(path: str, max_names: int = 40) -> str:
    """A by-hand view of a trace: every plane and line, and per line the
    event names with count, total and first start, plus one event's stats."""
    data = load(path).data
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r} stats={dict(plane.stats)}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                out.append(f"  LINE {line.name!r}: no events")
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            out.append(f"  LINE {line.name!r}: {len(events)} events, "
                       f"span {t0:.0f}..{t1:.0f} ns")
            by = defaultdict(lambda: [0, 0.0, None])
            for e in events:
                rec = by[e.name]
                rec[0] += 1
                rec[1] += e.duration_ns
                if rec[2] is None:
                    rec[2] = e
            ranked = sorted(by.items(), key=lambda kv: -kv[1][1])[:max_names]
            for name, (n, dur, first) in ranked:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in first.stats}
                out.append(f"    {n:6d} x {dur / 1e3:12.1f} us  "
                           f"{name[:120]!r} first@{first.start_ns:.0f} "
                           f"stats={stats}")
    return "\n".join(out) + "\n"


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
