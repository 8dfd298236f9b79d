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


def attribute_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]
                  ) -> str:
    """Name of the host span that covers most of ``gap``; of spans that
    cover it equally the shortest (the innermost). ``host_untraced`` where
    none touches it."""
    best, best_key = "host_untraced", (0.0, 0.0)
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


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
def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def host_spans(data, names: Iterable[str]) -> List[Tuple[str, float, float]]:
    want = set(names)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in want:
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return out


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
    data = load(path)
    spans = host_spans(data, set(span_names) | ({window_span} if window_span
                                                 else set()))
    window = None
    if window_span:
        ws = [(s, e) for n, s, e in spans if n == window_span]
        if ws:
            window = (min(s for s, _ in ws), max(e for _, e in ws))
    spans = [s for s in spans if s[0] != window_span]
    devices = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = _events(lines["XLA Ops"])
        if not ops:
            continue
        async_ops = (_events(lines["Async XLA Ops"])
                     if "Async XLA Ops" in lines else [])
        modules = (_events(lines["XLA Modules"])
                   if "XLA Modules" in lines else [])
        devices[int(m.group(1))] = _reduce_device(
            ops, async_ops, modules, window, spans, scopes or {}, top)
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


def _reduce_device(ops, async_ops, modules, window, spans, scopes, top):
    if window is None:
        window = (min(s for _, s, _ in ops), max(e for _, _, e in ops))
    lo, hi = window
    by_class = {"kernel": defaultdict(float), "collective": defaultdict(float),
                "xla": defaultdict(float)}
    groups = defaultdict(float)
    intervals, coll_iv, other_iv = [], [], []
    module_of = _module_lookup(modules)
    per_module = defaultdict(lambda: {"count": 0, "busy_s": 0.0,
                                      "kernel_s": defaultdict(float),
                                      "collective_s": 0.0, "xla_s": 0.0})
    for text, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        kind, group = classify(text)
        dur = (e - s) * 1e-9
        by_class[kind][group] += dur
        intervals.append((s, e))
        (coll_iv if kind == "collective" else other_iv).append((s, e))
        mod = per_module[module_of(s)]
        mod["busy_s"] += dur
        if kind == "kernel":
            mod["kernel_s"][group] += dur
            groups[group] += dur
        elif kind == "collective":
            mod["collective_s"] += dur
            groups[group] += dur
        else:
            mod["xla_s"] += dur
            # the node's scope where the compiled text gave one, else the
            # instruction's stem (``convert_reduce_fusion.3`` -> that fusion)
            name = instruction(text)[0]
            groups[scopes.get(name) or f"xla:{kernel_name(name)}"] += dur
    for text, s, e in async_ops:
        kind, _ = classify(text)
        if kind == "collective" and min(e, hi) > max(s, lo):
            coll_iv.append((max(s, lo), min(e, hi)))
    for name, s, e in modules:
        if lo <= s < hi:
            per_module[_module_name(name)]["count"] += 1
    busy = union(intervals)
    idle = gaps(busy, window)
    by_span = defaultdict(float)
    for g in idle:
        by_span[attribute_gap(g, spans)] += (g[1] - g[0]) * 1e-9
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:5]
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
        "longest_gaps": [[attribute_gap(g, spans), (g[1] - g[0]) * 1e-9]
                         for g in longest],
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
    data = load(path)
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
