"""The attribution and the two reductions as they were before PR 33, kept as
the oracle of ``test_reduce_equal.py`` and for nothing else: a loop over all
spans for every gap (``attribute_gap``, ``attribute``), a load of the trace
and a walk over its events for each of the two reductions. The sweep in
``reduce/xplane.py`` has to give what these give, in every field and digit.
The interval arithmetic and the naming rules (``union``, ``clip``, ``gaps``,
``classify``, ...) were not changed and are taken from the live module."""
from collections import defaultdict

from benchmark.reduce import program_spans as P
from benchmark.reduce import xplane as X


def attribute_gap(gap, spans) -> str:
    best, best_key = "host_untraced", (0.0, 0.0)
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def attribute(gaps, main):
    leaves = [(n, s, e) for n, s, e, _ in main if n not in P.ENCLOSING]
    outer = [(n, s, e) for n, s, e, _ in main if n in P.ENCLOSING]
    leaf_cover = X.union((s, e) for _, s, e in leaves)
    out = []
    for g in gaps:
        covered = X.total(X.clip(leaf_cover, g))
        named = 2 * covered > g[1] - g[0]
        name = attribute_gap(g, leaves if named else outer)
        out.append(((g[1] - g[0]) * 1e-9, name, named))
    return out


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _load(path):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def _host_spans(data, names):
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


def reduce_trace(path, span_names=(), window_span=None, scopes=None, top=10):
    data = _load(path)
    spans = _host_spans(data, set(span_names) | ({window_span} if window_span
                                                  else set()))
    window = None
    if window_span:
        ws = [(s, e) for n, s, e in spans if n == window_span]
        if ws:
            window = (min(s for s, _ in ws), max(e for _, e in ws))
    spans = [s for s in spans if s[0] != window_span]
    devices = {}
    for plane in data.planes:
        m = X.DEVICE_PLANE.match(plane.name)
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
    module_of = X._module_lookup(modules)
    per_module = defaultdict(lambda: {"count": 0, "busy_s": 0.0,
                                      "kernel_s": defaultdict(float),
                                      "collective_s": 0.0, "xla_s": 0.0})
    for text, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        kind, group = X.classify(text)
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
            name = X.instruction(text)[0]
            groups[scopes.get(name) or f"xla:{X.kernel_name(name)}"] += dur
    for text, s, e in async_ops:
        kind, _ = X.classify(text)
        if kind == "collective" and min(e, hi) > max(s, lo):
            coll_iv.append((max(s, lo), min(e, hi)))
    for name, s, e in modules:
        if lo <= s < hi:
            per_module[X._module_name(name)]["count"] += 1
    busy = X.union(intervals)
    idle = X.gaps(busy, window)
    by_span = defaultdict(float)
    for g in idle:
        by_span[attribute_gap(g, spans)] += (g[1] - g[0]) * 1e-9
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:5]
    return {
        "busy_s": X.total(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "kernel_s": dict(by_class["kernel"]),
        "collective_s": dict(by_class["collective"]),
        "collective_union_s": X.total(X.union(coll_iv)) * 1e-9,
        "collective_exposed_s": X.exposed(coll_iv, other_iv) * 1e-9,
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


def _collect(data, names):
    want = set(names)
    lines = []
    window, main = None, None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = []
            for e in line.events:
                if e.name == P.WINDOW:
                    s, t = float(e.start_ns), float(e.start_ns
                                                    + e.duration_ns)
                    window = (s, t) if window is None else (
                        min(window[0], s), max(window[1], t))
                    main = found
                elif e.name in want:
                    found.append((e.name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  dict(e.stats)))
            lines.append(found)
    others = [sp for found in lines if found is not main for sp in found]
    return window, list(main or []), others


def _device_idle(data, window):
    best = None
    for plane in data.planes:
        m = X.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not ops:
            continue
        busy = X.union(X.clip([(s, e) for _, s, e in _events(ops[0])],
                              window))
        if not busy:
            continue
        if best is None or X.total(busy) < best[1]:
            best = (int(m.group(1)), X.total(busy), busy)
    if best is None:
        return None, []
    return best[0], X.gaps(best[2], window)


def reduce_spans(window, main, others, gaps):
    main, others = P.clip_spans(main, window), P.clip_spans(others, window)
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


def reduce_file(path, names):
    """``program_spans._reduce_file`` without its two log lines."""
    data = _load(path)
    window, main, others = _collect(data, names)
    if window is None or not main:
        return None
    chip, gaps = _device_idle(data, window)
    out = reduce_spans(window, main, others, gaps)
    out["chip"] = chip
    return out
