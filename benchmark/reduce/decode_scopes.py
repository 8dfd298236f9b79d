"""Device time of one program's ops under a rule of the reader's own:
``cell.device_seconds`` restricted to the ops that ran inside a launch of the
run's step program (``run['step_module']``, the decode step of a serving
run). The node scopes (``run['scopes']``) are read from that program's
compiled text, and another program's instructions may carry the same names:
without the restriction a chunk's ``fusion.12`` would be read under the
decode step's scope of that name. Returns None where there is nothing to
read, and raises nothing."""
from __future__ import annotations

from typing import Callable, Optional

from benchmark import spans
from benchmark.reduce import xplane


def step_program_seconds(run: dict,
                         want: Callable[[str, str, Optional[str]], bool]
                         ) -> Optional[float]:
    path, tr = run.get("trace_file"), run.get("trace") or {}
    module = run.get("step_module")
    if not path or not module or "worst_device" not in tr:
        return None
    trace = xplane.load(path)
    window = [(s, e) for n, s, e in xplane.host_spans(trace, {spans.WINDOW})]
    if not window:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    lines = trace.chips[tr["worst_device"]]
    module_of = xplane._module_lookup(lines["modules"])
    scopes = run.get("scopes") or {}
    named, total = {}, 0.0
    for text, s, e in lines["ops"]:
        s, e = max(s, lo), min(e, hi)
        if e <= s or module_of(s) != module:
            continue
        if text not in named:
            kind, group = xplane.classify(text)
            named[text] = want(kind, group,
                               scopes.get(xplane.instruction(text)[0]))
        if named[text]:
            total += (e - s) * 1e-9
    return total or None


def decode_tick_counters(run: dict, name: str) -> Optional[float]:
    """Sum over the window's decode ticks of the counter ``name`` the
    program put on its ``serve_tick`` spans; None where no tick has it."""
    from benchmark.reduce import cell

    values = [int(a[name]) for a in cell.span_arguments(run, "serve_tick")
              if name in a]
    return float(sum(values)) if values else None
