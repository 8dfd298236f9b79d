"""What a per-layer reader may need beyond the run's facts and cannot get
from them: the files of the cell that was run, and device time summed by a
rule of the reader's own (``run['trace']`` keeps the ten largest groups).
Both read what the run already wrote; where there is nothing to read they
return None and raise nothing — the readers built on them are applied to
every cell and to programs older than they are. One thing raises: a trace
that is there and does not lie where the cell's name is read from (the
run's facts carry no cell name: PERF.md section 7), since a reader that
found everything else would then report nothing and nobody would know."""
from __future__ import annotations

import json
import os
from typing import Callable, Optional

from benchmark import spans
from benchmark.reduce import xplane

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cell_config(run: dict) -> Optional[dict]:
    """The configuration's file of the cell that wrote ``run['trace_file']``
    (``.bench_trace/<cell>/plugins/profile/<time>/*.xplane.pb``: the
    directory names the cell), from ``benchmark/`` or the rehearsal cells.
    None without a trace; a trace elsewhere, or one whose directory names no
    cell, raises: ask only once the run has shown the rest of what the
    reader needs."""
    path = run.get("trace_file") or ""
    if not path:
        return None
    parts = os.path.normpath(path).split(os.sep)
    if len(parts) < 5 or parts[-4:-2] != ["plugins", "profile"]:
        raise RuntimeError(
            f"benchmark/reduce/cell.py: the trace {path!r} does not lie "
            "under <cell>/plugins/profile/<time>/: the cell's name is read "
            "from that directory")
    for base in (HERE, os.path.join(HERE, "tests", "cells")):
        cell = os.path.join(base, "workloads", f"{parts[-5]}.json")
        if not os.path.exists(cell):
            continue
        with open(cell) as f:
            config = json.load(f)["config"]
        for cbase in (base, HERE):
            cfile = os.path.join(cbase, "configs", f"{config}.json")
            if os.path.exists(cfile):
                with open(cfile) as f:
                    return json.load(f)
    raise RuntimeError(f"benchmark/reduce/cell.py: no cell {parts[-5]!r} "
                       f"(read from the trace's path {path!r}) with a "
                       "configuration file")


def device_seconds(run: dict, want: Callable[[str, str, Optional[str]], bool]
                   ) -> Optional[float]:
    """Seconds, inside the window and on the chip the run's idle share is
    judged by, of the device ops for which ``want(kind, group, scope)``
    holds: ``kind`` 'kernel' | 'collective' | 'xla' and ``group`` as
    ``xplane.classify`` names them, ``scope`` the node scope of the compiled
    text (``run['scopes']``) or None. None where nothing matched."""
    path, tr = run.get("trace_file"), run.get("trace") or {}
    if not path or "worst_device" not in tr:
        return None
    trace = xplane.load(path)
    window = [(s, e) for n, s, e in xplane.host_spans(trace, {spans.WINDOW})]
    if not window:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    scopes = run.get("scopes") or {}
    named, total = {}, 0.0
    for text, s, e in trace.chips[tr["worst_device"]]["ops"]:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if text not in named:
            kind, group = xplane.classify(text)
            named[text] = want(kind, group,
                               scopes.get(xplane.instruction(text)[0]))
        if named[text]:
            total += (e - s) * 1e-9
    return total or None


def span_arguments(run: dict, name: str):
    """The arguments of the program's spans called ``name`` on the window's
    thread, inside the window, in their order; [] where there are none."""
    from benchmark.reduce import program_spans

    path = run.get("trace_file")
    if not path or program_spans.registry() is None:
        return []
    window, main, _ = program_spans.collect(xplane.load(path), [name])
    if window is None:
        return []
    return [args for _, _, _, args in program_spans.clip_spans(main, window)]
