#!/usr/bin/env python
"""Assert every tracer event/span name flexflow_tpu emits is documented.

Event-name drift is the observability analog of flag drift
(scripts/check_docs_flags.py): a subsystem grows a new
``tracer.event("...")`` and nobody can grep a trace for it because
docs/observability.md's event table never heard of it. This checker
extracts every name literal passed to a tracer emission method
(``span`` / ``span_at`` / ``event`` / ``event_at`` / ``complete`` /
``counter``) across the whole ``flexflow_tpu/`` package — plus the
request-trace phase-span names registered in ``reqtrace._PHASE_SPANS``
and the hot-loop span registry ``obs.trace.SPANS`` (the profiler-clock
spans of ``fit`` and the serve tick) — and requires each to appear
verbatim (whole-token) in the observability doc. Wired into tier-1 via tests/test_housekeeping_r16.py
so drift fails CI.

A few call sites build names dynamically (f-strings); those cannot be
extracted literally, so :data:`DYNAMIC_NAMES` pins the names they
expand to AND the checker asserts the dynamic call sites still exist —
deleting one without updating the pin fails the check instead of
silently shrinking coverage.

Usage: python scripts/check_trace_events.py [PACKAGE_DIR] [DOC_MD]
Exit status: 0 when every emitted name is documented, 1 otherwise
(missing names are listed on stderr).
"""
from __future__ import annotations

import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_PKG = os.path.join(_REPO, "flexflow_tpu")
DEFAULT_DOC = os.path.join(_REPO, "docs", "observability.md")

# a tracer emission with a literal name — re.S lets the name literal sit
# on the line after the open paren (multi-line call sites)
_EMIT_RE = re.compile(
    r'\.(?:span_at|event_at|span|event|complete|counter)\(\s*'
    r'"([a-z_][a-z0-9_]*)"', re.S)

# reqtrace's phase->span map: the span names are values, not call-site
# literals (the export loop passes them through a variable)
_PHASE_MAP_RE = re.compile(r"_PHASE_SPANS\s*=\s*\{(.*?)\}", re.S)
_PHASE_VAL_RE = re.compile(r':\s*"([a-z_][a-z0-9_]*)"')

# obs/trace.py's hot-loop span registry: the names are the mapping's keys
_SPANS_RE = re.compile(r"^SPANS\b[^\n]*\{(.*?)^\}", re.S | re.M)
_SPANS_KEY_RE = re.compile(r'^\s*"([a-z_][a-z0-9_]*)":', re.M)

#: dynamically-built names (f-string call sites) -> the substring that
#: must still appear in the source, so the pin cannot outlive the code
DYNAMIC_NAMES = {
    "unity_iter": '.event(f"{self.kind}_iter"',     # SearchLog kinds
    "mcmc_iter": '.event(f"{self.kind}_iter"',
    "op_profile": '.complete(f"op_profile:',        # drift per-op spans
}


def emitted_names(pkg_dir: str) -> "tuple[set, list]":
    """(literal names, stale-dynamic-pin errors) across the package."""
    names: set = set()
    sources = []
    for root, _dirs, files in os.walk(pkg_dir):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                src = f.read()
            sources.append(src)
            names.update(_EMIT_RE.findall(src))
            for m in _PHASE_MAP_RE.finditer(src):
                names.update(_PHASE_VAL_RE.findall(m.group(1)))
            for m in _SPANS_RE.finditer(src):
                names.update(_SPANS_KEY_RE.findall(m.group(1)))
    blob = "\n".join(sources)
    stale = []
    for name, marker in DYNAMIC_NAMES.items():
        if marker in blob:
            names.add(name)
        else:
            stale.append(f"dynamic pin '{name}': call site {marker!r} "
                         "no longer exists — update DYNAMIC_NAMES")
    return names, stale


def documented_in(text: str, name: str) -> bool:
    """Whole-token containment: ``prefill`` must not be satisfied by
    ``prefill_chunk`` and vice versa."""
    return re.search(r"(?<![\w-])" + re.escape(name) + r"(?![\w-])",
                     text) is not None


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    pkg_dir = argv[0] if argv else DEFAULT_PKG
    doc_md = argv[1] if len(argv) > 1 else DEFAULT_DOC
    names, stale = emitted_names(pkg_dir)
    with open(doc_md) as f:
        doc_text = f.read()
    missing = sorted(n for n in names if not documented_in(doc_text, n))
    if missing or stale:
        if missing:
            print(f"{doc_md}: {len(missing)} tracer event/span name(s) "
                  f"emitted by {pkg_dir} are undocumented:",
                  file=sys.stderr)
            for n in missing:
                print(f"  {n}", file=sys.stderr)
            print("add each to the event table in docs/observability.md",
                  file=sys.stderr)
        for s in stale:
            print(s, file=sys.stderr)
        return 1
    print(f"ok: all {len(names)} tracer event/span names emitted by "
          f"{os.path.basename(pkg_dir)}/ are documented in "
          f"{os.path.basename(doc_md)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
