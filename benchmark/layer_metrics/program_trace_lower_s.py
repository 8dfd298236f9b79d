"""Seconds the program's own builds (``flexflow_tpu.obs.builds()``, phase not
None) spent tracing functions to jaxprs and lowering them to modules:
``trace_s + lower_s``, paid warm or cold."""
NAME = "program_trace_lower_s"
UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.total(lambda b: b.trace_s + b.lower_s)
