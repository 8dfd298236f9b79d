"""Share of the device's idle seconds inside the window that lie in gaps
which the program's own leaf spans (``flexflow_tpu.obs.SPANS`` less the
enclosing ``serve_tick`` and ``epoch``) cover for more than half their
length: how much of the idle time has a name (``[bench] idle by program
span`` prints the names). Judges the tracing, not the program.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "idle_attributed_share"
UNIT = "ratio"
LAYER = "device"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_spans
    spans = program_spans.read(run)
    if spans is None or not spans['idle_s']:
        return None
    return spans['idle_named_s'] / spans['idle_s']
