"""Seconds the program's own builds (``flexflow_tpu.obs.builds()``, phase not
None) spent retrieving executables from the persistent compile cache
(``load_s`` of the hits)."""
NAME = "program_load_s"
UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.total(lambda b: b.load_s)
