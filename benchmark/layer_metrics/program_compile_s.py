"""Seconds the program's own builds (``flexflow_tpu.obs.builds()``, phase not
None) spent in the backend stage where the cache did not serve them
(``backend_s`` of the builds that did not hit): about 0 in a warm run."""
NAME = "program_compile_s"
UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.total(lambda b: 0.0 if b.cache == 'hit' else b.backend_s)
