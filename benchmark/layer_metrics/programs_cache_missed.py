"""Of the program's own builds (``flexflow_tpu.obs.builds()``, phase not None),
those the persistent compile cache did not serve (``cache`` ``miss`` or
``off``): 0 is a warm start, all of ``programs_built`` a cold one, between
the two a half-warm one."""
NAME = "programs_cache_missed"
UNIT = "count"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.total(lambda b: b.cache != 'hit')
