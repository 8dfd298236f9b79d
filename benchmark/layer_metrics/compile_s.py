"""Host clock around ``ff.compile()`` (less the search, reported apart) plus
the first call of each jitted shape, ending in a sync."""
NAME = "compile_s"
UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    return run.get('compile_s')
