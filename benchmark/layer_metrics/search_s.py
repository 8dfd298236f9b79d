"""Wall of the strategy search inside ``compile()`` (``SearchResult.search_wall_s``);
nothing where compile() did not search (one chip)."""
NAME = "search_s"
UNIT = "s"
LAYER = "search"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    return run.get('search_s')
