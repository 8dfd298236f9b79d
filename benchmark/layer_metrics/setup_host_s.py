"""What the set-up spent outside building programs: the walls of the program's
outermost set-up spans (``flexflow_tpu.obs.setup_walls(outermost=True)``:
``compile``, ``engine_build``, ``kv_pool_alloc``) less the seconds of the
builds that began inside them — graph, search, executor, parameter init,
the pool's allocation."""
NAME = "setup_host_s"
UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.setup_host_s()
