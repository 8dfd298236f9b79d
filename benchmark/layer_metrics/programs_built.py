"""Programs the process built, by its own record (``flexflow_tpu.obs.builds()``):
every build that began under a set-up span or an entry point of the program
(phase not None; the reference's and the driver's own programs are not
counted)."""
NAME = "programs_built"
UNIT = "count"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.total(lambda b: 1)
