"""Builds of the step program (``facts['step_module']``: ``jit_step`` /
``jit_decode``) by the program's own record (``flexflow_tpu.obs.builds()``,
phase not None): 1 is a program built once; the train step reads 2 until
its two signatures are one (ROADMAP.md S12)."""
NAME = "step_program_builds"
UNIT = "count"
LAYER = "entry points"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_builds
    return program_builds.total(lambda b: b.name == run['step_module'])
