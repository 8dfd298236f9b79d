"""The largest relative L2 error of a compared weight's first-step gradient
against the plain reference (``drivers/train.compare``; weights and sequence
from ``CHECK_SEED``, so one value per program and cell): how far the tree
sits from ``GRAD_TOL`` (8e-2), in every ledger line and on both sides.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "check_grad_rel_err_max"
UNIT = "ratio"
LAYER = "train step numerics"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    return (run.get("check_stats") or {}).get("grad_rel_err_max")
