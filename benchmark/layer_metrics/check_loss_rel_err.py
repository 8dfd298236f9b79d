"""|system - reference| / reference of the first step's loss on the check
sequence (``drivers/train.compare``; weights and sequence from
``CHECK_SEED``, so one value per program and cell): how far the tree sits
from ``LOSS_TOL`` (1.5e-2), in every ledger line and on both sides.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "check_loss_rel_err"
UNIT = "ratio"
LAYER = "train step numerics"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    return (run.get("check_stats") or {}).get("loss_rel_err")
