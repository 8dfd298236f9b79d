"""The worst gap by which a served token's logit under the plain reference
lies below the reference's best at that position, over the warm wave's
checked requests (``drivers/serve.compare``; weights and prompts from
``CHECK_SEED``, so one value per program and cell): how far the tree sits
from ``LOGIT_GAP_TOL`` (0.06), in every ledger line and on both sides.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "check_logit_gap_max"
UNIT = "logit"
LAYER = "decode step numerics"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    return (run.get("check_stats") or {}).get("logit_gap_max")
