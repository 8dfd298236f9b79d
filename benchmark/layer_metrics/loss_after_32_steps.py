"""``fit``'s loss at step 32 from the seed, read with telemetry on before the
traced window: it shows a change of arithmetic in the ledger (the same seed
and data order reproduce it).
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "loss_after_32_steps"
UNIT = "nats"
LAYER = "train step numerics"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    return run.get('loss_after_32_steps')
