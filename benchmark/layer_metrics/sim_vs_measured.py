"""The simulator's step time for the plan ``compile()`` chose, from the machine
model compile() itself used (analytic, no device calibration), over the
measured step. 1 is a perfect model; a model wrong by more than the margins
it decides on picks worse plans."""
NAME = "sim_vs_measured"
UNIT = "ratio"
LAYER = "search"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    if run.get('kind') != 'train' or not run.get('sim_step_s'):
        return None
    return run['sim_step_s'] / run['step_s']
