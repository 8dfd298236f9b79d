"""Model FLOP/s utilization: tokens/s times the model's forward+backward FLOPs
per token (closed form in ``benchmark/flops.py``, recomputation not counted)
over chips times the peak of ``benchmark/peaks.json``. It is
``train_tokens_per_s`` normalised so that cells compare; in the traced run it
carries the tracing overhead."""
NAME = "mfu"
UNIT = "ratio"
LAYER = "train step"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    if run.get('kind') != 'train' or not run.get('peaks'):
        return None
    return (run['tokens_per_s'] * run['flops_per_token']
            / (run['chips'] * run['peaks']['bf16_flops_per_s']))
