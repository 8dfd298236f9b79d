"""Device time of everything in the train step's program (``jit_step``) that is
neither a Mosaic kernel nor a collective, per step, on the least busy chip:
the dense fusions, Adam, the bias gradients, layout copies."""
NAME = "xla_ops_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import xplane
    return xplane.step_xla_ms(run, 'train')
