"""Time, per step and on one chip's plane, in which a collective
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all) is
in flight: the union of the synchronous ops and of the start-to-done spans."""
NAME = "collective_ms_per_step"
UNIT = "ms/step"
LAYER = "collectives"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    t = run['trace'].get('collective_union_s')
    if not t or not run.get('steps'):
        return None
    return 1e3 * t / run['steps']
