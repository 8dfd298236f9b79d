"""The part of ``collective_ms_per_step`` during which no other operation runs
on that chip: what overlap or a sharded optimizer could still hide."""
NAME = "collective_exposed_ms_per_step"
UNIT = "ms/step"
LAYER = "collectives"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    if not run['trace'].get('collective_union_s') or not run.get('steps'):
        return None
    return 1e3 * run['trace']['collective_exposed_s'] / run['steps']
