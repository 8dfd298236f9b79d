"""Share of decode-slot-steps inside the window that produced a kept token
(the arithmetic of ``ServingStats.batch_occupancy``, on the window's deltas)."""
NAME = "batch_occupancy"
UNIT = "ratio"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    d = run.get('delta')
    if not d or not d['decode_steps']:
        return None
    return max(d['tokens_generated'] - d['prefills'], 0) \
        / (d['decode_steps'] * run['n_slots'])
