"""The recurrent state the engine allocated: one slot's state of every
recurrent node (``kvcache.node_slot_bytes``) times the engine's slots. Read
from the program's own count: a decode tick's ``recurrent_state_bytes`` is
that allocation read once and written once, so half the largest tick's."""
NAME = "recurrent_state_gb"
UNIT = "GB"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["jamba2-*", "jamba-*"]


def read(run):
    from benchmark.reduce import cell
    if run.get('kind') != 'serve':
        return None
    moved = [int(a['recurrent_state_bytes'])
             for a in cell.span_arguments(run, 'serve_tick')
             if 'recurrent_state_bytes' in a]
    return max(moved) / 2 / 1e9 if moved else None
