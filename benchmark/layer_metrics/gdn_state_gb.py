"""The recurrent state the engine allocated for the delta-rule mixers: one
slot's state of every recurrent node (``Op.slot_state_bytes``: the LOGICAL
bytes; the chip rests a 192-lane row in 256) times the engine's slots. Read
from the program's own count: a decode tick's ``recurrent_state_bytes`` is
that allocation read once and written once, so half the largest tick's.
(``recurrent_state_gb`` is the same reading and admits the ``jamba2-*`` cells
alone; a later ``benchmark`` PR merges the two.)"""
NAME = "gdn_state_gb"
UNIT = "GB"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["olmo-hybrid-*"]


def read(run):
    from benchmark.reduce import cell
    if run.get('kind') != 'serve':
        return None
    moved = [int(a['recurrent_state_bytes'])
             for a in cell.span_arguments(run, 'serve_tick')
             if 'recurrent_state_bytes' in a]
    return max(moved) / 2 / 1e9 if moved else None
