"""The recurrent state the engine allocated beside its latent pool: one
slot's state of every recurrent node (``Op.slot_state_bytes``, by VALUE
heads) times the engine's slots, read from the program's own count: a decode
tick's ``recurrent_state_bytes`` is that allocation read once and written
once, so half the largest tick's. A guard: it moves only when the cell's
slots or the state's layout do."""
NAME = "hybrid_state_gb"
UNIT = "GB"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def read(run):
    # the same reading as the older reader's, whose ``CELLS`` stop at its
    # own configuration's cells
    from benchmark.layer_metrics import gdn_state_gb

    return gdn_state_gb.read(run)
