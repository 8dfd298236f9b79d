"""``device.memory_stats()['peak_bytes_in_use']`` after the window, fullest
chip: shows that the cell fills the chip ("memory is full").
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "peak_hbm_gb"
UNIT = "GB"
LAYER = "device"
MOVES = "setup_s"
CELLS = ["*"]


def read(run):
    b = run.get('memory_peak_bytes')
    return b / 1e9 if b else None
