"""Device durations of ``flash_decode`` events over the decode steps of the window."""
NAME = "flash_decode_ms_per_step"
UNIT = "ms/step"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    t = run['trace']['kernel_s'].get('flash_decode')
    if not t or not run.get('steps'):
        return None
    return 1e3 * t / run['steps']
