"""``flash_decode`` is bandwidth bound: the least time is the occupied K and V
bytes of the live slots (``ServingStats.kv_bytes_read``, counted by the
program per step from occupied blocks) over 819 GB/s; the share is that over
the kernel's measured time in the same window. Queries and outputs are
1/context of those bytes and are left out."""
NAME = "flash_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    t = run['trace']['kernel_s'].get('flash_decode')
    if not t or not run.get('peaks') or not run.get('delta'):
        return None
    least = run['delta']['kv_bytes_read'] / run['peaks']['hbm_bytes_per_s']
    return 100.0 * least / t
