"""99th percentile of single gaps between a request's consecutive tokens inside
the window (another request's prefill stalls every live stream)."""
NAME = "tpot_p99_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    import numpy as np
    lo, hi = run.get('window_ms', (0, 0))
    gaps = []
    for times in (run.get('token_times_ms') or {}).values():
        t = np.asarray(times)
        g = np.diff(t)
        gaps.extend(g[(t[:-1] >= lo) & (t[1:] < hi)].tolist())
    return float(np.percentile(gaps, 99)) if gaps else None
