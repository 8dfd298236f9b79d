"""Admitted-by-the-driver-loop time minus due time, 99th percentile: a starved
generator must not read as a fast server. It is inside ``ttft_p90_ms``.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "generator_lag_p99_ms"
UNIT = "ms"
LAYER = "load generator"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    import numpy as np
    lag = run.get('generator_lag_ms')
    return float(np.percentile(lag, 99)) if lag else None
