"""Due -> admitted into a slot, 90th percentile over the requests due inside
the window: the generator's due time to the driver loop's submit, plus
``RequestRecord.queue_ms`` (submit -> admission edge). It shows where a first
token's wait was spent.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "queue_p90_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    import numpy as np
    recs = run.get('request_records')
    if not recs:
        return None
    waits = []
    for rec in recs:
        k = run['rids'].get(rec['rid'])
        if k is None or run['submit_ms'].get(k) is None:
            continue
        waits.append(run['submit_ms'][k] - run['due_ms'][k] + rec['queue_ms'])
    return float(np.percentile(waits, 90)) if waits else None
