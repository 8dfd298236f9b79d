"""Dispatch + bookkeeping over the tick wall inside the window, from the serve
loop's own three buckets (the arithmetic of
``ServingStats.host_overhead_fraction``, on the window's deltas). A
host-clock split: its 'device' bucket is the wall of a blocking call, not
device busy time — the idle share comes from the trace."""
NAME = "host_overhead_share"
UNIT = "ratio"
LAYER = "serving host loop"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    d = run.get('delta')
    if not d:
        return None
    total = (d['host_dispatch_s'] + d['host_device_s'] + d['host_bookkeep_s']
             + d['host_overlap_s'])
    if total <= 0:
        return None
    return (d['host_dispatch_s'] + d['host_bookkeep_s']) / total
