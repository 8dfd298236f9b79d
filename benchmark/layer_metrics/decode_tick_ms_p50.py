"""Median wall of the serve loop's decode ticks inside the window (the
program's ``serve_tick`` spans with ``kind`` ``decode``): the decode step as
the host sees it, dispatch, the wait for its tokens and the commit included.
A decode tick that follows a prefill also waits for what the prefill left on
the device."""
NAME = "decode_tick_ms_p50"
UNIT = "ms"
LAYER = "serving host loop"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    import statistics

    from benchmark.reduce import program_spans
    walls = program_spans.tick_walls(run)
    if walls is None or not walls.get('decode'):
        return None
    return 1e3 * statistics.median(walls['decode'])
