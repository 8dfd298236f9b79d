"""Share of the serve loop's tick wall inside the window that went to ticks
which prefilled (the program's ``serve_tick`` spans with ``kind`` ``prefill``
or ``prefill_chunk``) and not to decode steps: the part of every running
request's token gap that is another request's prompt."""
NAME = "prefill_tick_share"
UNIT = "ratio"
LAYER = "serving host loop"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_spans
    walls = program_spans.tick_walls(run)
    if walls is None:
        return None
    prefill = sum(walls.get('prefill', [])) + sum(walls.get('prefill_chunk', []))
    return prefill / sum(sum(v) for v in walls.values())
