"""Output tokens committed between the window's two edges over the time
between them (``ServingStats.tokens_generated``, deltas). Below the knee this
is the offered load, and a window of 45 s against requests that live 29 s
commits 0.79 to 1.19 of what fell due in it (the requests in flight at its
edges), so it is no end-to-end metric of this cell and no check either: a
server that falls behind shows in ``failed`` (a first token over its limit).
It becomes the judged number of a cell above the knee, under a name of its
own.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "window_tokens_per_s"
UNIT = "tokens/s"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    d = run.get('delta')
    if not d or not run.get('measured_s'):
        return None
    return d['tokens_generated'] / run['measured_s']
