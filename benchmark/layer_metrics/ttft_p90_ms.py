"""Due -> first token on the host, nearest-rank 90th percentile over the
requests due inside the window; one that was refused, failed or has no first
token when the run ends counts as +inf. What a user of the endpoint feels
first — and still not an end-to-end metric of this cell: at 0.25 s a decode
step the rate a chip sustains is 1.4 req/s, a window holds 60 to 70
requests, and their 90th percentile spread 3.7% in one set of six seeds and
13.7% in the next (216-276 ms). It comes back as a judged number when a
faster decode step lets a window hold the hundreds of requests a tail needs.
Until then the tail is held by a limit, not by a bound: a request whose
first token takes longer than the cell's ``limits.ttft_ms`` is ``failed``
(``drivers/serve.py``). The arrow runs the other way: the tick that delays a
first token is the gap between tokens, so ``tpot_p50_ms`` moves this.
A guard: it moves no judged metric, and ``MOVES`` names the judged metric of its
cell only because every per-layer metric has to name one.
"""
NAME = "ttft_p90_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    return run.get('ttft_p90_ms')
