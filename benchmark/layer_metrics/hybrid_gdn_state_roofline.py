"""The decode step's delta-rule update against the memory roofline, in a
graph with grouped key heads: the least time is the recurrent state the
window's decode ticks read plus wrote (``recurrent_state_bytes`` of their
``serve_tick`` spans: every slot the program stepped, at
``Op.slot_state_bytes`` a node — by VALUE heads) over 819 GB/s; the share is
that over the time in which the decode step moved the state: the union of the
ops under ``l_gdnrule`` and the start-to-done spans of the asynchronous copies
that carry a state leaf (``f32[rows, H_v / p, d_k, p * d_v]``, ``p`` heads a
row as the state rests) — the same work whatever implements the update, as
``gdn_state_roofline`` has it for the ``olmo-hybrid-*`` cells. Other traffic
moves in those intervals too, so the share is a floor; it cannot pass 100%."""
NAME = "hybrid_gdn_state_roofline"
UNIT = "%"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def state_seconds(run):
    import re

    from benchmark import hybrid_flops, spans
    from benchmark.reduce import cell, xplane

    path, tr = run.get("trace_file"), run.get("trace") or {}
    module = run.get("step_module")
    if not path or not module or "worst_device" not in tr:
        return None
    trace = xplane.load(path)
    window = [(s, e) for n, s, e in xplane.host_spans(trace, {spans.WINDOW})]
    if not window:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    lines = trace.chips[tr["worst_device"]]
    module_of = xplane._module_lookup(lines["modules"])
    scopes = run.get("scopes") or {}
    _hk, hv, dk, dv, _k = hybrid_flops.delta_dims(cell.cell_config(run))
    # a head a row, or p heads side by side on a row's lanes
    leaf = re.compile("|".join(
        r"f32\[\d+,%d,%d,%d\]" % (hv // p, dk, p * dv)
        for p in (1, 2, 4) if hv % p == 0))

    def rule_op(text):
        return scopes.get(xplane.instruction(text)[0]) == "l_gdnrule"

    def state_copy(text):
        return leaf.search(text) is not None

    intervals = []
    for events, want in ((lines["ops"], rule_op),
                         (lines["async_ops"], state_copy)):
        wanted = {}   # an event's text comes back in every step
        for text, s, e in events:
            s, e = max(s, lo), min(e, hi)
            if e <= s or module_of(s) != module:
                continue
            if text not in wanted:
                wanted[text] = want(text)
            if wanted[text]:
                intervals.append((s, e))
    return xplane.total(xplane.union(intervals)) * 1e-9 or None


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('peaks'):
        return None
    moved = decode_scopes.decode_tick_counters(run, 'recurrent_state_bytes')
    t = state_seconds(run)
    if not moved or not t:
        return None
    return 100.0 * moved / run['peaks']['hbm_bytes_per_s'] / t
