"""Least time of the decode steps' grouped matrix products over their
measured time (the ops under ``l_moeexperts`` and the ``ragged-dot*`` kernels
of the decode step, the ``conditional`` wrappers that span them left out:
``decode_expert_matmul_roofline`` counts those too and reads half), with
EVERY held expert live: FLOPs = the forward
products over the rows the program counted as routed here
(``moe_pairs_here`` of its decode ticks' spans), bytes = the three matrices
of every held expert that GOT A ROW (``moe_experts_live``) and the rows in
and out (``benchmark/hybrid_flops.py``), the larger of the two over the
chip's peaks. With some 85 slots live every one of the 16 held experts gets
rows every step, so the bytes are all of the held matrices: the regime the
``openpangu-*`` cell's three live experts never reach. Under the async loop a
tick's span carries the PREVIOUS step's counters: the window's sums shift by
one step of 192."""
NAME = "hybrid_expert_matmul_roofline"
UNIT = "%"
LAYER = "expert layer"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def read(run):
    from benchmark import flops, hybrid_flops
    from benchmark.reduce import cell, decode_scopes
    if run.get('kind') != 'serve' or not run.get('peaks'):
        return None
    rows = decode_scopes.decode_tick_counters(run, 'moe_pairs_here')
    live = decode_scopes.decode_tick_counters(run, 'moe_experts_live')
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: group not in (
            "conditional", "while", "call") and (
            scope == 'l_moeexperts'
            or (kind == 'kernel' and group.startswith('ragged-dot'))))
    if not rows or not live or not t:
        return None
    config = cell.cell_config(run)
    least, _ = flops.roofline_seconds(
        hybrid_flops.expert_flops(rows, config),
        hybrid_flops.expert_bytes(rows, live, config), run['peaks'])
    return 100.0 * least / t
