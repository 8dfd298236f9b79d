"""Least time of the decode steps' grouped matrix products over their
measured time (the ops under ``l_moeexperts`` and the ``ragged-dot*``
kernels of the decode step). Least time: the larger of FLOPs over 197
TFLOP/s and bytes over 819 GB/s (``benchmark/mla_flops.py``): FLOPs = the
forward products over the rows the program COUNTED as routed here
(``moe_pairs_here`` of its decode ticks' spans, summed over layers and the
window's steps); bytes = the three matrices of every held expert that GOT A
ROW (``moe_experts_live``, counted on the device the same way) and the rows
in and out. At one or two rows an expert the products are bandwidth bound
and a held expert with no row need not be read: counting all sixteen where
the program skips the empty ones would read over 100%."""
NAME = "decode_expert_matmul_roofline"
UNIT = "%"
LAYER = "expert layer"
MOVES = "tpot_p50_ms"
CELLS = ["openpangu-*", "pangu-*"]


def read(run):
    from benchmark import flops, mla_flops
    from benchmark.reduce import cell, decode_scopes
    if run.get('kind') != 'serve' or not run.get('peaks'):
        return None
    rows = decode_scopes.decode_tick_counters(run, 'moe_pairs_here')
    live = decode_scopes.decode_tick_counters(run, 'moe_experts_live')
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: scope == 'l_moeexperts'
        or (kind == 'kernel' and group.startswith('ragged-dot')))
    if not rows or not live or not t:
        return None
    config = cell.cell_config(run)
    hidden = int(config['hidden_size'])
    inter = int(config['moe_intermediate_size'])
    least, _ = flops.roofline_seconds(
        mla_flops.expert_forward_flops(rows, hidden, inter),
        mla_flops.expert_forward_bytes(rows, live, hidden, inter),
        run['peaks'])
    return 100.0 * least / t
