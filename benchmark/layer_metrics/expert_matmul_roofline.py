"""Least time of the routed layers' grouped matrix products of one step over
their measured time (the ``ragged-dot*`` kernels: forward, recomputation and
backward). Least time: the larger of FLOPs over 197 TFLOP/s and bytes over
819 GB/s (closed forms in ``benchmark/moe_flops.py``): FLOPs = 6 x the rows
the program COUNTED as routed here (``moe_pairs_here`` of its ``epoch_fold``
spans: summed over the layers and the pass's steps) x 3 x hidden x expert
width; bytes = every expert layer's held matrices and the routed rows once
each way. Recomputation is not counted, so a step that recomputes the
forward products reads at most 75%."""
NAME = "expert_matmul_roofline"
UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark import flops, moe_flops
    from benchmark.reduce import cell
    k = (run.get('trace') or {}).get('kernel_s') or {}
    t = sum(v for n, v in k.items() if n.startswith('ragged-dot'))
    folds = [a for a in cell.span_arguments(run, 'epoch_fold')
             if 'moe_pairs_here' in a]
    if not t or not folds or not run.get('peaks'):
        return None
    # the run has a routed layer and its counters: its cell's files have to
    # be found (a moved trace directory raises there, it does not read None)
    config = cell.cell_config(run)
    # a fold closes one pass of fit: its count is over the pass's steps
    steps_per_pass = run['steps'] / len(run['info']['pass_s'])
    rows = sum(int(a['moe_pairs_here']) for a in folds) / (
        len(folds) * steps_per_pass)
    hidden = int(config['hidden_size'])
    inter = int(config['moe_intermediate_size'])
    layers = len(config['layer_types']) - int(config['num_dense_layers'])
    least, _ = flops.roofline_seconds(
        moe_flops.expert_matmul_flops(rows, hidden, inter),
        moe_flops.expert_matmul_bytes(rows, layers * int(
            config['experts_held'][1]), hidden, inter), run['peaks'])
    return 100.0 * least / (t / run['steps'])
