"""The most loaded held expert's tokens over its layer's mean, the largest
over the expert layers, from the program's routing counters (the
``moe_load_max_permille`` argument of its ``epoch_fold`` spans, counted over
a pass of ``fit``); the median over the window's passes. A guard: uniform
random ids route near 1; a router that collapses onto one expert reads the
number of held experts, and the grouped products' time follows it."""
NAME = "expert_load_max_over_mean"
UNIT = "ratio"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    import statistics
    from benchmark.reduce import cell
    loads = [int(a['moe_load_max_permille']) / 1e3
             for a in cell.span_arguments(run, 'epoch_fold')
             if 'moe_load_max_permille' in a]
    return statistics.median(loads) if loads else None
