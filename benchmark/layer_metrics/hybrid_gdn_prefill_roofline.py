"""The prefill's ``gated_delta_rule`` kernel against its roofline, grouped
key heads: the least time is the larger of the chunked grouped rule's
matrix-unit operations over 197 TFLOP/s and its least traffic over 819 GB/s
(``benchmark/hybrid_flops.py``: ``K K^T`` and ``Q K^T`` once a KEY head, the
rest a value head) for the window's REAL prompt tokens
(``prefill_tokens_computed``: a bucket's padding is waste and lowers the
share); the share is that over the kernel's measured time. The program hands
the kernel its keys repeated over their value heads, so it does the two
shared products twice: that, the float32 passes on the bf16 unit and the
uncounted triangular solve all lower the share, and no peak is invented to
raise it."""
NAME = "hybrid_gdn_prefill_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def read(run):
    from benchmark import flops, hybrid_flops
    from benchmark.reduce import cell
    t = ((run.get('trace') or {}).get('kernel_s') or {}).get(
        'gated_delta_rule')
    if not t or not run.get('peaks') or not run.get('delta'):
        return None
    delta, config = run['delta'], cell.cell_config(run)
    least, _ = flops.roofline_seconds(
        hybrid_flops.rule_flops(delta['prefill_tokens_computed'], config),
        hybrid_flops.rule_bytes(delta['prefill_tokens_computed'],
                                delta['prefills'], config),
        run['peaks'])
    return 100.0 * least / t
