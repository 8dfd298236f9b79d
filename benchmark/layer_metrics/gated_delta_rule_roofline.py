"""The prefill's ``gated_delta_rule`` kernel against its roofline: the least
time is the larger of the chunked rule's matrix-unit operations over 197
TFLOP/s and its least traffic over 819 GB/s (``benchmark/delta_flops.py``)
for the window's REAL prompt tokens (``prefill_tokens_computed``: a bucket's
padding is waste and lowers the share); the share is that over the kernel's
measured time. The operations are counted once a multiply-add pair whatever
passes a float32 product takes on the bf16 unit (six at "highest"), and the
unit-lower-triangular solve — a serial chain of 64 steps a chunk on the
vector unit, for which ``peaks.json`` has no published figure — is not
counted: the share is expected low, and no peak is invented to raise it."""
NAME = "gated_delta_rule_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["olmo-hybrid-*"]


def read(run):
    from benchmark import delta_flops, flops
    from benchmark.reduce import cell
    t = ((run.get('trace') or {}).get('kernel_s') or {}).get(
        'gated_delta_rule')
    if not t or not run.get('peaks') or not run.get('delta'):
        return None
    delta, config = run['delta'], cell.cell_config(run)
    least, _ = flops.roofline_seconds(
        delta_flops.rule_flops(delta['prefill_tokens_computed'], config),
        delta_flops.rule_bytes(delta['prefill_tokens_computed'],
                               delta['prefills'], config),
        run['peaks'])
    return 100.0 * least / t
