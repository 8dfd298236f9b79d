"""The prefill's ``selective_scan`` kernel against the MEMORY roofline: the
least time is the float32 bytes of ``x``, ``dt``, ``B``, ``C`` read and ``y``
and the final state written (``benchmark/ssm_flops.py``) for the window's
REAL prompt tokens (``prefill_tokens_computed``: a bucket's padding is waste
and lowers the share) over 819 GB/s; the share is that over the kernel's
measured time. The kernel's true bound is the vector unit — seven multiplies,
adds and an exponential per state element and token, none on the matrix unit
— for which ``peaks.json`` has no published figure: the share is of the
memory roofline, is expected low, and no peak is invented to raise it."""
NAME = "selective_scan_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["jamba2-*", "jamba-*"]


def read(run):
    from benchmark import ssm_flops
    from benchmark.reduce import cell
    t = ((run.get('trace') or {}).get('kernel_s') or {}).get(
        'selective_scan')
    if not t or not run.get('peaks') or not run.get('delta'):
        return None
    delta = run['delta']
    least = ssm_flops.scan_bytes(
        delta['prefill_tokens_computed'], delta['prefills'],
        cell.cell_config(run)) / run['peaks']['hbm_bytes_per_s']
    return 100.0 * least / t
