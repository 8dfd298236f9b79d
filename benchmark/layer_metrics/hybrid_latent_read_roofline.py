"""``flash_decode``'s latent read at 64 heads a row against its roofline: the
least time is the larger of the rows the program counted
(``latent_rows_read`` of the window's decode ticks: each live slot's keys, a
latent layer) times a stored row's 1,280 B over 819 GB/s and the absorbed
form's FLOPs over 197 TFLOP/s (``benchmark/hybrid_flops.py``); the share is
that over the kernel's measured time in the decode step. At 64 heads the
bytes lead (128 heads sit at the chip's ridge). The rows are the keys a slot
sees, not the 256-key tiles the kernel moves, and queries and outputs are
left out: the share is a floor and cannot pass 100%."""
NAME = "hybrid_latent_read_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def read(run):
    from benchmark import flops, hybrid_flops
    from benchmark.reduce import cell, decode_scopes
    mod = ((run.get('trace') or {}).get('modules') or {}).get(
        run.get('step_module')) or {}
    t = (mod.get('kernel_s') or {}).get('flash_decode')
    rows = decode_scopes.decode_tick_counters(run, 'latent_rows_read')
    if not t or not rows or not run.get('peaks'):
        return None
    config = cell.cell_config(run)
    least, _ = flops.roofline_seconds(
        hybrid_flops.absorbed_read_flops(rows, config),
        rows * hybrid_flops.latent_row_bytes(config), run['peaks'])
    return 100.0 * least / t
