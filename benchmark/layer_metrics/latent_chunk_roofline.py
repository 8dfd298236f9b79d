"""The prefill chunk's latent read (the kernel ``latent_chunk_attention``:
``flash_decode``'s chunk form, four positions a grid step, absorbed) against
its roofline. It is compute bound — four positions share each row that is
moved, so 512 query rows meet every 1,280 bytes — and the least time is the
absorbed form's FLOPs over 197 TFLOP/s (``benchmark/mla_flops.py``) for the
(query position, key) pairs the program's ``prefill_chunk`` spans say were
computed in the window (``start`` and ``tokens`` of every chunk, every
layer); the share is that over the kernel's measured time. Pad rows and
unwritten keys cost the kernel nothing and are not counted."""
NAME = "latent_chunk_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["openpangu-*", "pangu-*"]


def read(run):
    from benchmark import flops, mla_flops
    from benchmark.reduce import cell
    t = ((run.get('trace') or {}).get('kernel_s') or {}).get(
        'latent_chunk_attention')
    chunks = [a for a in cell.span_arguments(run, 'prefill_chunk')
              if 'start' in a and 'tokens' in a]
    if not t or not chunks or not run.get('peaks'):
        return None
    config = cell.cell_config(run)
    rank, rope = int(config['kv_lora_rank']), int(config['qk_rope_head_dim'])
    pairs = int(config['num_hidden_layers']) * sum(
        mla_flops.chunk_pairs(int(a['start']), int(a['tokens']))
        for a in chunks)
    least, _ = flops.roofline_seconds(
        mla_flops.absorbed_decode_flops(
            pairs, int(config['num_attention_heads']), rank, rope),
        pairs / 4.0 * mla_flops.latent_row_bytes(rank, rope), run['peaks'])
    return 100.0 * least / t
