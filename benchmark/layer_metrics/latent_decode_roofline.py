"""``flash_decode``'s latent read against its roofline: the least time is the
larger of the live rows' bytes over 819 GB/s and the absorbed form's FLOPs
over 197 TFLOP/s (closed forms in ``benchmark/mla_flops.py``, from the (key,
layer) pairs the program counted: ``ServingStats.kv_bytes_read`` over a
row's bytes); the share is that over the kernel's measured time in the decode
step. At 128 heads a row the two sit within 10% of each other: the chip's
ridge. Queries and outputs are 1/context of the bytes and are left out."""
NAME = "latent_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
CELLS = ["openpangu-*", "pangu-*"]


def read(run):
    from benchmark import flops, mla_flops
    from benchmark.reduce import cell
    mod = ((run.get('trace') or {}).get('modules') or {}).get(
        run.get('step_module')) or {}
    t = (mod.get('kernel_s') or {}).get('flash_decode')
    if not t or not run.get('peaks') or not run.get('delta'):
        return None
    config = cell.cell_config(run)
    rank, rope = int(config['kv_lora_rank']), int(config['qk_rope_head_dim'])
    keys = mla_flops.live_keys(run['delta']['kv_bytes_read'], rank, rope)
    least, _ = flops.roofline_seconds(
        mla_flops.absorbed_decode_flops(
            keys, int(config['num_attention_heads']), rank, rope),
        float(run['delta']['kv_bytes_read']), run['peaks'])
    return 100.0 * least / t
