"""Least time of the flash kernels of one step over their measured time.
Least time: per layer the larger of FLOPs over 197 TFLOP/s and bytes over
819 GB/s, forward and backward (closed forms in ``benchmark/flops.py``, the
recomputed QK^T not counted; the calls and their shapes from the
configuration's ``reference/<config>.py``). At (b,16,s,64) both are compute bound by two
orders of magnitude. head_dim 64 fills half of the MXU's 128-deep
contraction, so against the published peak this share cannot pass ~50%."""
NAME = "flash_attention_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark import flops
    k = run['trace']['kernel_s']
    t = sum(v for n, v in k.items() if n.startswith('flash_attention'))
    if not t or not run.get('attention_calls') or not run.get('peaks'):
        return None
    least = 0.0
    for count, b, h, sq, sk, d, causal in run['attention_calls']:
        fwd, _ = flops.roofline_seconds(
            flops.flash_fwd_flops(b, h, sq, sk, d, causal),
            flops.flash_fwd_bytes(b, h, sq, sk, d), run['peaks'])
        bwd, _ = flops.roofline_seconds(
            flops.flash_bwd_flops(b, h, sq, sk, d, causal),
            flops.flash_bwd_bytes(b, h, sq, sk, d), run['peaks'])
        least += count * (fwd + bwd)
    return 100.0 * least / (t / run['steps'])
