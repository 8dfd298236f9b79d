"""Sum of the device durations of ``flash_attention_fwd`` /
``flash_attention_bwd_fused`` / ``_bwd_dkv`` / ``_bwd_dq`` events, per step."""
NAME = "flash_attention_ms_per_step"
UNIT = "ms/step"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    k = run['trace']['kernel_s']
    t = sum(v for n, v in k.items() if n.startswith('flash_attention'))
    if not t or not run.get('steps'):
        return None
    return 1e3 * t / run['steps']
