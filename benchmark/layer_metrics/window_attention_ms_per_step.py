"""Sum of the device durations of the sliding-window flash kernels (names
that start ``flash_attention`` and end ``_window``: forward, and the
backward's ``_bwd_dkv_window`` / ``_bwd_dq_window`` or ``_bwd_fused_window``),
per step. The full-attention layers' kernels keep the plain names; both are
in ``flash_attention_ms_per_step``."""
NAME = "window_attention_ms_per_step"
UNIT = "ms/step"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    k = (run.get('trace') or {}).get('kernel_s') or {}
    t = sum(v for n, v in k.items()
            if n.startswith('flash_attention') and n.endswith('_window'))
    if not t or not run.get('steps'):
        return None
    return 1e3 * t / run['steps']
