"""Device time of everything in the decode step's program (``jit_decode``) that
is not a Mosaic kernel, per decode step: the weight matmuls with their
f32 -> bf16 converts, layer norms, the KV scatter, the sampler's input."""
NAME = "decode_xla_ops_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import xplane
    return xplane.step_xla_ms(run, 'serve')
