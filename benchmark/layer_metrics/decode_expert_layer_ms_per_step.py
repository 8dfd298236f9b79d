"""Device time of the routed expert layers per DECODE step: every op of the
decode step under a node scope of the layer (``l_moerouter``,
``l_moedispatch``, ``l_moeexperts``, ``l_moecombine``, ``l_moeshared``) and
every grouped-product kernel (``ragged-dot*``) in it. The training reader
``expert_layer_ms_per_step`` returns nothing for a serving run."""
NAME = "decode_expert_layer_ms_per_step"
UNIT = "ms/step"
LAYER = "expert layer"
MOVES = "tpot_p50_ms"
CELLS = ["openpangu-*", "pangu-*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: (scope or '').startswith('l_moe')
        or (kind == 'kernel' and group.startswith('ragged-dot')))
    return None if t is None else 1e3 * t / run['steps']
