"""Device time of the routed expert layers per DECODE step in a graph whose
mixers are delta-rule and latent: every op of the decode step under a node
scope of the layer (``l_moerouter``, ``l_moedispatch``, ``l_moeexperts``,
``l_moecombine``, ``l_moeshared``) and every grouped-product kernel
(``ragged-dot*``) in it — what ``decode_expert_layer_ms_per_step`` reads in
the ``openpangu-*`` cells (PERF.md section 7: to be folded), with one
difference: the profiler writes an event for a ``conditional`` (the bounded
path's ``lax.cond``, three a layer) that spans the ops inside it, and this
reader leaves the wrappers out (``CONTROL``), where that one counts both and
reads double (PERF.md section 7)."""
NAME = "hybrid_expert_layer_ms_per_step"
UNIT = "ms/step"
LAYER = "expert layer"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]
#: opcodes of events that span other events of the same line
CONTROL = ("conditional", "while", "call")


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: group not in CONTROL and (
            (scope or '').startswith('l_moe')
            or (kind == 'kernel' and group.startswith('ragged-dot'))))
    return None if t is None else 1e3 * t / run['steps']
