"""Device time, per decode step, of the state-space mixers' work in the decode
step: everything under the nodes' five scopes — ``l_ssmin`` (W_in),
``l_ssmconv``, ``l_ssmproj`` (W_x, the three norms, W_dt, softplus),
``l_ssmscan`` (the state update and the C contraction) and ``l_ssmout`` (gate
and W_out) — and whatever else carries the node's scope (``l_ssm``). The
largest of the step's parts where the mechanism does most of the work."""
NAME = "ssm_mixer_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["jamba2-*", "jamba-*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: (scope or '').startswith('l_ssm'))
    return None if t is None else 1e3 * t / run['steps']
