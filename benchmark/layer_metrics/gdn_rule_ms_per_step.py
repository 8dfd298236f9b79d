"""Device time, per decode step, of the delta-rule mixers' one-token update
in the decode step (the ops under ``l_gdnrule``, kernel or not): the part of
``gdn_mixer_ms_per_step`` that reads and writes every slot's matrix state."""
NAME = "gdn_rule_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["olmo-hybrid-*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: scope == 'l_gdnrule')
    return None if t is None else 1e3 * t / run['steps']
