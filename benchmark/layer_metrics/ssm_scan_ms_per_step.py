"""Device time, per decode step, of the mixers' one-token state update and C
contraction in the decode step (the ops under ``l_ssmscan``, kernel or not):
the part of ``ssm_mixer_ms_per_step`` that computes on every slot's recurrent
state. Where the compiler moves the state between HBM and VMEM by
asynchronous copies beside other ops (it does on the v5e), those ops' time is
the arithmetic's alone: ``ssm_state_roofline`` reads the copies' spans too."""
NAME = "ssm_scan_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["jamba2-*", "jamba-*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: scope == 'l_ssmscan')
    return None if t is None else 1e3 * t / run['steps']
