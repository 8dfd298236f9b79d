"""Device time, per decode step, of the gated delta-rule mixers' work in the
decode step: everything under the nodes' five scopes — ``l_gdnin`` (the
projections), ``l_gdnconv`` (the three depthwise convs and their tails),
``l_gdngate`` (beta, the log decay, the L2 norms), ``l_gdnrule`` (the
one-token update of every slot's matrix state) and ``l_gdnout`` (the head's
norm, the gate and W_o) — and whatever else carries the node's scope
(``l_gdn``). The largest of the step's parts where the mechanism does most of
the work."""
NAME = "gdn_mixer_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["olmo-hybrid-*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: (scope or '').startswith('l_gdn'))
    return None if t is None else 1e3 * t / run['steps']
