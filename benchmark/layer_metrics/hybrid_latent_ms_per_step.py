"""Device time, per decode step, of the latent-attention nodes' work in the
decode step of a graph that also holds a recurrent state: every op under the
node's scopes (``l_mlaq``, ``l_mlakv``, ``l_mlaabsorb``, ``l_mlagate``,
``l_mlaout``, ``l_mla``) and the step's ``flash_decode`` events (the latent
read: the graph's only paged read) — the whole of what a latent layer costs a
step, small beside the mixers by the design of its cache."""
NAME = "hybrid_latent_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: (scope or '').startswith('l_mla')
        or (kind == 'kernel' and group == 'flash_decode'))
    return None if t is None else 1e3 * t / run['steps']
