"""Device time, per decode step, of the latent-attention nodes' work in the
decode step that is NOT the ``flash_decode`` kernel: the query's two
projections and its norm (``l_mlaq``), the row's projection, norm and rotary
(``l_mlakv``), the absorption of ``W_kvb`` into the query and out of the
weighted sum (``l_mlaabsorb``), the output projection (``l_mlaout``), and
whatever else carries the node's scope (``l_mla``): what the absorbed form
pays around the read."""
NAME = "latent_projection_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["openpangu-*", "pangu-*"]


def read(run):
    from benchmark.reduce import decode_scopes
    if run.get('kind') != 'serve' or not run.get('steps'):
        return None
    t = decode_scopes.step_program_seconds(
        run, lambda kind, group, scope: kind != 'kernel'
        and (scope or '').startswith('l_mla'))
    return None if t is None else 1e3 * t / run['steps']
