"""Device time, per decode step, of the gated delta-rule mixers' work in the
decode step of a graph that also holds a latent pool: everything under the
nodes' five scopes (``l_gdnin``, ``l_gdnconv``, ``l_gdngate``, ``l_gdnrule``,
``l_gdnout``) and whatever else carries the node's scope (``l_gdn``) — what
``gdn_mixer_ms_per_step`` reads in the ``olmo-hybrid-*`` cells, whose ``CELLS``
this one cannot widen (PERF.md section 7: the next ``benchmark`` issue folds
the two)."""
NAME = "hybrid_gdn_mixer_ms_per_step"
UNIT = "ms/step"
LAYER = "xla program"
MOVES = "tpot_p50_ms"
CELLS = ["gigachat*"]


def read(run):
    # the same reading as the older reader's, whose ``CELLS`` stop at its
    # own configuration's cells
    from benchmark.layer_metrics import gdn_mixer_ms_per_step

    return gdn_mixer_ms_per_step.read(run)
