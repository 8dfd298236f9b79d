"""Device time of the routed expert layers per step: every op under a node
scope of the layer (``l_moerouter``, ``l_moedispatch``, ``l_moeexperts``,
``l_moecombine``, ``l_moeshared``: the executor's scopes with the layer index
dropped, forward, recomputation and backward alike) and every grouped-product
kernel (``ragged-dot*``), inside the window on the least busy chip. Nothing
to read in a cell whose program has no such scope."""
NAME = "expert_layer_ms_per_step"
UNIT = "ms/step"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import cell
    if run.get('kind') != 'train' or not run.get('steps'):
        return None
    t = cell.device_seconds(
        run, lambda kind, group, scope: (scope or '').startswith('l_moe')
        or (kind == 'kernel' and group.startswith('ragged-dot')))
    return None if t is None else 1e3 * t / run['steps']
