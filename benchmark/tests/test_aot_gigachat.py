"""The ``gigachat35-reasoning-2k`` cell's programs compiled ahead of time
for a described v5e:2x2 at the cell's real sizes, with no chip and no
weights (every argument a ``ShapeDtypeStruct``): the decode step (128 slots:
the latent pool and the delta-rule state side by side, ``flash_decode``'s
latent read at 64 heads, ``kv_write`` and ``gated_delta_update`` in it), the
2,048-row one-shot prefill (the materialised latent core in blocks of query
rows, the chunked ``gated_delta_rule`` kernel) and the plain reference's two
layer kinds at the mix's 8,192 positions — what Mosaic or the compiler
refuses here costs no chip time, and the memory figures size the pool and
the slots (PERF.md section 4). As ``test_aot_pangu.py``; run as a script it
prints the figures: ``python benchmark/tests/test_aot_gigachat.py``."""
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.tests.test_aot_pangu import (compile_for_tpu,  # noqa: E402
                                            figures, load,
                                            no_persistent_cache,  # noqa: F401
                                            one_chip)  # noqa: F401
from benchmark.tests.test_aot_v5e import kernels  # noqa: E402

CELL = "gigachat35-reasoning-2k"
CHIP_GB = 15.7


def abstract_engine(sharding):
    """(engine, abstract params, abstract decode state, the prefill's
    abstract cache) of the cell, built with no weight ever made."""
    import flexflow_tpu.kernels._common as common
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.execution.executor import Executor
    from flexflow_tpu.ffconst import dtype_to_jnp
    from flexflow_tpu.serving import ServingEngine
    from flexflow_tpu.serving.kvcache import (DecodeState,
                                              is_prefill_kv_entry,
                                              new_kv_pool)

    cell = load("workloads", f"{CELL}.json")
    config = load("configs", f"{cell['config']}.json")
    b = config["builder"]
    mod = importlib.import_module(b["module"])
    model_cfg = getattr(mod, b["config_class"])(
        batch_size=8, **{f: config[k] for f, k in b["fields"].items()})
    ffc = FFConfig()
    ffc.parse_args(["-b", "8"] + config["compile_flags"]
                   + cell["compile_flags"])
    rest = dtype_to_jnp(ffc.param_dtype)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    def shapes(self, seed=0):
        out = {}
        for node, wname, shape, _dtype, _init in self.weight_entries():
            out.setdefault(node.name, {})[wname] = sds(shape, rest)
        return out

    real = (Executor.init_params, common.on_tpu)
    Executor.init_params, common.on_tpu = shapes, (lambda: True)
    try:
        ff = FFModel(ffc)
        getattr(mod, b["build"])(ff, model_cfg)
        ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    finally:
        Executor.init_params = real[0]
    e = cell["engine"]
    eng = ServingEngine(ff, n_slots=e["n_slots"],
                        max_decode_len=e["max_decode_len"],
                        kv_pool_blocks=e["kv_pool_blocks"],
                        buckets=tuple(e["buckets"]))
    bucket = max(e["buckets"])
    cache = jax.eval_shape(
        eng._prefill_fn(bucket), ff.params, [sds((1, bucket), jnp.int32)],
        sds((1,), jnp.int32))[2]
    caches = {}
    for name, entry in cache.items():
        if is_prefill_kv_entry(entry):
            eng._paged_entry_names.add(name)
            caches[name] = jax.eval_shape(
                lambda en: new_kv_pool(en, eng.kv_pool_blocks,
                                       eng.kv_block_size, "native"), entry)
        else:
            caches[name] = jax.tree.map(
                lambda leaf: sds((eng.n_slots,) + leaf.shape[1:],
                                 leaf.dtype), entry)
    on = lambda tree: jax.tree.map(
        lambda a: sds(a.shape, a.dtype), tree)
    state = DecodeState(
        caches=on(caches), lengths=sds((eng.n_slots,), jnp.int32),
        block_tables=sds((eng.n_slots, eng.max_blocks_per_slot), jnp.int32))
    return eng, ff.params, state, on(cache), sds, real[1], config, cell


@pytest.fixture(scope="module")
def engine(one_chip):  # noqa: F811
    import flexflow_tpu.kernels._common as common

    out = abstract_engine(one_chip)
    yield out
    common.on_tpu = out[5]


def resident_gb(params, state) -> float:
    return sum(np.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves((params, state.caches))) / 1e9


def test_the_cell_fills_the_chip(engine):
    """ISSUE 53's memory reckoning: 9.46 GB of bf16 weights, 2.20 GB of
    state at 128 slots, 0.67 GB of latent pool."""
    eng, params, state, _cache, _sds, _r, config, _cell = engine
    weights = sum(np.prod(x.shape) for x in jax.tree.leaves(params))
    assert weights == config["parameters_held"] == 4_731_721_728
    slot_major = sum(
        np.prod(x.shape) * x.dtype.itemsize
        for name, entry in state.caches.items()
        if name not in eng._paged_entry_names
        for x in jax.tree.leaves(entry))
    assert slot_major == eng.n_slots * 17_170_432 \
        == eng.n_slots * eng._recurrent_slot_bytes()
    pool = [x for name in eng._paged_entry_names
            for x in jax.tree.leaves(state.caches[name])]
    assert [x.shape for x in pool] == [(32769, 1, 16, 640)]
    assert eng._kv_row_bytes() == 1280
    total = resident_gb(params, state)
    print("resident GB:", total)
    assert 12.2 < total < 12.5


def test_decode_step_writes_pool_and_state_in_place(engine):
    eng, params, state, _cache, sds, _r, _config, _cell = engine
    c = compile_for_tpu(eng._decode_fn(guard=False), params,
                        [sds((eng.n_slots, 1), jnp.int32)], state)
    fig = figures(c)
    print("decode step:", fig)
    text = c.as_text()
    assert {"flash_decode", "kv_write", "gated_delta_update"} \
        <= kernels(text)
    assert fig["arguments_gb"] + fig["temp_gb"] < CHIP_GB, fig
    # every pool and state leaf aliased onto an output, none rewritten by a
    # copy: no temporary of a state leaf's size, no copy of its shape
    leaves = [x for x in jax.tree.leaves(state.caches)]
    assert fig["alias_gb"] >= sum(
        np.prod(x.shape) * x.dtype.itemsize for x in leaves) / 1e9 - 1e-6
    state_leaf = max(np.prod(x.shape) * x.dtype.itemsize
                     for x in leaves) / 1e9
    assert fig["temp_gb"] < state_leaf, fig
    for x in leaves:
        if x.ndim < 3:
            continue
        shape = f"{x.dtype.name.replace('bfloat16', 'bf16').replace('float32', 'f32')}" \
            f"[{','.join(str(d) for d in x.shape)}]"
        copies = [ln for ln in text.splitlines()
                  if re.search(r"= " + re.escape(shape) + r"\S* copy\(", ln)]
        assert not copies, (shape, copies[:2])


def test_the_longest_prefill_fits_beside_the_engine(engine):
    eng, params, state, _cache, sds, _r, _config, cell = engine
    bucket = max(cell["engine"]["buckets"])
    c = compile_for_tpu(eng._prefill_fn(bucket), params,
                        [sds((1, bucket), jnp.int32)], sds((1,), jnp.int32))
    fig = figures(c)
    print(f"prefill ({bucket} rows):", fig)
    assert "gated_delta_rule" in kernels(c.as_text())
    pool_and_state = resident_gb({}, state)
    assert fig["arguments_gb"] + fig["temp_gb"] + pool_and_state < CHIP_GB, \
        (fig, pool_and_state)


@pytest.mark.parametrize("kind", ["delta_rule", "latent"])
def test_reference_layers_fit_beside_the_engine(engine, kind):
    """The reference's two mixer layers at the mix's padded length, with the
    routing alternatives' rows."""
    eng, params, state, _cache, sds, _r, config, cell = engine
    from benchmark.run import load_module

    ref = load_module(os.path.join(BENCH, "reference", config["reference"]),
                      "bench_reference_aot_gigachat")
    t = load("traffic", f"{cell['traffic']}.json")["max_total_tokens"]
    a = ref.TIE_WINDOW * (config["num_hidden_layers"]
                          - config["first_k_dense_replace"])
    r = ref.Reference(params, config)
    i = 4 if kind == "latent" else 1
    with jax.default_matmul_precision("highest"):
        c = compile_for_tpu(
            r._mixer, sds((t + a, config["hidden_size"]), jnp.float32),
            sds((a,), jnp.int32),
            r._p(f"l{i}_mla" if kind == "latent" else f"l{i}_gdn"),
            r._p(f"l{i}_norm1")["scale"], r._p(f"l{i}_norm2")["scale"],
            t, kind == "latent")
    fig = figures(c)
    print(f"reference {kind} layer:", fig)
    resident = resident_gb(params, state)
    assert resident + fig["temp_gb"] + fig["arguments_gb"] < CHIP_GB, \
        (fig, resident)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q", "-s", "-p", "no:cacheprovider"]))
