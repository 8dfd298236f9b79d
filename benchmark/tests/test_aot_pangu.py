"""The ``openpangu-ultra-docqa-8k`` cell's three big programs compiled ahead
of time for a described v5e:2x2 at the cell's real sizes, with no chip and no
weights (every argument a ``ShapeDtypeStruct``): the decode step (64 slots,
the latent pool, ``flash_decode``'s latent read and ``kv_write`` in it), the
1,024-token chunk step (the same kernel's chunk read,
``latent_chunk_attention``), and the plain reference's heaviest piece (one
attention layer at 13,184 positions) — what Mosaic or the compiler refuses
here costs no chip time, and the three memory figures size the pool
(PERF.md section 4). As ``test_aot_v5e.py``; run as a script it prints the
figures: ``python benchmark/tests/test_aot_pangu.py``."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.tests.test_aot_v5e import kernels  # noqa: E402


def one_chip_sharding():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def one_chip():
    try:
        return one_chip_sharding()
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def load(sub, name):
    with open(os.path.join(BENCH, sub, name)) as f:
        return json.load(f)


def abstract_engine(sharding, cell_name="openpangu-ultra-docqa-8k"):
    """(engine, abstract params, abstract decode state) of the cell, built
    here with no weight ever made: ``init_params`` hands out shapes."""
    import importlib

    import flexflow_tpu.kernels._common as common
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.execution.executor import Executor
    from flexflow_tpu.ffconst import dtype_to_jnp
    from flexflow_tpu.serving import ServingEngine
    from flexflow_tpu.serving.kvcache import (DecodeState, latent_lanes)

    cell = load("workloads", f"{cell_name}.json")
    config = load("configs", f"{cell['config']}.json")
    b = config["builder"]
    mod = importlib.import_module(b["module"])
    model_cfg = getattr(mod, b["config_class"])(
        batch_size=8, **{f: config[k] for f, k in b["fields"].items()})
    ffc = FFConfig()
    ffc.parse_args(["-b", "8"] + config["compile_flags"]
                   + cell["compile_flags"])
    rest = dtype_to_jnp(ffc.param_dtype)

    def shapes(self, seed=0):
        out = {}
        for node, wname, shape, _dtype, _init in self.weight_entries():
            out.setdefault(node.name, {})[wname] = jax.ShapeDtypeStruct(
                tuple(shape), rest, sharding=sharding)
        return out

    real, common.on_tpu = (Executor.init_params, common.on_tpu), \
        (lambda: True)
    Executor.init_params = shapes
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

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    caches = {
        n.name: sds((eng.kv_pool_blocks, 1, eng.kv_block_size,
                     latent_lanes(row)), rest)
        for n in ff.executor.pcg.compute_nodes() if "_mla" in n.name}
    state = DecodeState(
        caches=caches, lengths=sds((eng.n_slots,), jnp.int32),
        block_tables=sds((eng.n_slots, eng.max_blocks_per_slot), jnp.int32))
    return eng, ff.params, state, sds, real[1], config


def compile_for_tpu(fn, *args):
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()


def figures(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"arguments_gb": ma.argument_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "alias_gb": ma.alias_size_in_bytes / 1e9}


@pytest.fixture(scope="module")
def engine(one_chip):
    import flexflow_tpu.kernels._common as common

    eng, params, state, sds, real_on_tpu, config = abstract_engine(one_chip)
    yield eng, params, state, sds, config
    common.on_tpu = real_on_tpu


def test_decode_step_holds_the_latent_kernels_in_place(engine):
    eng, params, state, sds, _ = engine
    c = compile_for_tpu(eng._decode_fn(guard=False), params,
                        [sds((eng.n_slots, 1), jnp.int32)], state)
    fig = figures(c)
    print("decode step:", fig)
    assert {"flash_decode", "kv_write"} <= kernels(c.as_text())
    # the pool is written in place: no temporary of a pool leaf's size
    leaf = np.prod(next(iter(state.caches.values())).shape) * 2 / 1e9
    assert fig["temp_gb"] < leaf, fig


def test_chunk_step_fits_beside_weights_and_pool(engine):
    eng, params, state, sds, _ = engine
    rows = eng.prefill_chunk_tokens
    c = compile_for_tpu(
        eng._chunk_fn(rows), params, [sds((1, rows), jnp.int32)], state,
        sds((eng.max_blocks_per_slot,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32))
    fig = figures(c)
    print(f"chunk step ({rows} rows):", fig)
    assert {"kv_write", "latent_chunk_attention"} <= kernels(c.as_text())
    assert fig["arguments_gb"] + fig["temp_gb"] < 15.7, fig


def test_reference_attention_layer_fits_beside_the_engine(engine):
    """The reference's heaviest jitted piece at the mix's padded length."""
    eng, params, state, sds, config = engine
    from benchmark.run import load_module

    ref = load_module(os.path.join(BENCH, "reference", config["reference"]),
                      "bench_reference_aot")
    t = load("traffic", "docqa-8k.json")["max_total_tokens"]
    a = ref.TIE_WINDOW * (config["num_hidden_layers"]
                          - config["first_k_dense_replace"])
    r = ref.Reference(params, config)
    with jax.default_matmul_precision("highest"):
        c = compile_for_tpu(
            r._attention, sds((t + a, config["hidden_size"]), jnp.float32),
            sds((a,), jnp.int32), r._p("l1_mla"),
            r._p("l1_norm1")["scale"], r._p("l1_norm2")["scale"], t)
    fig = figures(c)
    print("reference attention layer:", fig)
    resident = sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves((params, state.caches))) / 1e9
    print("resident weights + pool GB:", resident)
    assert resident + fig["temp_gb"] + fig["arguments_gb"] < 15.7, fig


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q", "-s", "-p", "no:cacheprovider"]))
