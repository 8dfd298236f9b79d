"""The ``olmo-hybrid-7b-assist`` cell's programs compiled ahead of time for a
described v5e:2x2 at the cell's widths, slots, pool and vocabulary, depth 4
(three delta-rule mixers and one full-attention layer: one period of the
pattern, what compiles in seconds), with no chip and no weights (every
argument a ``ShapeDtypeStruct``): the decode step (64 slots; ``flash_decode``
reading the 30-K/V-head pool at a 256-lane row and ``kv_write`` in it, the
one-token state update the ``gated_delta_update`` kernel), the slot write, and
the 1,024-row prefill (``gated_delta_rule`` in it). The decode step and the
slot write alias every pool, state and tail leaf, and the decode step's
temporaries are stated: what Mosaic or the compiler refuses here costs no
chip time. As ``test_aot_jamba.py``; run as a script it prints the figures:
``python benchmark/tests/test_aot_olmo_hybrid.py``."""
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.tests.test_aot_jamba import (aliased_parameters,  # noqa: E402
                                            state_parameters)
from benchmark.tests.test_aot_pangu import (compile_for_tpu,  # noqa: E402
                                            figures, load, one_chip_sharding)
from benchmark.tests.test_aot_v5e import kernels  # noqa: E402

DEPTH = 4
SCOPES = ("in", "conv", "gate", "rule", "out")


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


@pytest.fixture(scope="module")
def engine(one_chip):
    """(engine, abstract params, abstract decode state, sds) of the cell at
    depth 4, built with no weight ever made."""
    import flexflow_tpu.kernels._common as common
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.execution.executor import Executor
    from flexflow_tpu.ffconst import dtype_to_jnp
    from flexflow_tpu.serving import ServingEngine
    from flexflow_tpu.serving.kvcache import DecodeState

    cell = load("workloads", "olmo-hybrid-7b-assist.json")
    config = load("configs", f"{cell['config']}.json")
    b = config["builder"]
    mod = importlib.import_module(b["module"])
    kwargs = {f: config[k] for f, k in b["fields"].items()}
    kwargs.update(batch_size=8, layer_types=config["layer_types"][:DEPTH])
    model_cfg = getattr(mod, b["config_class"])(**kwargs)
    ffc = FFConfig()
    ffc.parse_args(["-b", "8"] + config["compile_flags"]
                   + cell["compile_flags"])
    rest = dtype_to_jnp(ffc.param_dtype)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(self, seed=0):
        out = {}
        for node, wname, shape, _dtype, _init in self.weight_entries():
            out.setdefault(node.name, {})[wname] = sds(tuple(shape), rest)
        return out

    real_init, real_on_tpu = Executor.init_params, common.on_tpu
    Executor.init_params, common.on_tpu = shapes, (lambda: True)
    try:
        ff = FFModel(ffc)
        getattr(mod, b["build"])(ff, model_cfg)
        ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        e = cell["engine"]
        eng = ServingEngine(ff, n_slots=e["n_slots"],
                            max_decode_len=e["max_decode_len"],
                            kv_pool_blocks=e["kv_pool_blocks"],
                            buckets=tuple(e["buckets"]))
        heads, dk, dv, k_w = (model_cfg.linear_num_key_heads,
                              model_cfg.linear_key_head_dim,
                              model_cfg.linear_value_head_dim,
                              model_cfg.linear_conv_kernel_dim)
        caches = {}
        for n in ff.executor.pcg.compute_nodes():
            if "_gdn" in n.name:
                caches[n.name] = (
                    sds((eng.n_slots,
                         (k_w - 1) * heads * (2 * dk + dv)), rest),
                    sds((eng.n_slots, heads, dk, dv), jnp.float32))
            elif "_attn" in n.name:
                caches[n.name] = sds(
                    (eng.kv_pool_blocks, model_cfg.num_heads,
                     eng.kv_block_size, 2 * model_cfg.head_dim), rest)
        eng._paged_entry_names = {k for k in caches if "_attn" in k}
        state = DecodeState(
            caches=caches, lengths=sds((eng.n_slots,), jnp.int32),
            block_tables=sds((eng.n_slots, eng.max_blocks_per_slot),
                             jnp.int32))
        yield eng, ff.params, state, sds
    finally:
        Executor.init_params, common.on_tpu = real_init, real_on_tpu


def has_scopes(text):
    import re

    return all(re.search(rf"l\d+_gdn{what}\b", text) for what in SCOPES)


def test_decode_step_aliases_pool_and_state(engine):
    eng, params, state, sds = engine
    c = compile_for_tpu(eng._decode_fn(guard=False), params,
                        [sds((eng.n_slots, 1), jnp.int32)], state)
    fig, text = figures(c), c.as_text()
    print("decode step:", fig)
    assert {"flash_decode", "kv_write", "gated_delta_update"} \
        <= kernels(text)
    assert "gated_delta_rule" not in kernels(text)
    assert has_scopes(text)
    leaves = state_parameters(text, state)
    assert len(leaves) == 2 * (DEPTH - 1) + 1
    assert leaves <= aliased_parameters(text)
    # stated: the step's temporaries are the logits and a layer's
    # activations, far under ONE mixer's state over the slots (189 MB with
    # its lanes padded)
    one_state = eng.n_slots * 30 * 96 * 256 * 4 / 1e9
    assert fig["temp_gb"] < one_state, fig


def test_slot_write_aliases_pool_and_state(engine):
    eng, params, state, sds = engine
    b = eng.buckets[-1]
    cache = jax.eval_shape(eng._prefill_fn(b), params,
                           [sds((1, b), jnp.int32)],
                           sds((1,), jnp.int32))[2]
    cache = jax.tree.map(lambda s: sds(s.shape, s.dtype), cache)
    i32 = sds((), jnp.int32)
    c = compile_for_tpu(
        eng._write_slot_program(), state, sds((eng.n_slots, 1), jnp.int32),
        cache, i32, i32, i32, sds((eng.max_blocks_per_slot,), jnp.int32))
    text = c.as_text()
    print("slot write:", figures(c))
    leaves = state_parameters(text, state)
    assert len(leaves) == 2 * (DEPTH - 1) + 1
    assert leaves <= aliased_parameters(text)


def test_prefill_holds_the_chunked_kernel_and_fits(engine):
    eng, params, state, sds = engine
    b = eng.buckets[-1]
    c = compile_for_tpu(eng._prefill_fn(b), params, [sds((1, b), jnp.int32)],
                        sds((1,), jnp.int32))
    fig, text = figures(c), c.as_text()
    print(f"prefill ({b} rows):", fig)
    assert "gated_delta_rule" in kernels(text)
    assert has_scopes(text)
    # beside the 16 layers' weights (8.2 GB), state (2.3) and pool (3.0)
    assert fig["temp_gb"] + fig["output_gb"] < 15.7 - 13.6, fig


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q", "-s", "-p", "no:cacheprovider"]))
