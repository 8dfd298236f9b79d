"""The ``jamba2-3b-reasoning`` cell's programs compiled ahead of time for a
described v5e:2x2 at the cell's widths, slots, pool and vocabulary, depth 4
(three mixers and one one-K/V-head attention layer: a period of the pattern
cut to what compiles in seconds), with no chip and no weights (every argument
a ``ShapeDtypeStruct``): the decode step (192 slots; ``flash_decode`` reading
the one-head pool and ``kv_write`` in it, the one-token state update a fused
expression), the slot write, and the 2,048-row prefill (``selective_scan`` in
it). The decode step and the slot write alias every pool and recurrent-state
leaf, and the decode step's temporaries are stated: what Mosaic or the
compiler refuses here costs no chip time. As ``test_aot_pangu.py``; run as a
script it prints the figures: ``python benchmark/tests/test_aot_jamba.py``."""
import importlib
import json
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
                                            figures, load, one_chip_sharding)
from benchmark.tests.test_aot_v5e import kernels  # noqa: E402

DEPTH = 4


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

    cell = load("workloads", "jamba2-3b-reasoning.json")
    config = load("configs", f"{cell['config']}.json")
    b = config["builder"]
    mod = importlib.import_module(b["module"])
    kwargs = {f: config[k] for f, k in b["fields"].items()}
    kwargs.update(batch_size=8, num_layers=DEPTH, attn_layer_period=DEPTH,
                  attn_layer_offset=2)
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
        inner = model_cfg.inner
        caches = {}
        for n in ff.executor.pcg.compute_nodes():
            if "_ssm" in n.name:
                caches[n.name] = (
                    sds((eng.n_slots,
                         (model_cfg.mamba_d_conv - 1) * inner), rest),
                    sds((eng.n_slots, model_cfg.mamba_d_state, inner),
                        jnp.float32))
            elif "_attn" in n.name:
                caches[n.name] = sds(
                    (eng.kv_pool_blocks, model_cfg.num_kv_heads,
                     eng.kv_block_size,
                     2 * model_cfg.hidden // model_cfg.num_heads), rest)
        eng._paged_entry_names = {k for k in caches if "_attn" in k}
        state = DecodeState(
            caches=caches, lengths=sds((eng.n_slots,), jnp.int32),
            block_tables=sds((eng.n_slots, eng.max_blocks_per_slot),
                             jnp.int32))
        yield eng, ff.params, state, sds
    finally:
        Executor.init_params, common.on_tpu = real_init, real_on_tpu


def aliased_parameters(text):
    header = text[:text.index("\n")]
    start = header.index("input_output_alias={")
    return {int(n) for n in re.findall(
        r"\}: \((\d+), ", header[start:header.index(" }", start)])}


def state_parameters(text, state):
    """Entry parameter numbers of the decode state's cache leaves, by
    their shapes (no weight and no other argument has one of them)."""
    entry = text[text.index("\nENTRY "):]
    found = set()
    names = {"bfloat16": "bf16", "float32": "f32"}
    for leaf in jax.tree.leaves(state.caches):
        shape = f"{names[str(leaf.dtype)]}[{','.join(map(str, leaf.shape))}]"
        found |= {int(n) for n in re.findall(
            r"= " + re.escape(shape) + r"\{[^}]*\} parameter\((\d+)\)",
            entry)}
    return found


def test_decode_step_aliases_pool_and_state(engine):
    eng, params, state, sds = engine
    c = compile_for_tpu(eng._decode_fn(guard=False), params,
                        [sds((eng.n_slots, 1), jnp.int32)], state)
    fig, text = figures(c), c.as_text()
    print("decode step:", fig)
    assert {"flash_decode", "kv_write"} <= kernels(text)
    assert "selective_scan" not in kernels(text)
    leaves = state_parameters(text, state)
    assert len(leaves) == 2 * (DEPTH - 1) + 1
    assert leaves <= aliased_parameters(text)
    # stated: the step's temporaries are the logits and a layer's
    # activations, far under ONE mixer's state over the slots (63 MB)
    one_state = eng.n_slots * 16 * 5120 * 4 / 1e9
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


def test_prefill_holds_the_scan_kernel_and_fits(engine):
    eng, params, state, sds = engine
    b = eng.buckets[-1]
    c = compile_for_tpu(eng._prefill_fn(b), params, [sds((1, b), jnp.int32)],
                        sds((1,), jnp.int32))
    fig = figures(c)
    print(f"prefill ({b} rows):", fig)
    assert "selective_scan" in kernels(c.as_text())
    # beside the whole model's weights (6.4 GB), state (1.8) and pool (0.8)
    assert fig["temp_gb"] + fig["output_gb"] < 15.7 - 9.0, fig


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q", "-s", "-p", "no:cacheprovider"]))
