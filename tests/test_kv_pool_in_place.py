"""The KV pool is written in place: read from the compiled text.

The four serving programs that take the pool donated — the executor's
decode step, the engine's slot write, the chunk-prefill step and the
copy-on-write clone — are lowered for a described ``v5e:2x2`` (no chip;
the fixture of benchmark/tests/test_aot_v5e.py restated) at GPT-2 XL's
widths: 25 heads of 64, block 16, 1,400 blocks, 64 slots, depth 2, bf16.
Each must hold no instruction of a pool leaf's shape but its parameter
and the one write, every pool leaf must be in ``input_output_alias``,
and the step must hold the ``flash_decode`` and ``kv_write`` kernels.

What the same reading found before the pool was packed (K and V apart,
last dimension 64, XLA's one-row scatter): 3 ``copy`` instructions a
leaf in the decode step (to the scatter's layout, to the kernel's
operand layout, back to the at-rest layout ``{0,3,2,1}``) and 2 a leaf
in the slot write — 143 ms of a 201 ms step on the chip (PERF.md §7).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SLOTS, BLOCKS, BLOCK, MAX_LEN = 64, 1400, 16, 1024
HEADS, HEAD_DIM, DEPTH = 25, 64, 2
POOL = f"bf16[{BLOCKS},{HEADS},{BLOCK},{2 * HEAD_DIM}]"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def programs(one_chip, no_persistent_cache):
    """``{name: (jitted program, abstract arguments on the described
    chip)}``, traced with the kernels' gates answering as they do on a
    TPU (the process itself sees the CPU): steered here, in the test,
    not by an option of the program."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.kernels import _common
    from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
    from flexflow_tpu.serving import ServingEngine

    config = FFConfig()
    config.parse_args(["-b", "8", "--compute-dtype", "bf16",
                       "--only-data-parallel", "--mesh-shape", "1"])
    ff = FFModel(config)
    build_gpt2(ff, GPT2Config(num_layers=DEPTH, hidden=HEADS * HEAD_DIM,
                              num_heads=HEADS, vocab_size=512,
                              intermediate=4 * HEADS * HEAD_DIM,
                              seq_len=MAX_LEN, dropout=0.0, batch_size=8))
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    eng = ServingEngine(ff, n_slots=SLOTS, max_decode_len=MAX_LEN,
                        kv_block_size=BLOCK, kv_pool_blocks=BLOCKS,
                        buckets=(64,))

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    i32 = on(jnp.int32(0))
    ids = on(jnp.zeros((1, 64), jnp.int32))
    params = on(ff.params)
    # the prefill's cache as shapes (traced on the CPU's path: only its
    # structure is used), and the pool the engine builds from it
    cache = jax.eval_shape(eng._prefill_fn(64), ff.params, [ids],
                           on(jnp.ones((1,), jnp.int32)))[2]
    eng._ensure_state(cache)
    state, last = on(eng.state), on(eng._last_tokens)
    row = on(jnp.zeros((eng.max_blocks_per_slot,), jnp.int32))
    real_on_tpu = _common.on_tpu
    _common.on_tpu = lambda: True
    try:
        yield {
            "decode_step": (eng._decode_fn(), (
                params, [on(jnp.zeros((SLOTS, 1), jnp.int32))], state)),
            "slot_write": (eng._write_slot_program(), (
                state, last, on(cache), i32, i32, i32, row)),
            "chunk_step": (eng._chunk_fn(64), (
                params, [ids], state, row, i32, i32)),
            "cow_clone": (eng._cow_clone_program(), (state, i32, i32)),
        }
    finally:
        _common.on_tpu = real_on_tpu


def _kernels(text):
    return {m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r"(\w+)\)*/pallas_call", line)] if m}


def _pool_shaped(text):
    """``{opcode: count}`` of the instructions whose result is one pool
    leaf, anywhere in the module (fused computations included)."""
    counts = {}
    for m in re.finditer(
            r"= " + re.escape(POOL) + r"\{[^}]*\} ([\w-]+)\(", text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def _aliased_parameters(text):
    """Parameter numbers the module header aliases onto an output."""
    header = text[:text.index("\n")]
    start = header.index("input_output_alias={")
    return {int(n) for n in re.findall(
        r"\}: \((\d+), ", header[start:header.index(" }", start)])}


def _pool_parameters(text):
    """Parameter numbers of the entry computation's pool leaves."""
    entry = text[text.index("\nENTRY "):]
    return {int(n) for n in re.findall(
        r"= " + re.escape(POOL) + r"\{[^}]*\} parameter\((\d+)\)", entry)}


@pytest.mark.parametrize("name", ["decode_step", "slot_write",
                                  "chunk_step", "cow_clone"])
def test_no_program_rewrites_the_pool_whole(programs, name):
    fn, args = programs[name]
    text = fn.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    pools = _pool_parameters(text)
    assert len(pools) == DEPTH, pools
    assert pools <= _aliased_parameters(text), \
        f"{name}: a pool leaf is not in input_output_alias"
    # what may have a pool leaf's shape: its parameter and ONE write a
    # leaf — the aliased kernel, or the fusion of the whole-block scatter
    # / block copy that XLA does in place at this width (its body's
    # scatter or dynamic-update-slice has the shape too). A ``copy`` is
    # a pass over the whole leaf.
    shaped = _pool_shaped(text)
    assert set(shaped) <= {"parameter", "custom-call", "fusion", "scatter",
                           "dynamic-update-slice"}, \
        f"{name} rewrites the pool whole: {shaped}"
    assert shaped.get("custom-call", 0) + shaped.get("fusion", 0) \
        == DEPTH, f"{name}: {shaped}"
    kernels = _kernels(text)
    if name in ("decode_step", "chunk_step"):
        assert "kv_write" in kernels, kernels
        assert shaped.get("custom-call") == DEPTH, shaped
    if name == "decode_step":
        assert "flash_decode" in kernels, kernels
