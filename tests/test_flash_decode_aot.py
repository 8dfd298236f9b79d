"""``flash_decode`` compiled ahead of time for a described ``v5e:2x2`` at
the three serving cells' shapes, and its chunk read at openPangu's — no
chip, a second or two each (the fixture of benchmark/tests/test_aot_v5e.py
restated: the topology is described inside a module-scoped fixture, never
at import; where it cannot be described the tests skip).

Each program must hold ONE Mosaic call, named ``flash_decode`` (the chunk
read: ``latent_chunk_attention``) — the benchmark's ``flash_decode_in_step``
check, its readers and the trace's scopes read that name — and, read from
the kernel's module inside the lowered text, a grid of ONE dimension, a
step a slot: the key tiles of a slot are a loop inside the step. An int8
pool keeps the tile axis on its grid (its scales come through
``BlockSpec``s: Mosaic refuses a hand-written copy of rows narrower than
a lane tile)."""
import base64
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BLOCK = 16
#: cell: (slots, query heads, query width), (pool blocks, pool heads, lanes),
#: table entries a slot, v_lanes — the engines' shapes in
#: benchmark/workloads/<cell>.json and scripts/microbench_flash_decode.py
CELLS = {
    "gpt2-xl-chat": ((64, 25, 64), (1400, 25, 128), 64, None),
    "jamba2-3b-reasoning": ((192, 20, 256), (49153, 1, 256), 512, 128),
    "openpangu-ultra-docqa-8k": ((64, 128, 576), (28000, 1, 640), 824, 512),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def lowered_and_compiled(one_chip, q, pool, table, pool_dtype=jnp.bfloat16,
                         **kw):
    """The kernel's call lowered for the TPU and compiled for the
    described chip: ``(lowered text, compiled text)``."""
    from flexflow_tpu.kernels.flash_decode import flash_decode_pool

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    args = [shape(q, jnp.bfloat16),
            shape(pool[:2] + (BLOCK, pool[2]), pool_dtype),
            shape(table, jnp.int32), shape(q[:1], jnp.int32)]
    if pool_dtype == jnp.int8:
        args.append(shape((pool[0], 2, pool[1], BLOCK), jnp.float32))
    lowered = jax.jit(
        lambda q, pool, tables, n_keys, scales=None: flash_decode_pool(
            q, pool, tables, n_keys, scales=scales, interpret=False, **kw)
    ).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text(), lowered.compile().as_text()


def mosaic_calls(compiled_text):
    """The names of the compiled program's Mosaic calls, one an
    instruction."""
    return [m.group(1) for line in compiled_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r"(\w+)\)*/pallas_call", line)] if m]


def kernel_grids(lowered_text):
    """The grid of every Mosaic kernel in a lowered program, read from the
    kernel's own module: the ``tpu_custom_call``'s ``body`` is that module
    as MLIR bytecode, and its entry function carries the grid as
    ``iteration_bounds``."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    grids = []
    for m in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                         lowered_text):
        config = json.loads(m.group(1).replace("\\22", '"'))
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = str(ir.Module.parse(base64.b64decode(
                config["custom_call_config"]["body"])))
        bounds, = re.findall(r"iteration_bounds = array<i64: ([\d, ]+)>",
                             module)
        grids.append(tuple(int(n) for n in bounds.split(",")))
    return grids


@pytest.mark.parametrize("cell", list(CELLS))
def test_decode_read_is_one_kernel_with_a_grid_step_a_slot(one_chip, cell):
    q, pool, table, v_lanes = CELLS[cell]
    lowered, compiled = lowered_and_compiled(
        one_chip, q, pool, (q[0], table), v_lanes=v_lanes)
    assert mosaic_calls(compiled) == ["flash_decode"]
    assert kernel_grids(lowered) == [(q[0],)]


def test_chunk_read_is_one_kernel_with_a_grid_step_a_slot(one_chip):
    """openPangu's prefill chunk: 1,024 rows as 256 slots of 4 positions
    x 128 heads, one shared table row."""
    (_, heads, width), pool, table, v_lanes = \
        CELLS["openpangu-ultra-docqa-8k"]
    lowered, compiled = lowered_and_compiled(
        one_chip, (256, 4 * heads, width), pool, (1, table),
        v_lanes=v_lanes, tokens=4)
    assert mosaic_calls(compiled) == ["latent_chunk_attention"]
    assert kernel_grids(lowered) == [(256,)]


def test_int8_pool_keeps_the_tile_axis_on_its_grid(one_chip):
    """The fork by the pool's dtype: GPT-2 XL's shapes over an int8 pool
    with its f32 scales — 64 table entries are 4 tiles of 16."""
    q, pool, table, _ = CELLS["gpt2-xl-chat"]
    lowered, compiled = lowered_and_compiled(
        one_chip, q, pool, (q[0], table), pool_dtype=jnp.int8)
    assert mosaic_calls(compiled) == ["flash_decode"]
    assert kernel_grids(lowered) == [(q[0], 4)]
