"""The ``trinity-mini`` cell's attention kernels compiled ahead of time for a
described v5e:2x2 at the cell's real shape — 32 query heads on 4 K/V heads of
128 at 8,192 positions, batch 1 — with no chip: the windowed and the full
forward, and the two-pass backward that (8192, 128) takes (the fused one's
residency budget ends at 4,096 positions for head_dim 128). As
``test_aot_v5e.py``; a file of its own because that one may not be edited by
the PR that brought this cell (run the two in one process)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.tests.test_aot_v5e import compiled_text, kernels

SHAPE_Q, SHAPE_KV, WINDOW = (1, 32, 8192, 128), (1, 4, 8192, 128), 2048


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


@pytest.mark.parametrize("window,suffix", [(None, ""), (WINDOW, "_window")],
                         ids=["full", "window"])
def test_grouped_flash_forward_and_two_pass_backward(one_chip, window, suffix):
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import _flash_blocks

    bq, bk = _flash_blocks(SHAPE_Q[2], SHAPE_Q[2])

    def loss(q, k, v):
        o = flash_attention(q, k, v, True, bq, bk, interpret=False,
                            window=window)
        return jnp.sum(o.astype(jnp.float32))

    q = jax.ShapeDtypeStruct(SHAPE_Q, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(SHAPE_KV, jnp.bfloat16, sharding=one_chip)
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert {f"flash_attention_fwd{suffix}", f"flash_attention_bwd_dkv{suffix}",
            f"flash_attention_bwd_dq{suffix}"} <= kernels(text)
