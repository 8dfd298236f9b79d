"""The kernels of both hot paths compiled ahead of time for a described
v5e:2x2 at the cells' real widths — no chip, about two seconds each. They
decide GPT-2 XL (25 heads) against the gpt2-large fallback and guard every
later PR at no chip time. The topology is described inside a module-scoped
fixture, never at import; where it cannot be described the tests skip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


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


def compiled_text(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).compile().as_text()


def kernels(text):
    import re

    return {m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r"(\w+)\)*/pallas_call", line)] if m}


def qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
            for _ in range(3)]


# the training cells: (per-chip batch, 16 heads, seq, 64). At s4096 the fused
# backward's scoped VMEM grows with the batch (16.04 MB at b2, 18.29 MB at b4
# against a 16 MB limit): a fault of the program (PERF.md, open questions),
# which is why bert-large-s4096 runs at batch 1. The b4 case is kept as a
# non-strict xfail so that it reports the day it lowers.
@pytest.mark.parametrize("shape", [
    (32, 16, 512, 64), (1, 16, 4096, 64),
    pytest.param((4, 16, 4096, 64), marks=pytest.mark.xfail(
        reason="fused backward exceeds scoped VMEM at s4096 for batch >= 2",
        strict=False))], ids=["s512-b32", "s4096-b1", "s4096-b4"])
def test_flash_attention_forward_and_fused_backward(one_chip, shape):
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import _flash_blocks

    bq, bk = _flash_blocks(shape[2], shape[2])

    def loss(q, k, v):
        o = flash_attention(q, k, v, False, bq, bk, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                         *qkv(shape, one_chip))
    assert {"flash_attention_fwd", "flash_attention_bwd_fused"} <= kernels(text)


@pytest.mark.parametrize("shape", [(32, 16, 512, 64), (1, 16, 4096, 64)],
                         ids=["s512-b32", "s4096-b1"])
def test_flash_attention_two_pass_backward(one_chip, shape):
    """The streaming route (dkv, then dq) that sequences past the fused
    kernel's residency budget take."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    from flexflow_tpu.ops.attention import _flash_blocks

    bq, bk = _flash_blocks(shape[2], shape[2])
    bq, bk = fa._bwd_blocks(bq, bk, None, None, shape[2], shape[2], shape[3])
    q, k, v = qkv(shape, one_chip)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip)
    text = compiled_text(
        lambda q, k, v, o, lse, do: fa._flash_backward(
            q, k, v, o, lse, do, False, bq, bk, False, fused=False),
        q, k, v, q, lse, q)
    assert {"flash_attention_bwd_dkv", "flash_attention_bwd_dq"} <= kernels(text)


# the serving cell: 25 heads of 64; a prefill runs at its bucket's length
@pytest.mark.parametrize("seq", [256, 512, 768, 1024])
def test_causal_flash_forward_25_heads(one_chip, seq):
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import _flash_blocks

    bq, bk = _flash_blocks(seq, seq)
    text = compiled_text(
        lambda q, k, v: flash_attention(q, k, v, True, bq, bk,
                                        interpret=False),
        *qkv((1, 25, seq, 64), one_chip))
    assert "flash_attention_fwd" in kernels(text)


def test_flash_decode_25_heads_64_slots(one_chip):
    from flexflow_tpu.kernels.flash_decode import flash_decode

    slots, heads, hd, bs, pool, mb = 64, 25, 64, 16, 1400, 64
    q = jax.ShapeDtypeStruct((slots, heads, hd), jnp.bfloat16,
                             sharding=one_chip)
    kp = jax.ShapeDtypeStruct((pool, heads, bs, hd), jnp.bfloat16,
                              sharding=one_chip)
    tables = jax.ShapeDtypeStruct((slots, mb), jnp.int32, sharding=one_chip)
    n_keys = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    text = compiled_text(
        lambda q, k, v, t, n: flash_decode(q, k, v, t, n,
                                           sm_scale=1.0 / np.sqrt(hd)),
        q, kp, kp, tables, n_keys)
    assert "flash_decode" in kernels(text)
