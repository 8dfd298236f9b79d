"""The flash kernels' grouped-query and sliding-window forms (interpret
mode) against the einsum core: forward, and both backward schedules (fused
one-pass, two-pass streaming), for window x K/V groups x head_dim."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

SEQ, BQ, BK = 256, 64, 64


def _qkv(heads, kv_heads, d, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((2, heads, SEQ, d), dtype=np.float32)
    k = rng.standard_normal((2, kv_heads, SEQ, d), dtype=np.float32)
    v = rng.standard_normal((2, kv_heads, SEQ, d), dtype=np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


CASES = [(window, group, d) for window in (None, 48, 100)
         for group in (1, 4) for d in (64, 128)]


@pytest.mark.parametrize("window,group,d", CASES)
def test_forward_matches_einsum_core(window, group, d):
    q, k, v = _qkv(4, 4 // group, d)
    out = fa.flash_attention(q, k, v, True, BQ, BK, interpret=True,
                             window=window)
    ref = fa._reference_core(q, k, v, True, window)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two-pass"])
@pytest.mark.parametrize("window,group,d", CASES)
def test_backward_matches_einsum_core(window, group, d, fused):
    q, k, v = _qkv(4, 4 // group, d, seed=1)
    do = jnp.asarray(np.random.default_rng(2).standard_normal(
        q.shape, dtype=np.float32))
    ref, vjp = jax.vjp(lambda q, k, v: fa._reference_core(q, k, v, True,
                                                          window), q, k, v)
    out, lse = fa._flash_forward(q, k, v, True, BQ, BK, True, window=window)
    got = fa._flash_backward(q, k, v, out, lse, do, True, BQ, BK, True,
                             fused=fused, window=window)
    for g, r, name in zip(got, vjp(do), "qkv"):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


def test_grad_through_the_public_entry_point():
    q, k, v = _qkv(4, 1, 64, seed=3)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, True, BQ, BK, interpret=True, window=48)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: fa._reference_core(
        q, k, v, True, 48)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-4)


def test_windowed_kernels_carry_their_own_names():
    q, k, v = _qkv(4, 1, 64)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, True, BQ, BK, interpret=True,
                           window=48)), argnums=(0, 1, 2)))(q, k, v))
    assert "flash_attention_fwd_window" in text
    assert "flash_attention_bwd_fused_window" in text
    full = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, True, BQ, BK, interpret=True))(q, k, v))
    assert "flash_attention_fwd" in full and "_window" not in full


@pytest.mark.parametrize("bad", [
    dict(heads=4, kv=3, causal=True, window=None),
    dict(heads=4, kv=4, causal=False, window=16)])
def test_shapes_the_kernels_cannot_serve_raise(bad):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, bad["heads"], 128, 64),
                                        dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((1, bad["kv"], 128, 64),
                                        dtype=np.float32))
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, k, bad["causal"], 64, 64, interpret=True,
                           window=bad["window"])
