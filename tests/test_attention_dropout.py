"""Dropout on the fast attention paths (VERDICT r3 item 3): in-kernel
counter-based dropout for the Pallas flash kernel, and the same mask stream
on ring/Ulysses sequence parallelism — no silent drops anywhere.
Reference analog: cuDNN MHA's in-kernel dropout descriptor,
/root/reference/src/ops/attention.cu:225."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.flash_attention import (dropout_keep_scale_nd,
                                                  flash_attention)

B, H, S, D = 2, 4, 256, 64


def _qkv(seed=0, s=S):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(B, H, s, D)).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


def _ref_dropout_attn(q, k, v, seed, rate, causal=False):
    """Plain-jnp attention applying the SAME counter mask the kernels draw
    from — exact oracle for the flash path."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    bh = jnp.arange(q.shape[0] * q.shape[1], dtype=jnp.uint32).reshape(
        q.shape[0], q.shape[1], 1, 1)
    qp = jnp.arange(q.shape[2], dtype=jnp.int32)[:, None]
    kp = jnp.arange(k.shape[2], dtype=jnp.int32)[None, :]
    keep = dropout_keep_scale_nd(seed, bh, qp, kp, rate)
    out = jnp.einsum("bhqk,bhkd->bhqd", p * keep, v.astype(jnp.float32))
    return out.astype(v.dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_matches_mask_oracle(causal):
    q, k, v = _qkv()
    seed = jnp.uint32(1234)
    got = flash_attention(q, k, v, causal, 128, 128, dropout=0.1, seed=seed)
    want = _ref_dropout_attn(q, k, v, seed, 0.1, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_dropout_gradients_match_oracle():
    """The backward kernels regenerate the identical mask: grads of the
    flash path equal autodiff through the oracle."""
    q, k, v = _qkv(3)
    seed = jnp.uint32(77)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, False, 128, 128, dropout=0.2,
                            seed=seed)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        o = _ref_dropout_attn(q, k, v, seed, 0.2)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


def test_flash_dropout_zero_equals_no_dropout():
    q, k, v = _qkv(5)
    a = flash_attention(q, k, v, False, 128, 128)
    b = flash_attention(q, k, v, False, 128, 128, dropout=0.0,
                        seed=jnp.uint32(9))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_dropout_mean_field():
    """E[dropout attention] == no-dropout attention: averaging over seeds
    converges to the undropped output (loose tolerance, 32 seeds)."""
    q, k, v = _qkv(7)
    base = np.asarray(flash_attention(q, k, v, False, 128, 128),
                      dtype=np.float64)
    f = jax.jit(functools.partial(flash_attention, causal=False,
                                  block_q=128, block_k=128, dropout=0.3))
    acc = np.zeros_like(base)
    n = 32
    for i in range(n):
        acc += np.asarray(f(q, k, v, seed=jnp.uint32(1000 + i)),
                          dtype=np.float64)
    err = np.abs(acc / n - base).mean() / (np.abs(base).mean() + 1e-9)
    assert err < 0.15, err


def test_flash_dropout_requires_seed():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="seed"):
        flash_attention(q, k, v, False, 128, 128, dropout=0.1)


def _sp_mesh():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("data", "seq"))


@pytest.mark.parametrize("which", ["ring", "ulysses"])
def test_sp_dropout_mean_field_and_grads(which):
    """Ring/Ulysses with dropout: mean over seeds converges to the
    undropped output; gradients flow; dropout=0 is bit-identical to the
    no-dropout call."""
    from flexflow_tpu.kernels.ring_attention import ring_attention
    from flexflow_tpu.kernels.ulysses_attention import ulysses_attention

    fn = ring_attention if which == "ring" else ulysses_attention
    mesh = _sp_mesh()
    rng = np.random.default_rng(11)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(2, 4, 64, 16)).astype(np.float32)) * 0.3
    q, k, v = mk(), mk(), mk()

    @jax.jit
    def run(q, k, v, seed):
        return fn(q, k, v, mesh, dropout=0.25, seed=seed)

    @jax.jit
    def run_plain(q, k, v):
        return fn(q, k, v, mesh)

    base = np.asarray(run_plain(q, k, v), dtype=np.float64)
    same = np.asarray(jax.jit(lambda q, k, v: fn(
        q, k, v, mesh, dropout=0.0, seed=jnp.uint32(3)))(q, k, v))
    np.testing.assert_array_equal(same, np.asarray(run_plain(q, k, v)))

    acc = np.zeros_like(base)
    n = 24
    for i in range(n):
        acc += np.asarray(run(q, k, v, jnp.uint32(500 + i)),
                          dtype=np.float64)
    err = np.abs(acc / n - base).mean() / (np.abs(base).mean() + 1e-9)
    assert err < 0.2, err

    # gradients flow through the dropped SP path
    g = jax.grad(lambda q: jnp.sum(run(q, k, v, jnp.uint32(42)) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_sp_dropout_requires_seed():
    from flexflow_tpu.kernels.ring_attention import ring_attention
    from flexflow_tpu.kernels.ulysses_attention import ulysses_attention

    mesh = _sp_mesh()
    q = jnp.ones((2, 4, 64, 16), jnp.float32)
    for fn in (ring_attention, ulysses_attention):
        with pytest.raises(ValueError, match="seed"):
            fn(q, q, q, mesh, dropout=0.1)


def test_mha_op_uses_flash_with_dropout_when_training():
    """The op-level gate no longer bails to the einsum core for
    dropout>0 — a training forward on the flash path with dropout differs
    across rngs but matches shape/finite-ness, and eval ignores dropout."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import OpContext, op_class_for

    op = op_class_for(OperatorType.OP_SDPA)(
        "sdpa", {"dropout": 0.1, "causal": False, "use_flash": True},
        DataType.DT_FLOAT, num_inputs=3)
    q, k, v = _qkv(13)
    ctx_train = OpContext(training=True, rng=jax.random.PRNGKey(0))
    ctx_train2 = OpContext(training=True, rng=jax.random.PRNGKey(1))
    ctx_eval = OpContext(training=False, rng=None)
    o1 = op.forward({}, [q, k, v], ctx_train)[0]
    o2 = op.forward({}, [q, k, v], ctx_train2)[0]
    oe = op.forward({}, [q, k, v], ctx_eval)[0]
    assert not np.allclose(np.asarray(o1), np.asarray(o2))
    base = flash_attention(q, k, v, False, 128, 128)
    np.testing.assert_allclose(np.asarray(oe), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
