"""Microbench: einsum attention core vs Pallas flash at BERT-Large shapes.

Times fwd+bwd of the attention core (no projections) on the real chip for
(batch 8, heads 16, seq 512, head_dim 64) bf16 — the shape the flagship bench
runs. N iterations are chained inside ONE jit via lax.scan and the whole
scan is timed, so per-dispatch host time is amortized away. Run manually on
TPU; not part of the test suite.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

INNER = 50


def bench_core(core_fb, q, k, v, label):
    def body(carry, _):
        q, k, v = carry
        dq, dk, dv = core_fb(q, k, v)
        # chain to prevent DCE; cast keeps dtype stable
        return (q + 1e-6 * dq.astype(q.dtype),
                k + 1e-6 * dk.astype(k.dtype),
                v + 1e-6 * dv.astype(v.dtype)), ()

    @jax.jit
    def run(q, k, v):
        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None, length=INNER)
        return q

    out = run(q, k, v)
    _ = np.asarray(out[0, 0, 0, :1])  # compile + settle
    t0 = time.perf_counter()
    out = run(q, k, v)
    _ = np.asarray(out[0, 0, 0, :1])
    dt = (time.perf_counter() - t0) / INNER * 1e3
    print(f"{label}: {dt:.3f} ms/iter")
    return dt


def main():
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import mha_core

    b, h, s, d = 8, 16, 512, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(key, (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(key, (b, h, s, d), jnp.bfloat16)

    def loss_einsum(q, k, v):
        return jnp.sum(mha_core(q, k, v).astype(jnp.float32))

    bench_core(jax.grad(loss_einsum, argnums=(0, 1, 2)), q, k, v,
               "einsum core fwd+bwd")

    for bq, bk in [(128, 128), (256, 256), (512, 512), (256, 512),
                   (128, 256)]:
        if bq > s or bk > s:
            continue

        def loss_flash(q, k, v, bq=bq, bk=bk):
            return jnp.sum(flash_attention(q, k, v, False, bq, bk)
                           .astype(jnp.float32))

        try:
            bench_core(jax.grad(loss_flash, argnums=(0, 1, 2)), q, k, v,
                       f"flash bq={bq} bk={bk} fwd+bwd")
        except Exception as e:
            print(f"flash bq={bq} bk={bk}: FAILED {type(e).__name__}: "
                  f"{str(e)[:200]}")


if __name__ == "__main__":
    main()
