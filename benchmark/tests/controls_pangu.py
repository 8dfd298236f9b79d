"""The controls of the ``openpangu-ultra-docqa-8k`` comparison: one fault
each, planted in the PROGRAM under test, and then the benchmark's one
command, unedited. Every one has to print ``correct: false``; the sound tree
prints true.

    python3 benchmark/tests/controls_pangu.py <control> --workload ... \\
        --seed ... --seconds ... --trace 0 [--cells ...]

  a  the latent rows written to the pool in three mantissa bits (as fp8 e4m3
     rounds, without its range: the nearest precision below the cell's bf16)
  b  the absorbed projections' operands in three mantissa bits (``W_kvb``,
     the nope query going in and the kernel's weighted sum coming out), in
     the decode step alone
  c  the grouped products' operands in three mantissa bits
     (``controls_trinity.py``'s d, at decode and chunk shapes)
  d  ``k_r`` written unrotated (the queries keep their rotary positions)

What each does to the compared number (the limit is 0.06; the sound tree
reads 0 to 0.0011): on the chip at the cell's widths a 0.0855, b 0.0833,
d 1.049 — refused — and c 0, NOT refused: the comparison sees a fault only
where it moves a served token off the reference's best, and the held experts'
rounded contribution moved none (my chip runs, PR 37; PERF.md sections 6 and
7). At the rehearsal size (CPU) a 0.007, b 0.007, c 0, d 1.47."""
from __future__ import annotations

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plant(control: str) -> None:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import latent_attention as la

    def round3(a):
        m, e = jnp.frexp(a.astype(jnp.float32))
        return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e).astype(a.dtype)

    op = la.LatentAttentionOp
    if control == "a":
        plain = op._rows
        op._rows = lambda self, params, x, positions: round3(
            plain(self, params, x, positions))
    elif control == "b":
        plain = op._absorbed
        from flexflow_tpu.serving import kvcache

        read = kvcache.flash_decode_kv

        def absorbed(self, params, q_n, q_r, *rest, tokens=1):
            if tokens != 1:   # a chunk: the decode step's read alone
                return plain(self, params, q_n, q_r, *rest, tokens=tokens)
            params = dict(params, wkv_b=round3(params["wkv_b"]))
            kvcache.flash_decode_kv = lambda *a, **k: (
                lambda o: o if o is None else round3(o))(read(*a, **k))
            try:
                return plain(self, params, round3(q_n), q_r, *rest)
            finally:
                kvcache.flash_decode_kv = read

        op._absorbed = absorbed
    elif control == "c":
        plain = jax.lax.ragged_dot

        def ragged_dot(lhs, rhs, group_sizes, **kwargs):
            return plain(round3(lhs), round3(rhs), group_sizes, **kwargs)

        jax.lax.ragged_dot = ragged_dot
    elif control == "d":
        plain = la.rope_at

        def rope_at(x, positions, theta):
            # the shared rotary key is the one 2-D-per-token operand: the
            # queries come as (batch, heads, seq, rope)
            return x if x.ndim == 3 else plain(x, positions, theta)

        la.rope_at = rope_at
    else:
        raise SystemExit(f"controls_pangu.py: no control {control!r} "
                         "(a, b, c, d)")


def main() -> None:
    control = sys.argv.pop(1)
    plant(control)
    print(f"[bench] control {control} planted in the program", flush=True)
    sys.argv[0] = os.path.join(ROOT, "benchmark", "run.py")
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
