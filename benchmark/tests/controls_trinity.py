"""The controls of the ``trinity-mini`` comparison: one fault each, planted
in the PROGRAM under test, and then the benchmark's one command, unedited.
Every one has to print ``correct: false``; the sound tree prints true.

    python3 benchmark/tests/controls_trinity.py <control> --workload ... \\
        --seed ... --seconds ... --trace 0 [--cells ...]

  a  the routed experts' output dropped (the combine returns zeros)
  b  the window mask dropped on the sliding layers (they attend causally
     over the whole context)
  c  rotary positions dropped
  d  the grouped products' operands and cotangents in three mantissa bits
     (as fp8 e4m3 rounds, without its range: the nearest precision below
     the cell's bf16; ``test_check_seed.py``'s control, on the routed layer)
  e  d, and besides every projection's, dense and gated layer's, the
     router's and the head's matrices, inputs, outputs and their cotangents
     in three mantissa bits: the whole model one precision below the
     cell's (the flash kernels' inner products alone stay as they are)

The routed experts' three matrices and the router's kernel are not among the
compared gradients (``reference/trinity-mini.py`` says why), so a, and d
most of all, are caught through what they do to every cotangent that passes
the routed layers: on the chip d reads 1.4 to 1.5 times the sound tree on
EVERY compared weight (the flipped choices' error and d's ride on the same
cotangents), and passes the limit only on the expert layers' ``norm3`` /
``norm4`` gains. The readings at the rehearsal size and on the chip are in
PERF.md section 6 (PR 35)."""
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

    from flexflow_tpu.ops import attention, moe_ops

    if control == "a":
        plain = moe_ops.MoECombineOp.forward
        moe_ops.MoECombineOp.forward = lambda self, params, inputs, ctx: [
            0.0 * plain(self, params, inputs, ctx)[0]]
    elif control == "b":
        plain = attention.MultiHeadAttentionOp.forward

        def no_window(self, params, inputs, ctx):
            kept, self.attrs = self.attrs, {k: v for k, v in
                                            self.attrs.items()
                                            if k != "window"}
            try:
                return plain(self, params, inputs, ctx)
            finally:
                self.attrs = kept

        attention.MultiHeadAttentionOp.forward = no_window
    elif control == "c":
        attention._rotate_half_rope = lambda x, theta: x
    elif control in ("d", "e"):
        def round3(a):
            m, e = jnp.frexp(a.astype(jnp.float32))
            return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e).astype(a.dtype)

        @jax.custom_vjp
        def q(a):
            return round3(a)

        q.defvjp(lambda a: (round3(a), None), lambda _, g: (round3(g),))
        plain = jax.lax.ragged_dot

        def ragged_dot(lhs, rhs, group_sizes, **kwargs):
            return q(plain(q(lhs), q(rhs), group_sizes, **kwargs))

        jax.lax.ragged_dot = ragged_dot
        if control == "e":
            from flexflow_tpu.ops import linear

            def rounded(forward):
                def q_float(a, matrix=False):
                    ok = jnp.issubdtype(a.dtype, jnp.floating) and (
                        a.ndim >= 2 or not matrix)
                    return q(a) if ok else a

                def f(self, params, inputs, ctx):
                    # matrices alone: gains and the selection bias are no
                    # operand of a product
                    params = {k: q_float(v, matrix=True)
                              for k, v in params.items()}
                    return [q_float(y) for y in forward(
                        self, params, [q_float(x) for x in inputs], ctx)]
                return f

            for op in (linear.LinearOp, linear.GatedMLPOp,
                       attention.MultiHeadAttentionOp, moe_ops.MoERouterOp):
                op.forward = rounded(op.forward)
    else:
        raise SystemExit(f"controls_trinity.py: no control {control!r} "
                         "(a, b, c, d, e)")


def main() -> None:
    control = sys.argv.pop(1)
    plant(control)
    print(f"[bench] control {control} planted in the program", flush=True)
    sys.argv[0] = os.path.join(ROOT, "benchmark", "run.py")
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
