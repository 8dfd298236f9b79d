"""Pallas selective-scan kernel: the recurrence of a selective state-space
(Mamba-1) mixer over a whole sequence.

    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t      (N, E)
    y_t = S_t^T C_t                                            (E,)

``E`` channels, each with an ``N``-number state; ``dt``, ``B`` and ``C``
depend on the token (the selection), so the recurrence is no convolution and
``L`` tokens are ``L`` dependent steps. As a token-by-token ``lax.scan`` every
step is a separate pass over the ``(N, E)`` state in HBM; here the state of
a channel tile stays on the chip for the whole sequence:

* the channels ride the vector unit whole: ``E`` is folded to ``(8, E / 8)``
  — sublanes x lanes — and a grid step owns a tile of ``8 x 128`` channels,
  so one state row ``S[n]`` of the tile is exactly one vector register and
  the tile's whole state (``N`` of them, 16 at the published width) is
  carried in registers through the time loop beside ``A``'s ``N``. Nothing
  is reduced across lanes or sublanes: ``y`` is ``N`` multiply-adds of whole
  registers.
* ``B_t`` and ``C_t`` are ``N`` numbers a token shared by every channel:
  they are read as scalars from SMEM (a ``T * N`` block a time tile) and
  splat, which costs no vector-unit shuffle.
* grid = (batch, channel tiles, time tiles), time innermost and sequential;
  between time tiles the tile's state rests in a VMEM scratch, the final
  state is written once. ``x``, ``dt`` are read once and ``y`` written once.

A row at or past its sequence's ``length`` must leave the state as it is
(a right-padded prompt: the state handed on is the one after the last REAL
token). The wrapper forces ``dt`` to 0 there — ``exp(0 * A) = 1`` and
``0 * x (x) B = 0`` exactly — so the kernel has no mask.

Everything is float32: a bf16 state would round by 2^-8 a step for
thousands of steps. Off the chip the op layer runs
:func:`selective_scan_reference` (a ``lax.scan`` over tokens — also this
kernel's oracle); tests run the kernel in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

#: channels a grid step owns: one vector register (8 sublanes x 128 lanes)
#: a state row
TILE_CHANNELS = 8 * 128
#: tokens a grid step walks: three ``(T, 8, 128)`` f32 blocks (x, dt, y),
#: double-buffered, are 3 MiB; the two SMEM blocks 8 KiB each at N = 16
TILE_TOKENS = 128


def use_selective_scan(n_state: int) -> bool:
    """Routing gate for the mixer op: the kernel on a TPU where a time
    tile's ``B`` / ``C`` scalars fill whole 1,024-word SMEM rows (a state of
    8, 16, 32, ... numbers), the ``lax.scan`` form elsewhere (tier-1 on the
    CPU reaches the kernel through ``interpret=True`` alone)."""
    from ._common import on_tpu

    return (TILE_TOKENS // 2 * n_state) % 1024 == 0 and on_tpu()


def mask_dt(dt, lengths):
    """``dt (b, L, E)`` with the rows at and past each sequence's
    ``lengths (b,)`` forced to 0: those steps leave the state unchanged."""
    import jax.numpy as jnp

    if lengths is None:
        return dt
    t = jnp.arange(dt.shape[1], dtype=jnp.int32)
    return jnp.where((t[None, :] < lengths[:, None])[..., None], dt, 0.0)


def selective_scan_reference(x, dt, b, c, a, *, s0=None, lengths=None):
    """The recurrence as a plain ``lax.scan`` over tokens, float32.

    x, dt (batch, L, E); b, c (batch, L, N); a (N, E), negative; s0
    (batch, N, E) or None for zeros; lengths (batch,) or None. Returns
    ``(y (batch, L, E), s_last (batch, N, E))``, both float32."""
    import jax.lax as lax
    import jax.numpy as jnp

    f32 = jnp.float32
    x, dt, b, c, a = (t.astype(f32) for t in (x, dt, b, c, a))
    dt = mask_dt(dt, lengths)
    if s0 is None:
        s0 = jnp.zeros((x.shape[0],) + a.shape, f32)

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None, :] * a[None]) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s_last, ys = lax.scan(step, s0.astype(f32), tuple(
        jnp.swapaxes(t, 0, 1) for t in (x, dt, b, c)))
    return jnp.swapaxes(ys, 0, 1), s_last


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, s0_ref, y_ref, s_ref,
                 s_scr, *, tokens, n_state, n_time_tiles):
    """One (sequence, channel tile, time tile) grid step: ``tokens`` steps of
    the recurrence with the tile's state in registers."""
    import jax.lax as lax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    a = [a_ref[n] for n in range(n_state)]          # N x (8, 128)

    def step(t, s):
        dt = dt_ref[0, t]                           # (8, 128)
        dtx = dt * x_ref[0, t]
        y = jnp.zeros_like(dt)
        out = []
        for n in range(n_state):
            s_n = jnp.exp(dt * a[n]) * s[n] + dtx * b_ref[t * n_state + n]
            y = y + s_n * c_ref[t * n_state + n]
            out.append(s_n)
        y_ref[0, t] = y
        return tuple(out)

    s = lax.fori_loop(0, tokens, step,
                      tuple(s_scr[n] for n in range(n_state)))
    for n in range(n_state):
        s_scr[n] = s[n]

    @pl.when(j == n_time_tiles - 1)
    def _finish():
        s_ref[0] = s_scr[...]


def selective_scan(x, dt, b, c, a, *, s0=None, lengths=None,
                   interpret: Optional[bool] = None):
    """The kernel form of :func:`selective_scan_reference`: same arguments,
    same results. ``E`` and ``L`` that are no multiples of the tiles are
    padded here (channels with zeros, which keep a zero state; tokens with
    ``dt`` 0, which keep the state) and the padding is cut from the results.
    The call is named ``selective_scan`` in the compiled program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ._common import resolve_interpret

    f32 = jnp.float32
    batch, L, E = x.shape
    N = a.shape[0]
    x, dt, b, c, a = (t.astype(f32) for t in (x, dt, b, c, a))
    dt = mask_dt(dt, lengths)
    s0 = jnp.zeros((batch, N, E), f32) if s0 is None else s0.astype(f32)
    # a short sequence walks half a tile: the SMEM blocks of T * N scalars
    # rest in rows of 1,024 words (64 tokens at N = 16)
    T = TILE_TOKENS if L > TILE_TOKENS // 2 else TILE_TOKENS // 2
    Lp = -(-L // T) * T
    Ep = -(-E // TILE_CHANNELS) * TILE_CHANNELS
    pad_l, pad_e = Lp - L, Ep - E
    if pad_l or pad_e:
        x, dt = (jnp.pad(t, ((0, 0), (0, pad_l), (0, pad_e)))
                 for t in (x, dt))
        b, c = (jnp.pad(t, ((0, 0), (0, pad_l), (0, 0))) for t in (b, c))
        a = jnp.pad(a, ((0, 0), (0, pad_e)))
        s0 = jnp.pad(s0, ((0, 0), (0, 0), (0, pad_e)))
    lanes = Ep // 8
    n_e, n_t = Ep // TILE_CHANNELS, Lp // T
    # channel e sits at (e // lanes, e % lanes): a plain reshape, the same
    # for x, dt, y, A and the state
    x, dt = (t.reshape(batch, Lp, 8, lanes) for t in (x, dt))
    a = a.reshape(N, 8, lanes)
    s0 = s0.reshape(batch, N, 8, lanes)
    b, c = (t.reshape(batch * Lp * N) for t in (b, c))

    def scalars(i, e, j):
        return (i * n_t + j,)

    def tokens(i, e, j):
        return (i, j, 0, e)

    def state(i, e, j):
        return (i, 0, 0, e)

    y, s_last = pl.pallas_call(
        functools.partial(_scan_kernel, tokens=T, n_state=N,
                          n_time_tiles=n_t),
        grid=(batch, n_e, n_t),
        in_specs=[
            pl.BlockSpec((T * N,), scalars, memory_space=pltpu.SMEM),
            pl.BlockSpec((T * N,), scalars, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, T, 8, 128), tokens),
            pl.BlockSpec((1, T, 8, 128), tokens),
            pl.BlockSpec((N, 8, 128), lambda i, e, j: (0, 0, e)),
            pl.BlockSpec((1, N, 8, 128), state),
        ],
        out_specs=[pl.BlockSpec((1, T, 8, 128), tokens),
                   pl.BlockSpec((1, N, 8, 128), state)],
        out_shape=[jax.ShapeDtypeStruct((batch, Lp, 8, lanes), f32),
                   jax.ShapeDtypeStruct((batch, N, 8, lanes), f32)],
        scratch_shapes=[pltpu.VMEM((N, 8, 128), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="selective_scan",
    )(b, c, x, dt, a, s0)
    return (y.reshape(batch, Lp, Ep)[:, :L, :E],
            s_last.reshape(batch, N, Ep)[:, :, :E])
