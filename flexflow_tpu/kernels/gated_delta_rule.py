"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464): the recurrence of a linear-attention mixer whose state is
a ``(d_k, d_v)`` MATRIX a head, decayed by a gate and corrected by a rank-one
delta a token.

    S_t = a_t S_{t-1} + k_t ( b_t ( v_t - (a_t S_{t-1})^T k_t ) )^T      a_t = exp(g_t)
    o_t = S_t^T q_t

``q`` and ``k`` arrive L2-normalised a head (``q`` already scaled by
``d_k^-1/2``), ``g <= 0`` is the log decay and ``b`` (beta) in (0, 2) the
write strength. Three forms of it live here:

* :func:`gated_delta_rule_reference` — a ``lax.scan`` over TOKENS: the path
  off the chip and the oracle of the two below.
* :func:`gated_delta_rule` — the Pallas kernel of a whole sequence in the
  CHUNKED matrix form (a prefill). With ``C = 64`` tokens a chunk, ``S_0``
  the state before it, ``y_i = sum_{j<=i} g_j`` inside the chunk and ``G_ij
  = exp(y_i - y_j)`` for ``i >= j`` (masked BEFORE it is exponentiated:
  every kept exponent is <= 0, nothing overflows however fast the decay):

      A   = strict_lower( diag(b) (G * K K^T) )                     (C, C)
      U   = (I + A)^-1 ( diag(b) V - diag(b e^y) K S_0 )            (C, d_v)
      O   = (Q * e^y) S_0 + lower_incl( G * Q K^T ) U
      S_C = e^{y_C} S_0 + ( K * e^{y_C - y} )^T U

  which follows from ``S_i = e^{y_i} S_0 + sum_{j<=i} e^{y_i - y_j} k_j
  u_j^T`` with ``u_j = b_j (v_j - (a_j S_{j-1})^T k_j)``. Six products on
  the matrix unit a chunk and head, and one unit-lower-triangular solve,
  done by forward substitution (backward stable whatever the keys: a run
  of EQUAL keys makes ``A`` all ones below the diagonal, where the product
  form of the inverse, ``(I - A)(I + A^2)(I + A^4)...``, cancels from
  1e17 down to 1). The grid is ``(batch, heads, chunks)``, the chunks
  innermost and sequential with the head's state carried in a VMEM scratch
  in float32 between them, so a grid step holds one chunk's blocks whatever
  the sequence's length.
* :func:`one_token_update` — the decode step's update of every slot's state,
  written so that the state is read ONCE: ``S^T k`` and ``S^T q`` come from
  the same pass, ``u = b (v - a S^T k)`` and ``o = a S^T q + (k . q) u``,
  then ``S <- a S + k u^T``. It and its kernel form,
  :func:`gated_delta_update`, take and return the state AS IT RESTS in a
  serving slot: ``p`` heads side by side on the lanes of one row, ``p``
  the least count that makes ``p * d_v`` whole 128-lane tiles
  (:func:`state_heads_a_row`, :func:`pack_state`, :func:`unpack_state`:
  the packing is defined there and nowhere else).

A row at or past its sequence's ``length`` must leave the state as it is (a
right-padded prompt: the state handed on is the one after the last REAL
token): ``g`` and ``b`` are forced to 0 there — ``a = 1``, ``u = 0`` — by
:func:`mask_gates`, so neither form has a mask of its own.

Everything is float32: the state lives for thousands of steps and a bf16
state would round by 2^-8 in each. Tests run the kernel in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

#: tokens a chunk: the (C, C) triangle is a quarter of an MXU tile's worth
#: of rows, the substitution's serial chain is C steps
CHUNK = 64


def use_gated_delta_rule() -> bool:
    """Routing gate for the mixer op: the kernel on a TPU, the ``lax.scan``
    elsewhere (tier-1 on the CPU reaches the kernel through
    ``interpret=True`` alone)."""
    from ._common import on_tpu

    return on_tpu()


def mask_gates(g, beta, lengths):
    """``g, beta (b, L, H)`` with the rows at and past each sequence's
    ``lengths (b,)`` forced to 0: those steps leave the state unchanged."""
    import jax.numpy as jnp

    if lengths is None:
        return g, beta
    t = jnp.arange(g.shape[1], dtype=jnp.int32)
    real = (t[None, :] < lengths[:, None])[..., None]
    return jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)


def gated_delta_rule_reference(q, k, v, g, beta, *, s0=None, lengths=None):
    """The recurrence as a plain ``lax.scan`` over tokens, float32.

    q, k (batch, L, H, d_k); v (batch, L, H, d_v); g, beta (batch, L, H);
    s0 (batch, H, d_k, d_v) or None for zeros; lengths (batch,) or None.
    Returns ``(o (batch, L, H, d_v), s_last (batch, H, d_k, d_v))``."""
    import jax.lax as lax
    import jax.numpy as jnp

    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    g, beta = mask_gates(g, beta, lengths)
    if s0 is None:
        s0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), f32)

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[..., None, None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s_last, o = lax.scan(step, s0.astype(f32), tuple(
        jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), s_last


#: what one grid step of the one-token update may hold of the state: the
#: block is double-buffered on its way in and on its way out
UPDATE_BLOCK_BYTES = 3 * 2 ** 19

#: the most heads that share a row of the state at rest
MAX_HEADS_A_ROW = 4


def state_heads_a_row(heads: int, dv: int) -> int:
    """``p``, the heads whose ``(d_k, d_v)`` matrices rest side by side in one
    row of the state: the least count for which ``p * d_v`` is whole
    128-lane tiles (2 at ``d_v`` 192; 1 at 128 or 256), taken when it
    divides ``heads`` and is at most ``MAX_HEADS_A_ROW``, else 1."""
    import math

    p = 128 // math.gcd(dv, 128)
    return p if p <= MAX_HEADS_A_ROW and heads % p == 0 else 1


def pack_state(s, p: Optional[int] = None):
    """``(..., H, d_k, d_v)``, a matrix a head, as it rests: ``(..., H / p,
    d_k, p * d_v)`` with ``p = state_heads_a_row(H, d_v)`` unless given; head
    ``r * p + i`` lies on row ``r``, lanes ``[i * d_v, (i + 1) * d_v)``. The
    inverse of :func:`unpack_state`; ``s`` itself at ``p = 1``."""
    import jax.numpy as jnp

    *lead, H, dk, dv = s.shape
    p = p or state_heads_a_row(H, dv)
    if p == 1:
        return s
    n = len(lead)
    s = s.reshape(*lead, H // p, p, dk, dv)
    return jnp.swapaxes(s, n + 1, n + 2).reshape(*lead, H // p, dk, p * dv)


def unpack_state(s, dv: int):
    """The state at rest ``(..., H / p, d_k, p * d_v)`` as a matrix a head,
    ``(..., H, d_k, d_v)``: the inverse of :func:`pack_state`."""
    import jax.numpy as jnp

    *lead, R, dk, W = s.shape
    p = W // dv
    if p == 1:
        return s
    n = len(lead)
    s = s.reshape(*lead, R, dk, p, dv)
    return jnp.swapaxes(s, n + 1, n + 2).reshape(*lead, R * p, dk, dv)


def one_token_update(s, q, k, v, g, beta):
    """One step of the recurrence for every row with ONE read of the state:
    ``s (rows, H / p, d_k, p * d_v)`` f32, the state as it rests
    (:func:`pack_state`), ``q, k (rows, H, d_k)``, ``v (rows, H, d_v)``,
    ``g, beta (rows, H)`` -> ``(o (rows, H, d_v), s_new)``, ``s_new`` as
    ``s`` rests."""
    import jax.numpy as jnp

    p = s.shape[-1] // v.shape[-1]
    s = unpack_state(s, v.shape[-1])
    a = jnp.exp(g)[..., None]                              # (rows, H, 1)
    sk = jnp.einsum("rhkv,rhk->rhv", s, k)
    sq = jnp.einsum("rhkv,rhk->rhv", s, q)
    u = beta[..., None] * (v - a * sk)
    o = a * sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, pack_state(a[..., None] * s + k[..., :, None] * u[..., None, :],
                         p)


def update_heads(heads: int, dk: int, dv: int) -> int:
    """Rows of heads a grid step of :func:`gated_delta_update` owns: the most
    that divide ``heads`` (the state's rows) with their (lane-padded)
    float32 state of ``dv`` lanes inside ``UPDATE_BLOCK_BYTES``, at least
    one."""
    lanes = -(-dv // 128) * 128
    fit = max(1, UPDATE_BLOCK_BYTES // (dk * lanes * 4))
    return max(h for h in range(1, heads + 1)
               if heads % h == 0 and h <= fit)


def _update_kernel(q_ref, k_ref, v_ref, a_ref, b_ref, kq_ref, s_ref, o_ref,
                   s_out_ref, *, rows, pack):
    """One (slot, tile of rows) grid step of the one-token update: each
    row's ``(d_k, p * d_v)`` state — ``p`` heads side by side — is read
    once, contracted with its heads' ``k`` and ``q`` down the sublanes, and
    written once."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    f32 = jnp.float32
    dk, width = s_ref.shape[-2:]
    dv = width // pack
    row = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    eye = jnp.where(row == col, 1.0, 0.0).astype(f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def columns(ref):
        # (heads, d_k) rows -> (d_k, heads) columns: I @ X^T, the matrix
        # unit's own transposed product, exact at "highest"
        return lax.dot_general(eye, ref[0, 0], (((1,), (1,)), ((), ())),
                               preferred_element_type=f32,
                               precision=lax.Precision.HIGHEST)

    def over_lanes(c, j):
        # row j's p columns, each over its own head's d_v lanes: a select a
        # head past the first, never a slice of lanes; (d_k, 1) at p = 1
        out = c[:, j * pack:j * pack + 1]
        for i in range(1, pack):
            out = jnp.where(lane >= i * dv,
                            c[:, j * pack + i:j * pack + i + 1], out)
        return out

    kc, qc = columns(k_ref), columns(q_ref)
    v, a, b, kq = v_ref[0, 0], a_ref[0, 0], b_ref[0, 0], kq_ref[0, 0]
    for j in range(rows):
        s = s_ref[0, j]                                     # (d_k, p d_v)
        k_j, q_j = over_lanes(kc, j), over_lanes(qc, j)
        a_j = a[j:j + 1]                                    # (1, p d_v)
        sk = jnp.sum(s * k_j, axis=0, keepdims=True)
        sq = jnp.sum(s * q_j, axis=0, keepdims=True)
        u = b[j:j + 1] * (v[j:j + 1] - a_j * sk)
        o_ref[0, 0, j:j + 1, :] = a_j * sq + kq[j:j + 1] * u
        s_out_ref[0, j] = a_j * s + k_j * u


def gated_delta_update(s, q, k, v, g, beta, *,
                       interpret: Optional[bool] = None):
    """The kernel form of :func:`one_token_update`: same arguments, same
    results, the state updated IN PLACE (its buffer is aliased onto the
    result's). ``p``, the heads a row of the state holds, is read from the
    shapes (``s.shape[-1] / d_v``). A grid step owns :func:`update_heads`
    rows of one slot; ``v``, the output and the per-head scalars
    (``exp(g)``, ``beta``, ``k . q``, each spread over its head's ``d_v``
    lanes, a thousandth of the state) ride ``p * d_v`` lanes a row, head-major
    as the projections lay them. The call is named ``gated_delta_update`` in
    the compiled program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ._common import resolve_interpret

    f32 = jnp.float32
    slots, R, dk, width = s.shape
    H, dv = v.shape[1:]
    p = width // dv
    if (R * p, p * dv) != (H, width):
        raise ValueError(f"gated_delta_update: a state {s.shape} does not "
                         f"hold {H} heads of {dv} lanes")
    rb = update_heads(R, dk, width)
    n_t = R // rb
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    lanes = lambda t: t.reshape(slots, n_t, rb, width)
    spread = lambda t: lanes(jnp.broadcast_to(t[..., None], (slots, H, dv)))
    heads = lambda t: t.reshape(slots, n_t, rb * p, dk)

    def tile(height, w):
        return pl.BlockSpec((1, 1, height, w), lambda r, t: (r, t, 0, 0))

    state = pl.BlockSpec((1, rb, dk, width), lambda r, t: (r, t, 0, 0))
    o, s_new = pl.pallas_call(
        functools.partial(_update_kernel, rows=rb, pack=p),
        grid=(slots, n_t),
        in_specs=[tile(rb * p, dk), tile(rb * p, dk)]
        + [tile(rb, width)] * 4 + [state],
        out_specs=[tile(rb, width), state],
        out_shape=[jax.ShapeDtypeStruct((slots, n_t, rb, width), f32),
                   jax.ShapeDtypeStruct(s.shape, f32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
        name="gated_delta_update",
    )(heads(q), heads(k), lanes(v), spread(jnp.exp(g)), spread(beta),
      spread(jnp.sum(k * q, axis=-1)), s.astype(f32))
    return o.reshape(slots, H, dv), s_new


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, yc_ref, yr_ref, b_ref, s0_ref,
                  o_ref, s_ref, s_scr, *, chunk, n_chunks):
    """One (sequence, head, chunk) grid step of the chunked form."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    f32 = jnp.float32
    dot = functools.partial(jnp.dot, preferred_element_type=f32,
                            precision=lax.Precision.HIGHEST)
    C = chunk
    q, k, kt, v = q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0, 0], v_ref[0, 0]
    yc, yr, bc = yc_ref[0, 0], yr_ref[0, 0, 0], b_ref[0, 0]
    s0 = s_scr[...]                                         # (d_k, d_v)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    keep = row >= col
    # exp(y_i - y_j) for i >= j: masked first, so no exponent is positive
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, yc - yr, 0.0)), 0.0)
    a = jnp.where(row > col, bc * decay * dot(k, kt), 0.0)
    ey = jnp.exp(yc)                                        # (C, 1)
    rhs = bc * v - (bc * ey) * dot(k, s0)
    # (I + A) U = rhs by forward substitution, column by column: once row j
    # is final, every later row i loses A[i, j] * U[j]. Rows are kept in
    # tiles of 8 (a sublane tile) so that a step touches only the tiles
    # that still hold a row past j.
    tile = 8 if C % 8 == 0 else C
    u = [rhs[t:t + tile] for t in range(0, C, tile)]
    at = [a[t:t + tile] for t in range(0, C, tile)]
    for j in range(C - 1):
        t0, r = divmod(j, tile)
        u_j = u[t0][r:r + 1, :]
        for t in range(t0, len(u)):
            u[t] = u[t] - at[t][:, j:j + 1] * u_j
    u = jnp.concatenate(u, axis=0)
    o_ref[0, 0] = dot(q * ey, s0) + dot(decay * dot(q, kt), u)
    y_last = yr[:, C - 1:C]                                 # (1, 1)
    # (K * e^{y_C - y})^T as rows: the transposed keys scaled a column. The
    # whole state's decay is spread over the lanes first, then the sublanes
    # (Mosaic has no broadcast of one number over both at once)
    e_last = jnp.broadcast_to(jnp.exp(y_last), (1, s0.shape[1]))
    s_new = e_last * s0 + dot(kt * jnp.exp(y_last - yr), u)
    s_scr[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _finish():
        s_ref[0, 0] = s_new


def gated_delta_rule(q, k, v, g, beta, *, s0=None, lengths=None,
                     interpret: Optional[bool] = None):
    """The kernel form of :func:`gated_delta_rule_reference`: same arguments,
    same results. A length that is no multiple of ``CHUNK`` is padded with
    rows of ``g = beta = 0`` (which keep the state) and the padding is cut
    from the result. The call is named ``gated_delta_rule`` in the compiled
    program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ._common import resolve_interpret

    f32 = jnp.float32
    batch, L, H, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    g, beta = mask_gates(g, beta, lengths)
    s0 = jnp.zeros((batch, H, dk, dv), f32) if s0 is None else s0.astype(f32)
    C = CHUNK
    n_c = -(-L // C)
    pad = n_c * C - L
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (g, beta))
    # heads outermost, and the in-chunk running sum of the log decay as a
    # column (a row of the chunk a sublane) and as a row (a lane)
    q, k, v = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))
    # the keys once more, a chunk's transposed: (b, H, chunks, dk, C)
    kt = jnp.swapaxes(k.reshape(batch, H, n_c, C, dk), 3, 4)
    g, beta = (jnp.transpose(t, (0, 2, 1)) for t in (g, beta))
    y = jnp.cumsum(g.reshape(batch, H, n_c, C), axis=-1)
    y_col = y.reshape(batch, H, n_c * C, 1)
    y_row = y.reshape(batch, H, n_c, 1, C)
    b_col = beta.reshape(batch, H, n_c * C, 1)

    def rows(width):
        return pl.BlockSpec((1, 1, C, width), lambda i, h, c: (i, h, c, 0))

    def chunked(height):
        return pl.BlockSpec((1, 1, 1, height, C),
                            lambda i, h, c: (i, h, c, 0, 0))

    def whole(i, h, c):
        return (i, h, 0, 0)

    o, s_last = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=C, n_chunks=n_c),
        grid=(batch, H, n_c),
        in_specs=[
            rows(dk), rows(dk),
            chunked(dk),
            rows(dv), rows(1),
            chunked(1),
            rows(1),
            pl.BlockSpec((1, 1, dk, dv), whole),
        ],
        out_specs=[rows(dv), pl.BlockSpec((1, 1, dk, dv), whole)],
        out_shape=[jax.ShapeDtypeStruct((batch, H, n_c * C, dv), f32),
                   jax.ShapeDtypeStruct((batch, H, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="gated_delta_rule",
    )(q, k, kt, v, y_col, y_row, b_col, s0)
    return jnp.transpose(o, (0, 2, 1, 3))[:, :L], s_last
