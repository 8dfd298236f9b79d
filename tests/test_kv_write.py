"""The pool's one write primitive against its oracle.

``kvcache.write_kv_rows`` is, on the chip, the aliased Pallas call
``kv_write`` (run here in interpret mode) and elsewhere the ``.at[].set``
scatter it replaces — the oracle. It is a move, not arithmetic, so the
comparison is ``array_equal`` on the stored values, over the whole pool:
every byte the write does not name stays as it was. The garbage block is
where writes may be lost (free slots and pad rows meet there), so it is
held to "finite" alone.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.serving.kvcache import GARBAGE_BLOCK, write_kv_rows

HEADS, BS, LANES, N_BLOCKS = 3, 8, 32, 9


def _pool_and_rows(dtype, n_rows, seed=0):
    rng = np.random.default_rng(seed)

    def draw(shape):
        if dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape, np.int8))
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    return (draw((N_BLOCKS, HEADS, BS, LANES)),
            draw((n_rows, HEADS, LANES)))


def _assert_moved(out, pool, rows, block_ids, offsets):
    """``out`` is ``pool`` with each row at its place — compared with the
    scatter oracle outside the garbage block, where alone rows may
    collide."""
    oracle = pool.at[block_ids, :, offsets].set(rows)
    out, oracle = np.asarray(out), np.asarray(oracle)
    assert out.dtype == oracle.dtype
    live = np.arange(N_BLOCKS) != GARBAGE_BLOCK
    assert np.array_equal(out[live].view(np.uint8),
                          oracle[live].view(np.uint8))
    assert np.isfinite(out[GARBAGE_BLOCK].astype(np.float32)).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, jnp.float32],
                         ids=["bf16", "int8", "f32"])
def test_token_write_matches_the_scatter(dtype):
    """One row a slot: offset 0, offset ``block_size - 1``, two slots on
    either side of a block boundary, and three free slots that collide
    in the garbage block."""
    #            first row, last row, before / after a boundary, free x3
    block_ids = jnp.asarray([1, 2, 3, 4, 0, 0, 0], jnp.int32)
    offsets = jnp.asarray([0, BS - 1, BS - 1, 0, 0, 0, 0], jnp.int32)
    pool, rows = _pool_and_rows(dtype, 7)
    out = jax.jit(lambda p, r: write_kv_rows(
        p, r, block_ids, offsets, interpret=True))(pool, rows)
    _assert_moved(out, pool, rows, block_ids, offsets)
    # the garbage block holds one of the colliding rows or its old one
    g = np.asarray(out)[GARBAGE_BLOCK, :, 0]
    assert any(np.array_equal(g, np.asarray(c)) for c in
               (rows[4], rows[5], rows[6], pool[GARBAGE_BLOCK, :, 0]))


CHUNKS = {
    # name: (chunk length, start position, real rows, the slot's blocks)
    "aligned_full": (2 * BS, 0, 2 * BS, [3, 5]),
    "pad_rows": (2 * BS, BS, BS + 3, [2, 4, 6]),
    "three_blocks": (2 * BS, BS - 3, 2 * BS, [7, 1, 8]),
    "inside_one_block": (4, 2, 4, [5]),
    "only_pads": (BS, 3, 0, [4]),
    "one_row_at_the_block_end": (BS, BS - 1, 1, [6, 2]),
}


def _chunk_targets(name):
    chunk_len, start, n_new, blocks = CHUNKS[name]
    table = np.zeros((4,), np.int32)
    table[:len(blocks)] = blocks
    pos = start + np.arange(chunk_len)
    bi = np.where(np.arange(chunk_len) < n_new,
                  table[np.clip(pos // BS, 0, 3)], GARBAGE_BLOCK)
    return (jnp.asarray(bi, jnp.int32), jnp.asarray(pos % BS, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("name", sorted(CHUNKS))
def test_chunk_write_matches_the_scatter(name, dtype):
    """A prefill chunk's rows — successive positions of one slot — with
    pad rows, across three blocks, inside one: merged a block at a time
    and equal to the row-by-row scatter."""
    block_ids, offsets = _chunk_targets(name)
    pool, rows = _pool_and_rows(dtype, CHUNKS[name][0], seed=1)
    out = jax.jit(lambda p, r, b, o: write_kv_rows(
        p, r, b, o, consecutive=True, interpret=True))(
            pool, rows, block_ids, offsets)
    _assert_moved(out, pool, rows, block_ids, offsets)


@pytest.mark.parametrize("name", sorted(CHUNKS))
def test_chunk_write_gives_no_block_to_two_grid_steps(monkeypatch, name):
    """The hazard the interpreter does not show: the aliased call fetches
    step i + 1's block before step i's is written back, so two steps on
    one block lose the first's rows. Read from what the kernel is given:
    every block but the garbage block is named by at most one step, each
    step's rows are one run ``lo <= t < hi``, and a step without rows
    goes to the garbage block."""
    from flexflow_tpu.kernels import kv_write as mod

    seen = {}

    def spy(pool, rows, block_ids, lo, hi, *, interpret=None):
        seen.update(block_ids=np.asarray(block_ids), lo=np.asarray(lo),
                    hi=np.asarray(hi), rows=rows.shape)
        return pool

    monkeypatch.setattr(mod, "kv_write", spy)
    block_ids, offsets = _chunk_targets(name)
    pool, rows = _pool_and_rows(jnp.bfloat16, CHUNKS[name][0])
    with jax.disable_jit():
        write_kv_rows(pool, rows, block_ids, offsets, consecutive=True,
                      interpret=True)
    chunk_len, _start, n_new, blocks = CHUNKS[name]
    steps = (chunk_len + BS - 2) // BS + 1
    assert seen["rows"] == (steps, HEADS, BS, LANES)
    real = seen["block_ids"][seen["block_ids"] != GARBAGE_BLOCK]
    assert len(set(real)) == len(real), seen
    assert set(real) <= set(blocks)
    count = seen["hi"] - seen["lo"]
    assert (count >= 0).all() and (seen["hi"] <= BS).all()
    assert (seen["block_ids"][count == 0] == GARBAGE_BLOCK).all()
    # every real row is written, once
    assert count[seen["block_ids"] != GARBAGE_BLOCK].sum() == n_new


def test_rows_that_do_not_fit_the_pool_are_refused():
    from flexflow_tpu.kernels.kv_write import kv_write

    pool, rows = _pool_and_rows(jnp.float32, 2)
    z = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="do not fit"):
        kv_write(pool, rows[:, :, None, :-1], z, z, z + 1, interpret=True)
    with pytest.raises(ValueError, match="do not fit"):
        kv_write(pool, rows[:, :, None, :].astype(jnp.bfloat16), z, z,
                 z + 1, interpret=True)


def test_off_the_chip_the_write_is_the_scatter():
    """No TPU, no ``interpret``: the gate refuses and the same function
    is the ``.at[].set`` — what every CPU serving test runs."""
    from flexflow_tpu.kernels.kv_write import use_kv_write

    pool, rows = _pool_and_rows(jnp.float32, 2)
    assert not use_kv_write(pool)
    b, o = jnp.asarray([1, 2], jnp.int32), jnp.asarray([3, 4], jnp.int32)
    out = write_kv_rows(pool, rows, b, o)
    assert np.array_equal(np.asarray(out),
                          np.asarray(pool.at[b, :, o].set(rows)))
