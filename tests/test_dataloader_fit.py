"""fit's default shuffled epochs route through the native C++ BatchPipeline
and --profiling prints per-op times (VERDICT round-1 item 9)."""
import jax
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.data import dataloader
from flexflow_tpu.data.dataloader import batch_iterator


def _mlp(batch=16):
    config = FFConfig()
    config.batch_size = batch
    ff = FFModel(config)
    x = ff.create_tensor((batch, 8))
    t = ff.dense(x, 16)
    ff.softmax(ff.dense(t, 4))
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, config


def test_fit_default_shuffle_uses_native_pipeline(monkeypatch):
    import flexflow_tpu.native as native

    used = []
    real = native.BatchPipeline

    class SpyPipeline(real):
        def __init__(self, *a, **k):
            used.append(True)
            super().__init__(*a, **k)

    monkeypatch.setattr(native, "BatchPipeline", SpyPipeline)
    ff, _ = _mlp()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(48, 8)).astype(np.float32)
    ys = rng.integers(0, 4, size=(48, 1)).astype(np.int32)
    ff.fit(xs, ys, epochs=1)
    assert used, "shuffled fit did not stage through BatchPipeline"
    # opt-out still works
    used.clear()
    ff.fit(xs, ys, epochs=1, shuffle=False)
    assert not used


def test_fit_shuffle_changes_batch_order():
    seen = {}

    def run(shuffle):
        ff, _ = _mlp()
        rng = np.random.default_rng(0)
        xs = np.arange(48 * 8, dtype=np.float32).reshape(48, 8)
        ys = rng.integers(0, 4, size=(48, 1)).astype(np.int32)
        first = next(iter(batch_iterator([xs, ys], 16, shuffle=shuffle,
                                         seed=1)))
        return first[0][:, 0]

    unshuffled = run(False)
    shuffled = run(True)
    assert not np.array_equal(unshuffled, shuffled)


def test_profiling_prints_per_op_times(capsys):
    ff, config = _mlp()
    config.profiling = True
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(32, 8)).astype(np.float32)
    ys = rng.integers(0, 4, size=(32, 1)).astype(np.int32)
    ff.fit(xs, ys, epochs=1)
    out = capsys.readouterr().out
    assert "PER-OP PROFILE" in out
    assert "OP_LINEAR" in out and "us" in out
    # printed once even across repeated fits
    ff.fit(xs, ys, epochs=1)
    out2 = capsys.readouterr().out
    assert "PER-OP PROFILE" not in out2


# ------------------------------------------------------------------ PR 30
# the unshuffled batch is a slice of the set, not a fancy-index copy
def _copying_batch_iterator(arrays, batch_size, shuffle=False, seed=0,
                            drop_remainder=True, start_batch=0):
    """The unshuffled path as it was before PR 30: an index array, so numpy
    copies every batch row by row. The yardstick for values, and what the
    fit tests patch in."""
    if shuffle:
        yield from batch_iterator(arrays, batch_size, shuffle=True, seed=seed,
                                  drop_remainder=drop_remainder,
                                  start_batch=start_batch)
        return
    idx = np.arange(arrays[0].shape[0])[start_batch * batch_size:]
    m = len(idx)
    nb = m // batch_size if drop_remainder else -(-m // batch_size)
    for b in range(nb):
        sl = idx[b * batch_size:(b + 1) * batch_size]
        yield [a[sl] for a in arrays]


def _sources(layout, n_arrays, n=50):
    rng = np.random.default_rng(3)
    shapes = [(n, 6, 4), (n, 1), (n,)][:n_arrays]
    if layout == "c":
        return [rng.normal(size=s).astype(np.float32) for s in shapes]
    if layout == "strided_rows":  # every other row of a larger set
        return [rng.normal(size=(2 * n,) + s[1:]).astype(np.float32)[::2]
                for s in shapes]
    assert layout == "fortran"
    return [np.asfortranarray(rng.normal(size=s).astype(np.float32))
            for s in shapes]


@pytest.mark.parametrize("start_batch", [0, 2, 7])
@pytest.mark.parametrize("drop_remainder", [True, False])
@pytest.mark.parametrize("n_arrays", [1, 3])
@pytest.mark.parametrize("layout", ["c", "strided_rows", "fortran"])
def test_unshuffled_batches_are_views_equal_to_the_copies(
        layout, n_arrays, drop_remainder, start_batch):
    arrays = _sources(layout, n_arrays)  # 50 rows: six batches of 8 and 2
    kw = dict(drop_remainder=drop_remainder, start_batch=start_batch)
    got = list(batch_iterator(arrays, 8, **kw))
    want = list(_copying_batch_iterator(arrays, 8, **kw))
    full = max(6 - start_batch, 0)
    tail = 0 if drop_remainder or start_batch > 6 else 1
    assert len(got) == len(want) == full + tail
    for g, w in zip(got, want):
        assert len(g) == len(w) == n_arrays
        for a, src, ref in zip(g, arrays, w):
            assert a.shape == ref.shape and a.dtype == ref.dtype
            np.testing.assert_array_equal(a, ref)
            assert np.shares_memory(a, src) and not a.flags.owndata
            # what the device receives is the same rows, whatever the layout
            np.testing.assert_array_equal(np.asarray(jax.device_put(a)), ref)


@pytest.mark.parametrize("drop_remainder", [True, False])
@pytest.mark.parametrize("n_arrays", [1, 3])
def test_shuffled_batches_are_copies(n_arrays, drop_remainder):
    arrays = _sources("c", n_arrays)
    batches = list(batch_iterator(arrays, 8, shuffle=True, seed=4,
                                  drop_remainder=drop_remainder))
    assert len(batches) == (6 if drop_remainder else 7)
    for batch in batches:
        for a, src in zip(batch, arrays):
            assert a.flags.owndata and not np.shares_memory(a, src)
    rows = np.concatenate([b[0] for b in batches]).reshape(-1, 24)
    assert len(rows) == (48 if drop_remainder else 50)
    if not drop_remainder:  # every row of the set once, as before
        np.testing.assert_array_equal(
            np.sort(rows, axis=0), np.sort(arrays[0].reshape(-1, 24), axis=0))


def test_unshuffled_second_array_longer_than_the_first_is_cut_to_it():
    x, y = np.arange(20.0).reshape(10, 2), np.arange(14)
    got = list(batch_iterator([x, y], 4, drop_remainder=False))
    assert [b[1].tolist() for b in got] == [[0, 1, 2, 3], [4, 5, 6, 7],
                                            [8, 9]]


def _fit_data(n=56):
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(n, 8)).astype(np.float32)
    ys = rng.integers(0, 4, size=(n, 1)).astype(np.int32)
    return xs, ys


def _fit_losses_and_params(xs, ys, **fit_kw):
    ff, _ = _mlp()
    ff._telemetry_requested = True  # per-step losses, in process
    ff.fit(xs, ys, epochs=2, **fit_kw)
    losses = list(ff.get_telemetry().loss_history)
    params = jax.tree_util.tree_map(np.asarray, ff.params)
    return losses, params, ff.input_stats()


@pytest.mark.parametrize("layout", ["c", "strided_rows"])
def test_fit_unshuffled_bitwise_the_copying_iterator(monkeypatch, layout):
    xs, ys = _fit_data()
    if layout == "strided_rows":
        wide = np.zeros((2 * len(xs), 8), np.float32)
        wide[::2] = xs
        xs = wide[::2]
        assert not xs.flags.c_contiguous
    losses, params, stats = _fit_losses_and_params(xs, ys, shuffle=False)
    assert len(losses) == 6 and stats["copied_bytes"] == 0
    monkeypatch.setattr(dataloader, "batch_iterator", _copying_batch_iterator)
    old_losses, old_params, old_stats = _fit_losses_and_params(
        xs, ys, shuffle=False)
    assert old_stats["copied_bytes"] == 6 * 16 * (8 * 4 + 4)
    assert losses == old_losses  # floats, bit for bit
    flat, _ = jax.tree_util.tree_flatten(params)
    old_flat, _ = jax.tree_util.tree_flatten(old_params)
    assert len(flat) == len(old_flat) > 0
    for a, b in zip(flat, old_flat):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("shuffle", [False, True])
def test_fit_reads_x_and_y_in_place_and_leaves_them_bit_identical(shuffle):
    xs, ys = _fit_data()
    x0, y0 = xs.copy(), ys.copy()
    ff, _ = _mlp()
    ff.fit(xs, ys, epochs=2, shuffle=shuffle)
    ff.eval(xs, ys)
    ff.predict(xs)  # remainder batch, padded with a copy
    assert xs.tobytes() == x0.tobytes() and ys.tobytes() == y0.tobytes()


@pytest.mark.parametrize("shuffle,batches_copied", [(False, 0), (True, 3)])
def test_input_stats_copied_bytes(shuffle, batches_copied):
    xs, ys = _fit_data()
    ff, _ = _mlp()
    ff.fit(xs, ys, epochs=1, shuffle=shuffle)
    stats = ff.input_stats()
    assert stats["batches"] == 3
    # a batch: 16 rows of 8 float32 and one int32 label
    assert stats["copied_bytes"] == batches_copied * 16 * (8 * 4 + 4)
    if shuffle:  # with no remainder, the whole set's bytes
        ff.fit(xs[:48], ys[:48], epochs=1, shuffle=True)
        assert ff.input_stats()["copied_bytes"] == \
            xs[:48].nbytes + ys[:48].nbytes


def test_predict_unshuffled_views_of_a_non_contiguous_source():
    xs, _ = _fit_data(n=40)  # two batches of 16 and a remainder of 8
    ff, _ = _mlp()
    want = ff.predict(xs)
    assert want.shape[0] == 40
    np.testing.assert_array_equal(ff.predict(np.asfortranarray(xs)), want)
