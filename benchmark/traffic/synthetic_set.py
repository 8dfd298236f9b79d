"""The one general generator of training data for proxies that take float
activations: a synthetic set of ``batches_per_epoch`` batches of
``(batch, seq_len, hidden)`` standard-normal float32 and uniform random
labels, all from ``seed``. A job's shape is a data file beside this file
(``<job>.json``): ``seq_len``, ``batches_per_epoch``, ``num_classes``;
``hidden`` is the configuration's ``hidden_size``. A model that takes other
inputs (token ids) brings a generator file of its own with the same two
functions."""
from __future__ import annotations

import numpy as np


def generate(job: dict, seed: int, batch: int, config: dict):
    """(x, y) of the whole set. Each batch has a generator of its own, seeded
    by (seed, batch index), so four threads fill the set (numpy releases the
    lock while it draws) and the data do not depend on how many did."""
    from concurrent.futures import ThreadPoolExecutor

    nb, seq = int(job["batches_per_epoch"]), int(job["seq_len"])
    hidden = int(config["hidden_size"])
    x = np.empty((nb * batch, seq, hidden), np.float32)

    def fill(b: int) -> None:
        rng = np.random.default_rng([int(seed), 0x5E7, b])
        rng.standard_normal(out=x[b * batch:(b + 1) * batch],
                            dtype=np.float32)

    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(fill, range(nb)))
    y = np.random.default_rng([int(seed), 0x1ABE1]).integers(
        0, int(job.get("num_classes", 2)), size=(nb * batch,)
    ).astype(np.int32)
    return x, y


def check_batch(job: dict, seed: int, batch: int, config: dict):
    """One seeded sequence with label 0 (the train driver passes its
    ``CHECK_SEED``, never the run's seed: the comparison with the reference
    is about the program), alone and repeated to a batch of ``batch`` rows: the batch's mean loss and gradients are those of the one
    sequence, which is what the plain reference computes. One sequence and
    not two: the gradients of two sequences with different labels largely
    cancel in the last layers and in every bias, and the relative error of
    what is left swung 0.5-25% from seed to seed (PERF.md, correct)."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    x = rng.standard_normal(size=(1, int(job["seq_len"]),
                                  int(config["hidden_size"])),
                            dtype=np.float32)
    y = np.zeros(1, np.int32)
    return x, y, np.tile(x, (batch, 1, 1)), np.tile(y, batch)
