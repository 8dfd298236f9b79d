"""Training data of a language model for the rehearsal: uniform token ids
from ``seed``, each sequence's labels its own ids shifted by one. The same
two functions as ``traffic/synthetic_set.py``."""
import numpy as np


def _streams(rng, n: int, seq: int, vocab: int):
    s = rng.integers(0, vocab, size=(n, seq + 1)).astype(np.int32)
    return s[:, :-1], s[:, 1:]


def generate(job: dict, seed: int, batch: int, config: dict):
    rng = np.random.default_rng([int(seed), 0x70C])
    return _streams(rng, int(job["batches_per_epoch"]) * batch,
                    int(job["seq_len"]), int(config["vocab_size"]))


def check_batch(job: dict, seed: int, batch: int, config: dict):
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    x, y = _streams(rng, 1, int(job["seq_len"]), int(config["vocab_size"]))
    return x, y, np.tile(x, (batch, 1)), np.tile(y, (batch, 1))
