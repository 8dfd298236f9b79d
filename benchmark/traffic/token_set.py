"""Training data of a language model: a synthetic set of
``batches_per_epoch`` batches of ``seq_len`` token ids, uniform over the
vocabulary rows the configuration holds (its ``vocab_size``), all from
``seed``; each sequence's labels are its own ids shifted by one (one
document a sequence, no packing). The same two functions as
``synthetic_set.py``; a job's shape is a data file beside this file
(``<job>.json``: ``seq_len``, ``batches_per_epoch``)."""
import numpy as np


def _streams(rng, n: int, seq: int, vocab: int):
    s = rng.integers(0, vocab, size=(n, seq + 1)).astype(np.int32)
    return np.ascontiguousarray(s[:, :-1]), np.ascontiguousarray(s[:, 1:])


def generate(job: dict, seed: int, batch: int, config: dict):
    """(ids, labels) of the whole set, (batches * batch, seq_len) int32."""
    rng = np.random.default_rng([int(seed), 0x70C])
    return _streams(rng, int(job["batches_per_epoch"]) * batch,
                    int(job["seq_len"]), int(config["vocab_size"]))


def check_batch(job: dict, seed: int, batch: int, config: dict):
    """One seeded sequence with its labels (the train driver passes its
    ``CHECK_SEED``: the comparison with the reference is about the program),
    alone and tiled to ``batch`` rows: the batch's mean loss and gradients
    are those of the one sequence, which is what the plain reference
    computes."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    x, y = _streams(rng, 1, int(job["seq_len"]), int(config["vocab_size"]))
    return x, y, np.tile(x, (batch, 1)), np.tile(y, (batch, 1))
