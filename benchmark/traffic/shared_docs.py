"""Open-loop document question-answering traffic: documents arrive as a
Poisson process conditioned on its count, and each is asked several times —
every ask is the document followed by a question of its own — so that later
asks share the document as a prefix with the first. ``open_loop.py``'s
interface (``Arrival``, ``generate``); a mix is a data file of parameters
beside this file, the cell fixes the rate, and the rate counts ASKS.

Everything is drawn from ``seed`` up front — document arrivals, how often each
is asked, the gaps between its asks, lengths, token ids — so the same seed
gives the same schedule whatever the server does (an open loop: a later ask is
due when the schedule says, not when the answer before it came), and every
seed offers the same amount of work — in the measured window, not only over
the horizon: the horizon is the mix's pre-roll and then the window, and the
count is conditioned on in each of the two apart (``rate x length / mean
asks`` documents in the pre-roll and in the window whatever the seed,
exponential gaps scaled to the segment), each segment's ask counts in equal
shares and its document lengths one draw from each of n equal-probability
strata, shuffled. A new document is nine tenths of an ask's prefill work, so
what is left to the seed is where in the segment the documents fall and
which later asks cross its edges. An ask that would fall due after the
horizon is not offered.

Parameters of a mix:

* ``doc_len`` / ``question_len`` / ``output_len``: ``{"median": m, "sigma":
  s, "min": a, "max": b}`` — lognormal, clipped (not resampled).
* ``asks_per_doc``: the ask counts, in equal shares.
* ``reask_fixed_s`` / ``reask_mean_s``: a later ask is due ``reask_fixed_s``
  plus an exponential gap of mean ``reask_mean_s`` after the one before it.
* ``prompt_len``: ``{"min", "max"}`` of document + question, which the
  driver reads to know which prefill buckets the mix can hit.
* ``max_total_tokens``: prompt + output never exceeds it; the output is cut.
* ``pre_roll_s`` / ``drain_grace_s`` / ``traced_drain_s``: as in
  ``open_loop.py``.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float            # seconds after the first possible arrival
    prompt: np.ndarray      # int32 token ids: the document, then the question
    max_new_tokens: int
    doc: int = -1           # which document this ask is about
    doc_len: int = 0        # the prompt's first doc_len ids are the document


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    u = rng.permutation((np.arange(n) + rng.random(n)) / n)
    z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(raw), int(spec["min"]),
                   int(spec["max"])).astype(np.int64)


def arrival_times(rng: np.random.Generator, n: int,
                  horizon_s: float) -> np.ndarray:
    """``n`` arrivals in the horizon: a Poisson process conditioned on its
    count (exponential gaps, scaled)."""
    gaps = rng.gamma(1.0, 1.0, size=n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * horizon_s


def generate(mix: dict, rate_rps: float, seed: int, horizon_s: float,
             vocab_size: int, initial_inflight: int = 0) -> List[Arrival]:
    """Every ask due in ``[0, horizon_s)``, in order of due time."""
    if initial_inflight:
        raise ValueError("shared_docs: a document's later asks need its "
                         "first; the window opens after a pre-roll, not "
                         "with requests in flight")
    rng = np.random.default_rng([int(seed), 0xD0C5])
    pre = float(mix.get("pre_roll_s", 0.0))
    counts = [int(c) for c in mix["asks_per_doc"]]
    edges = [0.0, pre, horizon_s] if 0.0 < pre < horizon_s \
        else [0.0, horizon_s]
    arrive, n_asks, doc_len = [], [], []
    for lo, hi in zip(edges, edges[1:]):   # the pre-roll, then the window
        n = max(int(round((hi - lo) * rate_rps / np.mean(counts))), 1)
        arrive.append(lo + arrival_times(rng, n, hi - lo))
        n_asks.append(rng.permutation(np.resize(counts, n)))
        doc_len.append(draw_lengths(rng, mix["doc_len"], n))
    arrive, n_asks, doc_len = (np.concatenate(x)
                               for x in (arrive, n_asks, doc_len))
    n_docs = len(arrive)
    total = int(n_asks.sum())
    question_len = draw_lengths(rng, mix["question_len"], total)
    output_len = draw_lengths(rng, mix["output_len"], total)
    gaps = float(mix["reask_fixed_s"]) + rng.exponential(
        float(mix["reask_mean_s"]), size=total)
    asks, k = [], 0
    for d in range(n_docs):
        doc = rng.integers(0, vocab_size,
                           size=int(doc_len[d])).astype(np.int32)
        due = float(arrive[d])
        for j in range(int(n_asks[d])):
            if j:
                due += float(gaps[k])
            question = rng.integers(
                0, vocab_size, size=int(question_len[k])).astype(np.int32)
            out = min(int(output_len[k]), int(mix["max_total_tokens"])
                      - len(doc) - len(question))
            if out < 1:
                raise ValueError("max_total_tokens leaves no room for an "
                                 "output token after the longest prompt")
            if due < horizon_s:
                asks.append((due, k, np.concatenate([doc, question]), out,
                             d, len(doc)))
            k += 1
    asks.sort(key=lambda a: (a[0], a[1]))
    return [Arrival(i, due, prompt, out, d, n)
            for i, (due, _k, prompt, out, d, n) in enumerate(asks)]
