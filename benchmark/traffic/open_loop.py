"""Open-loop serving traffic: Poisson arrivals conditioned on their count,
clipped lognormal lengths drawn by strata, distinct prompts. A mix is a data
file of parameters beside this file (``<mix>.json``); the cell fixes the rate.
Traffic of another shape (bursts, sessions, shared prefixes) brings a
generator file of its own, found by the ``generator`` name in its mix.

Everything is drawn from ``seed`` up front — arrival times, lengths, token
ids — so the same seed gives the same schedule whatever the server does
(an open loop: a request is due when the schedule says, not when an earlier
one finished), and every seed offers the same amount of work: the number of
arrivals in the horizon is rate x horizon (exponential gaps scaled to the
horizon), and each length list holds one draw from each of n
equal-probability strata, shuffled.

Parameters of a mix:

* ``prompt_len`` / ``output_len``: ``{"median": m, "sigma": s, "min": a,
  "max": b}`` — lognormal, clipped (not resampled).
* ``max_total_tokens``: prompt + output never exceeds it; the output is cut.
* ``pre_roll_s``: arrivals begin this long before the measured window.
* ``drain_grace_s`` / ``traced_drain_s``: how long after the window the run
  may go on (the driver says what for).
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
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    u = rng.permutation((np.arange(n) + rng.random(n)) / n)
    z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(raw), int(spec["min"]),
                   int(spec["max"])).astype(np.int64)


def arrival_times(rng: np.random.Generator, rate_rps: float,
                  horizon_s: float) -> np.ndarray:
    n = max(int(round(horizon_s * rate_rps)), 1)
    # gamma of shape 1: exponential gaps (a Poisson process)
    gaps = rng.gamma(1.0, 1.0 / rate_rps, size=n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * horizon_s


def generate(mix: dict, rate_rps: float, seed: int, horizon_s: float,
             vocab_size: int, initial_inflight: int = 0) -> List[Arrival]:
    """Every request due in ``[0, horizon_s)``, in order of due time.

    ``initial_inflight`` requests more are due at time 0 with a uniform share
    of their drawn output length left to run: the occupancy a server in
    steady state would already hold, so that a short pre-roll opens the
    window at steady occupancy and not during the ramp."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    due = arrival_times(rng, rate_rps, horizon_s)
    k = int(initial_inflight)
    due = np.concatenate([np.zeros(k), due])
    n = len(due)
    prompt_len = draw_lengths(rng, mix["prompt_len"], n)
    output_len = draw_lengths(rng, mix["output_len"], n)
    output_len = np.minimum(output_len,
                            int(mix["max_total_tokens"]) - prompt_len)
    output_len[:k] = np.maximum(np.rint(output_len[:k] * rng.random(k)), 1)
    if (output_len < 1).any():
        raise ValueError("max_total_tokens leaves no room for an output "
                         "token after the longest prompt")
    return [Arrival(i, float(due[i]),
                    rng.integers(0, vocab_size,
                                 size=int(prompt_len[i])).astype(np.int32),
                    int(output_len[i]))
            for i in range(n)]
