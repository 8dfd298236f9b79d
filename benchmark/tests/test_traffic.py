"""The generators: the same seed gives the same schedule, lengths and
batches; clips and the total-length rule hold."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "traffic", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def chat():
    with open(os.path.join(BENCH, "traffic", "chat.json")) as f:
        return json.load(f)


def test_same_seed_same_schedule(chat):
    gen = load("open_loop")
    a = gen.generate(chat, 8.0, 3, 33.0, 50257)
    b = gen.generate(chat, 8.0, 3, 33.0, 50257)
    c = gen.generate(chat, 8.0, 4, 33.0, 50257)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all((x.prompt == y.prompt).all() and
               x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert [x.due_s for x in a] != [x.due_s for x in c]


def test_rate_clips_and_total(chat):
    gen = load("open_loop")
    reqs = gen.generate(chat, 8.0, 11, 400.0, 50257)
    assert len(reqs) == 3200
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() >= 16 and p.max() == 768          # the clip is hit
    assert o.min() >= 8 and o.max() <= 256
    assert (p + o).max() <= 1024
    assert np.median(p) == pytest.approx(192, rel=0.1)
    assert np.median(o) == pytest.approx(96, rel=0.1)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) > 0).all() and due[-1] < 400.0
    assert all(0 <= t < 50257 for r in reqs[:20] for t in r.prompt)


def test_total_rule_cuts_the_output():
    gen = load("open_loop")
    mix = {"prompt_len": {"median": 900, "sigma": 0.0, "min": 1, "max": 999},
           "output_len": {"median": 256, "sigma": 0.0, "min": 1, "max": 999},
           "max_total_tokens": 1024}
    reqs = gen.generate(mix, 5.0, 0, 10.0, 100)
    assert {r.max_new_tokens for r in reqs} == {124}


def test_every_seed_offers_the_same_work(chat):
    """The count is rate x horizon whatever the seed, and stratified lengths
    sum to nearly the same."""
    gen = load("open_loop")
    sets = [gen.generate(chat, 1.4, seed, 55.0, 50257, initial_inflight=40)
            for seed in range(6)]
    assert {len(s) for s in sets} == {77 + 40}
    totals = [sum(r.max_new_tokens for r in s[40:]) for s in sets]
    assert (max(totals) - min(totals)) / np.mean(totals) < 0.05
    # the requests in flight at time 0 have a share of their output left
    assert all(r.due_s == 0.0 for r in sets[0][:40])
    gaps = np.diff([r.due_s for r in gen.generate(chat, 10.0, 1, 2000.0, 9)])
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, rel=0.1)


def test_synthetic_set_is_seeded():
    gen = load("synthetic_set")
    job = {"seq_len": 8, "batches_per_epoch": 3, "num_classes": 2}
    config = {"hidden_size": 16}
    x1, y1 = gen.generate(job, 5, 4, config)
    x2, y2 = gen.generate(job, 5, 4, config)
    x3, _ = gen.generate(job, 6, 4, config)
    assert x1.shape == (12, 8, 16) and x1.dtype == np.float32
    assert (x1 == x2).all() and (y1 == y2).all() and not (x1 == x3).all()
    cx, cy, tx, ty = gen.check_batch(job, 5, 4, config)
    assert tx.shape == (4, 8, 16) and (tx == cx).all() and list(ty) == [0] * 4
