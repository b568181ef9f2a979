"""The generators are deterministic in the seed, keep to the length sets,
the rate and the window, and give every seed the same amount of work."""
from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

from tiny_cells import BENCH, loop_module

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
SEEDS = [1, 2 ** 31 + 12345, 2 ** 40 + 3]


def _gen(seed, seconds=30.0):
    return loop_module("serve").generate(CHAT, seed, seconds, 49155)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(seed):
    a, b = _gen(seed), _gen(seed)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["arrival_s"] == y["arrival_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


def test_seeds_change_order_not_work():
    a, b = _gen(SEEDS[0]), _gen(SEEDS[1])
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    # the same gaps between arrivals, but for the one left after the last
    gaps = [Counter(np.round(np.diff([r["arrival_s"] for r in x]), 9))
            for x in (a, b)]
    assert sum((gaps[0] & gaps[1]).values()) >= len(a) - 2


def test_lengths_rate_and_window():
    seconds = 30.0
    reqs = _gen(SEEDS[1], seconds)
    assert len(reqs) == round(CHAT["rate_per_s"] * seconds)
    assert {len(r["prompt"]) for r in reqs} <= set(CHAT["prompt_lengths"])
    out = CHAT["output"]
    assert all(out["min"] <= r["max_new_tokens"] <= out["max"]
               for r in reqs)
    arrivals = [r["arrival_s"] for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[0] == 0.0
    assert arrivals[-1] < seconds
    rate = (len(reqs) - 1) / arrivals[-1]
    assert abs(rate - CHAT["rate_per_s"]) < 0.1 * CHAT["rate_per_s"]
    ids = np.concatenate([r["prompt"] for r in reqs])
    assert ids.min() >= 0 and ids.max() < 49155


def test_length_weights_are_kept():
    reqs = _gen(SEEDS[0], 40.0)
    n = len(reqs)
    got = Counter(len(r["prompt"]) for r in reqs)
    w = np.asarray(CHAT["prompt_weights"]) / sum(CHAT["prompt_weights"])
    for length, share in zip(CHAT["prompt_lengths"], w):
        assert abs(got[length] - share * n) <= 1


def test_training_rows_differ_and_repeat():
    train = loop_module("train")
    job = {"batch": 2, "seq": 64}
    a = train.batch_at(job, SEEDS[1], 0, 500)
    assert np.array_equal(a["tokens"], train.batch_at(job, SEEDS[1], 0,
                                                      500)["tokens"])
    b = train.batch_at(job, SEEDS[1], 1, 500)
    rows = [tuple(r) for x in (a, b) for r in x["tokens"]]
    assert len(set(rows)) == 4
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_length_weights_are_the_stated_lognormal():
    """The chat mix's weights are its lognormal's mass between the
    geometric midpoints of the length set, each within rounding."""
    import math
    from statistics import NormalDist

    lengths = CHAT["prompt_lengths"]
    median, sigma = CHAT["prompt_median"], CHAT["prompt_sigma"]
    edges = [math.sqrt(a * b) for a, b in zip(lengths, lengths[1:])]
    cdf = [0.0] + [NormalDist().cdf(math.log(e / median) / sigma)
                   for e in edges] + [1.0]
    want = [b - a for a, b in zip(cdf, cdf[1:])]
    assert CHAT["prompt_weights"] == pytest.approx(want, abs=1e-4)
