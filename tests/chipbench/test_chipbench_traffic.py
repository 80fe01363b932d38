"""The traffic generator: repeatable per seed, the same work for every seed,
and the stated distributions."""
import statistics

import numpy as np
import pytest

from chipbench import traffic

CHAT = {"kind": "poisson", "rate_per_s": 2.0, "block": 16, "trace_seed": 1,
        "prompt": {"median": 384, "sigma": 0.8, "min": 64, "max": 1536},
        "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 384}}
DOCS = {"kind": "batch", "backlog_per_slot": 2, "block": 16, "trace_seed": 1,
        "prompt": {"median": 2048, "sigma": 0.4, "min": 1024, "max": 3584},
        "output": {"median": 96, "sigma": 0.5, "min": 32, "max": 256}}


def take(mix, seed, n=None, seconds=None):
    it = traffic.generate(mix, seed, vocab=1000, seconds=seconds)
    return [r for _, r in zip(range(n), it)] if n else list(it)


def test_same_seed_repeats_and_seeds_differ():
    a, b = take(CHAT, 7, seconds=40), take(CHAT, 7, seconds=40)
    assert a == b
    c = take(CHAT, 2**33 + 7, seconds=40)  # seeds beyond 32 bits are distinct
    assert [r.prompt for r in a[:5]] != [r.prompt for r in c[:5]]


def test_every_seed_replays_the_same_trace():
    for mix in (CHAT, DOCS):
        a, b = take(mix, 1, n=40, seconds=100), take(mix, 2**33 + 1, n=40, seconds=100)
        assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == \
            [(len(r.prompt), r.max_new, r.due_s) for r in b]
        assert [r.prompt for r in a] != [r.prompt for r in b]  # the seed draws the tokens
        other = take(dict(mix, trace_seed=2), 1, n=32, seconds=100)
        # another trace: the same sizes in each block of 16, in another order
        assert sorted(len(r.prompt) for r in other) == sorted(len(r.prompt) for r in a[:32])
        assert [len(r.prompt) for r in other] != [len(r.prompt) for r in a[:32]]


def test_lengths_follow_the_clipped_lognormal():
    p = traffic.lengths(CHAT["prompt"], 16)
    assert p.min() >= 64 and p.max() <= 1536
    assert statistics.median(p) == pytest.approx(384, rel=0.1)
    o = traffic.lengths(CHAT["output"], 16)
    assert statistics.median(o) == pytest.approx(128, rel=0.1)


def test_poisson_gaps_have_the_stated_rate():
    g = traffic.gaps(2.0, 16)
    assert g.mean() == pytest.approx(0.5)
    assert np.all(np.diff(np.sort(g)) > 0)  # exponential quantiles, all distinct
    reqs = take(CHAT, 5, seconds=80)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 80
    assert len(reqs) == pytest.approx(160, abs=16)


def test_prefix_and_batch():
    mix = dict(CHAT, prefix_tokens=64)
    reqs = take(mix, 3, n=10, seconds=100)
    head = reqs[0].prompt[:64]
    assert all(r.prompt[:64] == head for r in reqs)
    assert all(r.due_s is None for r in take(DOCS, 3, n=100))  # never runs dry


def test_validate_refuses_what_cannot_fit():
    traffic.validate(CHAT, 2048)
    with pytest.raises(ValueError):
        traffic.validate(CHAT, 1024)
    with pytest.raises(ValueError):
        traffic.validate(dict(CHAT, kind="closed"), 2048)
