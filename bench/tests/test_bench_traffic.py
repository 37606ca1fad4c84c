"""The traffic generator: one seed gives one schedule; other seeds give
the same work in another order."""

import copy

import numpy as np
import pytest

from bench import traffic
from bench.tests import tiny

BIG_SEED = 2 ** 33 + 17


@pytest.fixture(scope="module")
def chat():
    return tiny._read("bench/traffic/chat.json")


def _key(arrivals):
    return [(a.uid, a.due_s, a.prompt.tolist(), a.max_new_tokens)
            for a in arrivals]


def test_open_loop_same_seed_same_schedule(chat):
    a = traffic.open_loop(chat, BIG_SEED, 20.0, 152064)
    b = traffic.open_loop(chat, BIG_SEED, 20.0, 152064)
    assert _key(a) == _key(b)
    assert len(a) == round(chat["rate_per_s"] * 20.0)


def test_open_loop_seeds_share_the_work(chat):
    a = traffic.open_loop(chat, 1, 20.0, 152064)
    b = traffic.open_loop(chat, 2, 20.0, 152064)
    assert _key(a) != _key(b)
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
    ga, gb = np.diff([r.due_s for r in a]), np.diff([r.due_s for r in b])
    assert abs(ga.sum() - gb.sum()) < 1e-9
    for r in a:
        assert 32 <= len(r.prompt) <= 1536 and 16 <= r.max_new_tokens <= 512
        assert 0 <= r.due_s < 20.0
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 152064


def test_lengths_follow_the_stated_median(chat):
    q = traffic.quantiles(chat["prompt_tokens"], 1001)
    assert np.median(q) == chat["prompt_tokens"]["median"]


def test_warmup_is_seeded_and_fills_every_slot(chat):
    b1, s1 = traffic.warmup(chat, BIG_SEED, 1000, 32, 10 ** 6)
    b2, s2 = traffic.warmup(chat, BIG_SEED, 1000, 32, 10 ** 6)
    assert _key(b1) == _key(b2) and _key(s1) == _key(s2)
    assert len(b1) == 32 and all(a.due_s == 0.0 for a in b1)
    assert len({a.uid for a in b1 + s1}) == len(b1) + len(s1)


def test_closed_pool_same_seed_same_inputs():
    mix = tiny._read("bench/traffic/closed_b1.json")
    mix = dict(mix, pool=3)
    a = traffic.closed_pool(mix, BIG_SEED, (4, 4, 3))
    b = traffic.closed_pool(mix, BIG_SEED, (4, 4, 3))
    c = traffic.closed_pool(mix, BIG_SEED + 1, (4, 4, 3))
    assert a.shape == (3, 1, 4, 4, 3) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({x.tobytes() for x in a}) == 3


@pytest.mark.parametrize("edit", [
    lambda m: m.update(rate_per_s=0),
    lambda m: m.update(burstiness=2),
    lambda m: m.pop("drain"),
    lambda m: m.update(loop="sometimes"),
])
def test_check_mix_refuses_unknown_parameters(chat, edit):
    m = copy.deepcopy(chat)
    edit(m)
    with pytest.raises(ValueError):
        traffic.check_mix(m)
