"""The seeded traffic generator: determinism, distributions, due times."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 977


#: an open-loop chat mix, as a later cell's traffic file would state it
CHAT = {
    "kind": "poisson", "rate_per_s": 0.48,
    "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
               "lo": 32, "hi": 1536},
    "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
               "lo": 8, "hi": 448},
}


def mix(name):
    if name == "chat":
        return dict(CHAT)
    return json.loads((MIXES / f"{name}.json").read_text())


def summary(planned):
    return (sorted(len(p.prompt) for p in planned),
            sorted(p.max_new_tokens for p in planned),
            sorted(np.diff([0.0] + [p.due_s for p in planned]).round(9)))


@pytest.mark.parametrize("name", ["offline-long", "offline-batch", "chat"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 30, BIG_SEED, 1000)
    b = traffic.generate(mix(name), 30, BIG_SEED, 1000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.due_s) == (y.max_new_tokens, y.due_s)


@pytest.mark.parametrize("name", ["offline-long", "offline-batch", "chat"])
def test_every_seed_serves_the_same_work(name):
    a = traffic.generate(mix(name), 30, 1, 1000)
    b = traffic.generate(mix(name), 30, BIG_SEED, 1000)
    assert summary(a) == summary(b)
    reordered = [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert reordered == (mix(name).get("order", "seeded") == "seeded")
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_lengths_follow_their_distributions():
    rng = np.random.default_rng(0)
    logn = {"dist": "lognormal", "median": 512, "sigma": 0.8,
            "lo": 32, "hi": 1536}
    x = traffic.draw_lengths(rng, logn, 20000)
    assert x.min() >= 32 and x.max() <= 1536
    assert abs(np.median(x) / 512 - 1) < 0.05
    # the clip holds the share of the tail beyond it, and no more
    assert abs(np.mean(x == 1536) - 0.0853) < 0.01
    uni = traffic.draw_lengths(rng, {"dist": "uniform", "lo": 1024,
                                     "hi": 1536}, 20000)
    assert uni.min() == 1024 and uni.max() == 1536
    assert abs(uni.mean() - 1280) < 5
    fixed = traffic.draw_lengths(rng, {"dist": "fixed", "value": 448}, 5)
    assert list(fixed) == [448] * 5
    with pytest.raises(ValueError):
        traffic.draw_lengths(rng, {"dist": "zipf"}, 3)


def test_offline_requests_are_all_due_at_once():
    planned = traffic.generate(mix("offline-long"), 40, 3, 50304)
    assert len(planned) == mix("offline-long")["requests"]
    assert {p.due_s for p in planned} == {0.0}
    assert all(1024 <= len(p.prompt) <= 1536 for p in planned)
    assert all(p.prompt.dtype == np.int32 and p.prompt.max() < 50304
               for p in planned)


@pytest.mark.parametrize("seconds", [10, 40])
def test_open_loop_arrivals_fill_the_window(seconds):
    m = mix("chat")
    planned = traffic.generate(m, seconds, 11, 50304)
    due = np.array([p.due_s for p in planned])
    n = round(m["rate_per_s"] * seconds)
    assert len(planned) == n
    assert np.all(np.diff(due) >= 0) and due[0] > 0
    # n Poisson arrivals conditioned on the window: the last one lands
    # one mean gap before the close
    assert due[-1] == pytest.approx(seconds * n / (n + 1))
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.3)


def test_rate_override_changes_only_the_rate():
    m = mix("chat")
    planned = traffic.generate(m, 20, 5, 1000, rate_per_s=2.0)
    assert len(planned) == 40


def test_a_fixed_order_keeps_sizes_and_arrivals_in_place():
    m = dict(mix("chat"), order="fixed")
    a = traffic.generate(m, 20, 1, 1000)
    b = traffic.generate(m, 20, BIG_SEED, 1000)
    assert [(len(p.prompt), p.max_new_tokens, p.due_s) for p in a] == [
        (len(p.prompt), p.max_new_tokens, p.due_s) for p in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
