"""The generator: every seed offers one fixed schedule of work."""
import numpy as np

from harness import traffic

MIX = {"loop": "open", "rate_rps": 4.0,
       "burst": {"multiplier": 3.0, "mean_on_s": 2.0, "mean_off_s": 8.0},
       "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                  "min": 64, "max": 2048, "round_up": 64},
       "output": {"dist": "uniform", "min": 16, "max": 512},
       "layout_seed": 1606}


def _key(offers):
    return sorted((len(o.prompt), o.max_new_tokens) for o in offers)


def test_same_seed_same_requests_large_seed():
    a = traffic.offers(MIX, seconds=30, seed=2 ** 31 + 11, vocab=100)
    b = traffic.offers(MIX, seconds=30, seed=2 ** 31 + 11, vocab=100)
    assert [o.at_s for o in a] == [o.at_s for o in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_offer_the_same_schedule():
    a = traffic.offers(MIX, seconds=30, seed=1, vocab=100)
    b = traffic.offers(MIX, seconds=30, seed=2, vocab=100)
    assert [o.at_s for o in a] == [o.at_s for o in b]
    assert _key(a) == _key(b)
    assert [len(o.prompt) for o in a] == [len(o.prompt) for o in b]
    assert [o.max_new_tokens for o in a] == [o.max_new_tokens for o in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_in_range_and_rounded():
    rng = np.random.default_rng(0)
    x = traffic.draw_lengths(MIX["prompt"], rng, 5000)
    assert x.min() >= 64 and x.max() <= 2048 and not (x % 64).any()
    assert set(x) <= set(traffic.possible_lengths(MIX["prompt"]))
    assert 400 < np.median(x) < 640


def test_arrivals_lie_in_the_window_in_order():
    offers = traffic.offers(MIX, seconds=30, seed=4, vocab=100)
    t = [o.at_s for o in offers]
    assert t == sorted(t) and 0 <= t[0] and t[-1] < 30
    assert all(o.prompt.dtype == np.int32 and o.prompt.max() < 100
               for o in offers)


def test_bursts_raise_the_mean_rate():
    rng = np.random.default_rng(0)
    t = traffic.mmpp_arrivals(4.0, MIX["burst"], 2000.0, rng)
    assert 4.0 * 1.2 < len(t) / 2000.0 < 4.0 * 1.6  # 1.4x in expectation
