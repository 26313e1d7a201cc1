"""The traffic generator: deterministic per seed, the stated statistics."""
import benchpath
import numpy as np
import pytest

import traffic_gen


@pytest.mark.parametrize("name,directory", [
    ("chat-g2b", traffic_gen.TRAFFIC_DIR),
    ("tiny-chat", benchpath.FIXTURES / "traffic")])
def test_traffic_deterministic_and_seed_only_changes_ids(name, directory):
    mix = traffic_gen.load_mix(name, directory)
    big = 2 ** 31 + 12345
    a = traffic_gen.generate(mix, big, 30, 49155)
    b = traffic_gen.generate(mix, big, 30, 49155)
    c = traffic_gen.generate(mix, 7, 30, 49155)
    assert [(x.at_s, x.max_new_tokens, x.prompt.tolist()) for x in a] == \
        [(x.at_s, x.max_new_tokens, x.prompt.tolist()) for x in b]
    # another run seed: the same schedule (arrivals and lengths, in order),
    # other prompt ids
    assert [(x.at_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.at_s, len(x.prompt), x.max_new_tokens) for x in c]
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, c, strict=True))
    # another schedule seed: the same multisets of sizes and gaps, another order
    d = traffic_gen.generate(dict(mix, schedule_seed=mix["schedule_seed"] + 1),
                             big, 30, 49155)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in d)
    assert sorted(x.max_new_tokens for x in a) == sorted(x.max_new_tokens for x in d)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in d]
    ga, gd = np.diff([x.at_s for x in a]), np.diff([x.at_s for x in d])
    assert len(np.intersect1d(np.round(ga, 9), np.round(gd, 9))) >= len(ga) - 2


def test_traffic_statistics():
    mix = traffic_gen.load_mix("chat-g2b")
    rate = 20.0
    reqs = traffic_gen.generate(mix, 3, 100, 49155, rate_per_s=rate)
    n = len(reqs)
    assert n == 2000
    at = np.array([x.at_s for x in reqs])
    assert at[0] == 0 and np.all(np.diff(at) > 0) and at[-1] < 100
    gaps = np.diff(at)
    assert abs(gaps.mean() - 1 / rate) < 0.01 / rate
    # exponential gaps: the standard deviation equals the mean
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1
    p = np.array([len(x.prompt) for x in reqs])
    o = np.array([x.max_new_tokens for x in reqs])
    assert p.min() >= 32 and p.max() <= 2048 and o.min() >= 8 and o.max() <= 512
    assert abs(np.median(p) - 512) <= 2 and abs(np.median(o) - 128) <= 1
    # lognormal spread: log-lengths' interquartile range is 1.349 sigma
    iqr = lambda v: np.subtract(*np.percentile(np.log(v), [75, 25]))
    assert abs(iqr(p) - 1.349 * 0.8) < 0.02 and abs(iqr(o) - 1.349 * 0.7) < 0.02
    ids = np.concatenate([x.prompt for x in reqs[:50]])
    assert ids.min() >= 0 and ids.max() < 49155


def test_bucket_fits_the_longest_request():
    mix = traffic_gen.load_mix("chat-g2b")
    assert traffic_gen.bucket(mix, 16) == (2048, 2560)
