"""The traffic generator is a function of the mix file and the seed."""

import json
import os

import numpy as np

from bench import traffic

MIXES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def _stream(t, clients=4, per=5):
    return [t.request(c, j) for c in range(clients) for j in range(per)]


def _same(a, b):
    return all(x[0] == y[0] and x[2] == y[2] and np.array_equal(x[1], y[1])
               for x, y in zip(a, b))


def test_same_seed_same_requests():
    mix = _mix("closed-zipf")
    big = 2 ** 33 + 17                      # seeds beyond 32 bits
    a = _stream(traffic.Traffic(mix, 256, 51200, 4, big))
    b = _stream(traffic.Traffic(mix, 256, 51200, 4, big))
    assert _same(a, b)
    c = _stream(traffic.Traffic(mix, 256, 51200, 4, big + 1))
    assert not _same(a, c)


def test_pool_is_seed_free_and_in_range():
    mix = _mix("closed-zipf")
    p1 = traffic.make_pool(mix)
    p2 = traffic.make_pool(mix)
    assert np.array_equal(p1.prompt, p2.prompt)
    assert np.array_equal(p1.output, p2.output)
    assert p1.prompt.min() >= 32 and p1.prompt.max() <= 1536
    assert p1.output.min() >= 16 and p1.output.max() <= 256
    # 32 quantiles of lognormal(median 1020, sigma 0.5) and (129, 0.99),
    # cut at 1536 and 256: medians as the trace's, means under its own
    assert len(p1.prompt) == 32 and np.median(p1.prompt) == 1020
    assert 1000 < p1.prompt.mean() < 1080 and 130 < p1.output.mean() < 150
    assert np.sum(p1.prompt == 1536) == 7 and np.sum(p1.output == 256) == 8


def test_every_seed_serves_the_same_sizes():
    mix = _mix("closed-zipf")
    sizes = []
    for seed in (1, 2, 2 ** 40):
        t = traffic.Traffic(mix, 256, 51200, 8, seed)
        # 8 clients x 4 requests: each 32 requests serve the pool once
        sizes.append(sorted((len(t.request(c, j)[1]), t.request(c, j)[2])
                            for c in range(8) for j in range(4)))
    assert sizes[0] == sizes[1] == sizes[2]


def test_zipf_tail_misses_the_bank():
    # Zipf 1.1 over 256 tenants: about 18% of requests fall outside the
    # 64 hottest, so cold tenants keep arriving
    t = traffic.Traffic(_mix("closed-zipf"), 256, 51200, 8, 9)
    rng = np.random.default_rng(0)
    ranks = np.array([t.rank(rng) for _ in range(20000)])
    assert 0.15 < np.mean(ranks >= 64) < 0.21
    assert 0.18 < np.mean(ranks == 0) < 0.23


def test_hot_mix_has_one_tenant():
    mix = _mix("closed-hot")
    t = traffic.Traffic(mix, 256, 51200, 16, 5)
    assert len({t.request(c, j)[0] for c in range(16) for j in range(8)}) == 1


def test_prompt_tokens_in_vocab():
    t = traffic.Traffic(_mix("closed-zipf"), 256, 122753, 8, 3)
    for tenant, prompt, gen in _stream(t, 8, 4):
        assert 0 <= tenant < 256 and prompt.dtype == np.int32
        assert prompt.min() >= 0 and prompt.max() < 122753 and gen >= 16
