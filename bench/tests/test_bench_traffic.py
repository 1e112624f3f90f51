"""The traffic generator: deterministic per seed, the same work for every
seed, and the open loop's arrivals fixed by the mix."""

import json
import os

import pytest

from bench.traffic import Mix, arrival_times, base_rate, quantile

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "traffic")


def mixes():
    return sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", mixes())
def test_same_seed_same_requests(name):
    a, b = Mix(load(name), 2**33 + 7, 1000), Mix(load(name), 2**33 + 7, 1000)
    assert [a.request(k) for k in range(40)] == \
        [b.request(k) for k in range(40)]
    assert a.due_times(30.0) == b.due_times(30.0) if \
        load(name)["loop"] == "open" else True


@pytest.mark.parametrize("name", mixes())
def test_every_seed_gets_the_same_lengths_per_block(name):
    mix = load(name)
    n = mix["block"]
    seeds = (1, 99, 2**31 + 3)
    for i in (0, 1):  # prompts, outputs: the same multiset every seed
        blocks = [sorted(Mix(mix, s, 1000).lengths(k)[i]
                         for k in range(n, 2 * n)) for s in seeds]
        assert blocks[0] == blocks[1] == blocks[2]
    orders = [[Mix(mix, s, 1000).lengths(k) for k in range(n)]
              for s in seeds]
    assert orders[0] != orders[1]
    for k in range(n):
        p, o = Mix(mix, 5, 1000).lengths(k)
        assert mix["prompt"]["lo"] <= p <= mix["prompt"]["hi"]
        assert mix["output"]["lo"] <= o <= mix["output"]["hi"]
        # the engine ends a request at max_seq - 1 tokens
        assert p + o < mix["engine"]["max_seq"]


def test_arrivals_do_not_depend_on_the_seed_and_keep_the_mean_rate():
    mix = {"rate_req_s": 2.0, "burst_factor": 4.0, "on_s": 3.0,
           "off_s": 5.0, "arrival_seed": 3, "preroll_s": 4.0}
    a = Mix(dict(mix, block=4, prompt={}, output={}), 1, 10).due_times(20)
    b = Mix(dict(mix, block=4, prompt={}, output={}), 2, 10).due_times(20)
    assert a == b and a[0] >= -4.0 and a[-1] < 20.0
    assert a == sorted(a)
    long_run = arrival_times(mix, 20000.0)
    assert len(long_run) / 20000.0 == pytest.approx(2.0, rel=0.05)
    # ON at base * 4 for 3 s, OFF at base / 4 for 5 s, mean 2
    assert base_rate(mix) * (3 * 4 + 5 / 4) / 8 == pytest.approx(2.0)


def test_quantiles():
    u = {"dist": "uniform", "lo": 10, "hi": 19}
    assert [quantile(u, (i + 0.5) / 10) for i in range(10)] == \
        list(range(10, 20))
    ln = {"dist": "lognormal", "median": 100, "sigma": 1.0, "lo": 8,
          "hi": 400}
    assert quantile(ln, 0.5) == 100
    assert quantile(ln, 0.999) == 400 and quantile(ln, 0.001) == 8
