"""The traffic generator is deterministic per seed, and every seed gets
the same sizes and gaps, in the same order, that its mix's parameters
define."""
import numpy as np
import pytest

from chipbench import loadgen

MIXES = ["docqa", "docqa-closed", "unshared"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = loadgen.load(name)
    a = loadgen.requests(mix, 50, 2 ** 33 + 5, 1000, stream=2)
    b = loadgen.requests(mix, 50, 2 ** 33 + 5, 1000, stream=2)
    assert all(np.array_equal(x.prompt, y.prompt) and
               x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    c = loadgen.requests(mix, 50, 2 ** 33 + 6, 1000, stream=2)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    mix = loadgen.load(name)
    n = 201
    sizes = []
    for seed in (1, 2 ** 31 + 11, 987654321987):
        reqs = loadgen.requests(mix, n, seed, 1000, stream=1)
        sizes.append(([len(r.prompt) for r in reqs],
                      [r.max_new_tokens for r in reqs]))
    assert sizes[0] == sizes[1] == sizes[2]
    other = loadgen.requests(mix, n, 1, 1000, stream=2)
    assert [len(r.prompt) for r in other] != sizes[0][0]
    plens, alens = map(np.asarray, sizes[0])
    p, a = mix["prompt_len"], mix["new_tokens"]
    assert np.median(plens) == p["median"] and np.median(alens) == a["median"]
    assert plens.min() >= p["min"] and plens.max() <= p["max"]
    assert alens.min() >= a["min"] and alens.max() <= a["max"]
    reqs = loadgen.requests(mix, n, 3, 1000, stream=1)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in reqs)


@pytest.mark.parametrize("name", ["docqa", "unshared"])
def test_open_loop_arrivals(name):
    mix = loadgen.load(name)
    rate = mix["arrival"]["rate_per_s"]
    n = loadgen.open_count(mix, 10.0)
    assert n == int(np.ceil(rate * 10.0))
    a = loadgen.arrival_offsets(mix, n, stream=2)
    b = loadgen.arrival_offsets(mix, n, stream=2)
    c = loadgen.arrival_offsets(mix, n, stream=1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    # the same exponential gaps in another order, summing to n / rate
    ga = np.diff(np.append(a, n / rate))
    gc = np.diff(np.append(c, n / rate))
    assert np.isclose(ga.sum(), n / rate)
    assert np.allclose(np.sort(ga), np.sort(gc))
    assert np.isclose(ga.mean(), 1.0 / rate)


def test_closed_mix_has_clients():
    mix = loadgen.load("docqa-closed")
    assert mix["arrival"] == {"kind": "closed", "clients": 128}
    assert mix["max_slots"] == 128
