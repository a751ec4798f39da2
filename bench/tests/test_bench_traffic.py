import numpy as np
import pytest

from bench.traffic import Calls, Mix

MIXES = ["offline-decode-128in-512out", "offline-prefill-1536in-32out",
         "offline-prefill-768in-32out", "offline-decode-256in-256out"]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_on_the_grid_and_in_the_clip(name):
    mix = Mix.load(name)
    lengths = mix.lengths()
    assert len(lengths) == mix.requests_per_call
    assert all(n % mix.grid == 0 for n in lengths)
    assert min(lengths) >= mix.min_len
    assert max(lengths) <= -(-mix.max_len // mix.grid) * mix.grid
    assert mix.longest_request() == max(lengths) + mix.max_new


@pytest.mark.parametrize("name", MIXES)
def test_calls_are_deterministic_by_seed(name):
    mix = Mix.load(name)
    seed = 2 ** 31 + 12345                 # larger than 32 signed bits
    a, b = Calls(mix, 49152, seed), Calls(mix, 49152, seed)
    for i in range(2):
        for p, q in zip(a.call(i), b.call(i)):
            np.testing.assert_array_equal(p, q)


def test_every_seed_sends_the_same_lengths_in_another_order():
    mix = Mix.load("offline-prefill-1536in-32out")
    one, two = Calls(mix, 1000, 1).call(0), Calls(mix, 1000, 2).call(0)
    assert sorted(map(len, one)) == sorted(map(len, two)) == mix.lengths()
    assert [len(p) for p in one] != [len(p) for p in two]
    assert all(p.min() >= 0 and p.max() < 1000 for p in one)
    assert not all(np.array_equal(p, q) for p, q in
                   zip(sorted(one, key=len), sorted(two, key=len)))


def test_lengths_follow_the_lognormal_median():
    mix = Mix.load("offline-decode-128in-512out")
    lengths = sorted(mix.lengths())
    median = lengths[len(lengths) // 2]
    assert mix.median <= median <= mix.median + mix.grid
