"""The traffic generator deals the same work to every seed."""
import numpy as np

import loadgen


class Layout:
    populations = {"record": [f"/t/g{i % 4}/user{i}" for i in range(64)]}


def mix(**extra):
    return dict({"clients": 8, "key_distribution": {"name": "uniform"},
                 "requests": [{"kind": "scan", "share": 1.0, "target": "record",
                               "length": [1, 10]}]}, **extra)


class Client:
    def scan(self, prefix, limit):
        self.limit = limit


def limits(seed, n):
    gen, client = loadgen.LoadGen(mix(), Layout(), np.random.default_rng(seed)), Client()
    out = []
    for _ in range(n):
        next(gen.start(client)[1])
        out.append(client.limit)
    return out


def test_scan_limits_are_dealt_from_decks_of_the_whole_range():
    for seed in (1, 2 ** 31 + 5):
        got = limits(seed, 30)
        for lo in range(0, 30, 10):
            assert sorted(got[lo:lo + 10]) == list(range(1, 11))
    assert limits(1, 10) != limits(2, 10)


def test_first_requests_are_staggered_over_the_mix_waves():
    gen = loadgen.LoadGen(mix(stagger_waves=4), Layout(), np.random.default_rng(0))
    assert [gen.first_wave(c) for c in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    plain = loadgen.LoadGen(mix(), Layout(), np.random.default_rng(0))
    assert {plain.first_wave(c) for c in range(8)} == {0}
