"""The traffic generator deals the same work to every seed."""
import numpy as np
import pytest

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


class Writer:
    """Records the per-wave writes a mix enqueues, in order."""

    def __init__(self):
        self.log = []

    def unlink(self, path):
        self.log.append(("unlink", path))

    def update(self, path, spec):
        self.log.append(("update", path))


class Pages(Layout):
    def update_spec(self, path, serial, rng):
        return ("field", None, f"v{serial}", 0)


def test_an_unlink_deck_never_repeats_a_key_and_retired_keys_are_not_drawn():
    spec = mix(per_wave=[{"kind": "unlink", "count": 5, "target": "record"},
                         {"kind": "update", "count": 3, "target": "record"}])
    gen, w = loadgen.LoadGen(spec, Pages(), np.random.default_rng(2 ** 31 + 9)), Writer()
    for _ in range(12):
        gen.wave_writes(w)
    unlinked = [p for kind, p in w.log if kind == "unlink"]
    assert len(unlinked) == len(set(unlinked)) == 60
    assert set(unlinked) <= set(Pages.populations["record"])
    for i, (kind, p) in enumerate(w.log):
        if kind == "update":
            assert ("unlink", p) not in w.log[:i]
    with pytest.raises(ValueError, match="every 'record' key"):
        gen.wave_writes(w)


def test_a_mix_without_unlinks_draws_its_keys_as_a_bare_chooser_does():
    """No unlink, no retired key: the same draws from the same seed."""
    dist = {"name": "zipfian", "theta": 0.99}
    gen = loadgen.LoadGen(mix(key_distribution=dist), Layout(), np.random.default_rng(5))
    bare = loadgen.KeyChooser(64, dist, np.random.default_rng(5))
    keys = Layout.populations["record"]
    assert [gen.key("record") for _ in range(200)] == \
        [keys[int(bare.draw(1)[0])] for _ in range(200)]
