"""The one traffic generator: reads a traffic mix (``bench/traffic/<name>.json``)
and turns it into client requests and per-wave writes over a layout.

A mix names a closed loop of ``clients``; each client runs one request at a
time and starts the next as soon as the last returns.  Request kinds, with
their ``share`` of started requests:

  navigate  a ``Navigator`` session for the query of a ``target`` key, under
            a unit budget of ``budget_units``
  read      Q1 get of a ``target`` key
  update    OCC rewrite of a ``target`` key (the layout's update)
  insert    admission of the layout's next new record
  scan      Q4 prefix search of the ``target`` key's parent, with a limit
            uniform over ``length`` = [low, high]

Kinds are dealt in blocks of ``clients`` starts with exact counts (largest
remainder), shuffled, and scan limits from shuffled decks that hold every
length of the range once, so every seed issues the same mix, and every
stretch of a run the same set of limits, in another order.
``per_wave`` lists writes that every wave carries besides the clients':

  admit   ``count`` new children under ``parent`` keys
  update  ``count`` rewrites of ``target`` keys

Clients start their first requests spread evenly over the mix's first
``stagger_waves`` waves (0 where it names none): clients that all start at
once stay in step for many waves, and the load of a wave swings with them,
where a deployment's long-running clients are out of step.

Keys are drawn from a layout population by ``key_distribution``: zipfian
with exponent ``theta`` over a seeded rank permutation, or uniform.
"""
from __future__ import annotations

import numpy as np

import refkv


class KeyChooser:
    """Draws indices into a population of ``n`` keys."""

    def __init__(self, n: int, dist: dict, rng: np.random.Generator):
        self.rng = rng
        self.n = n
        self.perm = rng.permutation(n)
        if dist["name"] == "zipfian":
            w = np.arange(1, n + 1, dtype=np.float64) ** -float(dist["theta"])
            self.cdf = np.cumsum(w)
            self.cdf /= self.cdf[-1]
        elif dist["name"] == "uniform":
            self.cdf = None
        else:
            raise ValueError(f"unknown key distribution {dist['name']!r}")

    def draw(self, k: int) -> np.ndarray:
        if self.cdf is None:
            return self.rng.integers(0, self.n, size=k)
        ranks = np.searchsorted(self.cdf, self.rng.random(k), side="right")
        return self.perm[np.minimum(ranks, self.n - 1)]


class LoadGen:
    def __init__(self, spec: dict, layout, rng: np.random.Generator):
        self.spec = spec
        self.layout = layout
        self.rng = rng
        self.clients = int(spec["clients"])
        self.requests = spec["requests"]
        self.stagger = int(spec.get("stagger_waves", 0))
        self._choosers: dict[str, KeyChooser] = {}
        self._kinds: list[dict] = []
        self._decks: dict[tuple[int, int], list[int]] = {}
        self.inserted = 0
        self.updated = 0

    # -- keys ------------------------------------------------------------
    def key(self, population: str) -> str:
        ch = self._choosers.get(population)
        keys = self.layout.populations[population]
        if ch is None:
            ch = self._choosers[population] = KeyChooser(
                len(keys), self.spec["key_distribution"], self.rng)
        return keys[int(ch.draw(1)[0])]

    def _next_kind(self) -> dict:
        if not self._kinds:
            shares = np.array([float(r["share"]) for r in self.requests])
            exact = shares / shares.sum() * self.clients
            counts = np.floor(exact).astype(int)
            short = self.clients - counts.sum()
            for i in np.argsort(-(exact - counts), kind="stable")[:short]:
                counts[i] += 1
            block = [r for r, c in zip(self.requests, counts) for _ in range(c)]
            self._kinds = [block[i] for i in self.rng.permutation(len(block))]
        return self._kinds.pop()

    def _deal(self, low: int, high: int) -> int:
        """The next length of a shuffled deck of ``low`` .. ``high``."""
        deck = self._decks.setdefault((low, high), [])
        if not deck:
            deck.extend(int(v) for v in self.rng.permutation(np.arange(low, high + 1)))
        return deck.pop()

    def first_wave(self, client: int) -> int:
        """The traffic wave in which ``client`` starts its first request."""
        return client * self.stagger // self.clients

    # -- requests ----------------------------------------------------------
    def start(self, client) -> tuple[str, object]:
        """The next request of a client: (kind, generator).  The generator
        yields whenever it has enqueued operations for the next wave."""
        req = self._next_kind()
        kind = req["kind"]
        if kind == "navigate":
            target = self.key(req["target"])
            return kind, client.navigate(self.layout.query(target),
                                         int(req["budget_units"]))
        if kind == "read":
            return kind, _one(client.read, self.key(req["target"]))
        if kind == "update":
            path = self.key(req["target"])
            return kind, _one(client.update, path, self._update_spec(path))
        if kind == "insert":
            return kind, _one(client.admit, *self._new_item(None))
        if kind == "scan":
            prefix = refkv.parent(self.key(req["target"]))
            return kind, _one(client.scan, prefix, self._deal(*req["length"]))
        raise ValueError(f"unknown request kind {kind!r}")

    def wave_writes(self, client) -> None:
        """Enqueue this wave's ``per_wave`` writes."""
        for w in self.spec.get("per_wave", ()):
            kind, count = w["kind"], int(w["count"])
            if kind == "admit":
                for _ in range(count):
                    client.admit(*self._new_item(self.key(w["parent"])))
            elif kind == "update":
                for _ in range(count):
                    path = self.key(w["target"])
                    client.update(path, self._update_spec(path))
            else:
                raise ValueError(f"unknown per-wave write {kind!r}")

    def new_items(self, n: int) -> list[tuple[str, tuple]]:
        """``n`` new records of the layout, as the mix's admissions make
        them (under ``parent`` keys where its per-wave admits name one)."""
        parent = next((w["parent"] for w in self.spec.get("per_wave", ())
                       if w["kind"] == "admit"), None)
        return [self._new_item(None if parent is None else self.key(parent))
                for _ in range(n)]

    def _new_item(self, parent):
        item = self.layout.new_item(parent, self.inserted, self.rng)
        self.inserted += 1
        return item

    def _update_spec(self, path):
        spec = self.layout.update_spec(path, self.updated, self.rng)
        self.updated += 1
        return spec


def _one(op, *args):
    """A one-operation request: enqueue, wait one wave, return the answer."""
    fut = op(*args)
    yield
    return fut.value
