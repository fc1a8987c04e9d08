"""The timed path: closed-loop clients over BatchPlanner → DeviceEngine.

One wave advances every client to its next storage dependency, enqueues the
mix's per-wave writes, runs ``BatchPlanner.flush()`` (reads, then writes)
and then ``DeviceEngine.refresh()``, so that the wave's writes are visible to
the next (Δ = 1 wave).  Every operation enqueued on the planner is logged
with its future and its enqueue time; the comparison and the latency
metrics read that log.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, replace

from repro.core import records as R
from repro.core.engine import BatchPlanner
from repro.core.navigate import Navigator, UnitBudget
from repro.core.oracle import HeuristicOracle

import refkv

#: the planner method that enqueues each read operation
PLANNER_READS = {"q1_get": "get", "q2_ls": "ls", "q3_navigate": "navigate",
                 "q4_search": "search", "q4_contains": "contains"}
ENGINE_CALLS = ("q1_get", "q2_ls", "q3_navigate", "q4_search", "q4_contains",
                "admit_many", "update_many", "unlink_many")


def to_program(rec: tuple):
    """A reference tuple as the program's record."""
    if rec[0] == refkv.FILE:
        _, name, text, version, confidence, sources, verified, access = rec
        return R.FileRecord(name=name, text=text, meta=R.FileMeta(
            version=version, confidence=confidence, sources=list(sources),
            last_verified=verified, access_count=access))
    _, name, sub_dirs, files, summary, updated, count, access = rec
    return R.DirRecord(name=name, sub_dirs=list(sub_dirs), files=list(files),
                       summary=summary, meta=R.DirMeta(
                           updated_at=updated, entry_count=count,
                           access_count=access))


class SetText:
    """An update's OCC mutation; ``spec`` is what the reference applies."""

    __slots__ = ("spec",)

    def __init__(self, spec: tuple):
        self.spec = spec

    def __call__(self, rec):
        return replace(rec, text=refkv.apply_text(rec.text, self.spec))

    def __repr__(self) -> str:
        return f"SetText{self.spec[:2]}"


class RecordingPlanner(BatchPlanner):
    """The program's planner, logging (op, key, limit | record | mutation,
    future, enqueue time) for every operation enqueued on it."""

    def __init__(self, engine):
        super().__init__(engine)
        self.entries: list = []

    def _log(self, op, arg, extra, fut):
        self.entries.append((op, arg, extra, fut, time.perf_counter()))
        return fut

    def get(self, path):
        return self._log("q1_get", path, None, super().get(path))

    def ls(self, path):
        return self._log("q2_ls", path, None, super().ls(path))

    def navigate(self, path):
        return self._log("q3_navigate", path, None, super().navigate(path))

    def search(self, prefix, limit=None):
        return self._log("q4_search", prefix, limit, super().search(prefix, limit))

    def contains(self, token, limit=None):
        return self._log("q4_contains", token, limit, super().contains(token, limit))

    def admit(self, path, rec):
        return self._log("w_admit", path, rec, super().admit(path, rec))

    def update(self, path, mutate):
        return self._log("w_update", path, mutate, super().update(path, mutate))

    def unlink(self, path):
        return self._log("w_unlink", path, None, super().unlink(path))


@dataclass
class Wave:
    phase: str
    entries: list
    t_start: float
    t_flush_end: float
    t_end: float
    resolved: int


@dataclass
class Finished:
    kind: str
    t_start: float
    t_done: float
    waves: int


class WaveLoop:
    """Drives ``clients`` closed-loop clients of a ``LoadGen`` over one
    engine; ``annotate(name)`` wraps the phases of each wave (a profiler
    annotation in traced runs)."""

    def __init__(self, engine, loadgen, annotate=None):
        self.engine = engine
        self.planner = RecordingPlanner(engine)
        self.nav = Navigator(self.planner, HeuristicOracle())
        self.gen = loadgen
        self.slots: list = [None] * loadgen.clients
        self.waves: list[Wave] = []
        self.traffic_waves = 0
        self.finished: list[Finished] = []
        self.annotate = annotate or (lambda name: nullcontext())

    # -- the client side of the load generator -------------------------
    def navigate(self, query: str, units: int):
        return self.nav.session(query, UnitBudget(units))

    def read(self, path: str):
        return self.planner.get(path)

    def update(self, path: str, spec: tuple):
        return self.planner.update(path, SetText(spec))

    def admit(self, path: str, rec: tuple):
        return self.planner.admit(path, to_program(rec))

    def unlink(self, path: str):
        return self.planner.unlink(path)

    def scan(self, prefix: str, limit: int):
        return self.planner.search(prefix, limit)

    # -- waves ----------------------------------------------------------
    def _advance(self, c: int) -> None:
        slot = self.slots[c]
        started = False
        while True:
            if slot is None:
                if started:
                    return      # a request that needed no storage idles its client
                kind, gen = self.gen.start(self)
                slot = self.slots[c] = [kind, gen, time.perf_counter(), 0]
                started = True
            try:
                next(slot[1])
                slot[3] += 1
                return
            except StopIteration:
                self.finished.append(Finished(slot[0], slot[2], time.perf_counter(),
                                              slot[3]))
                slot = self.slots[c] = None

    def wave(self, phase: str) -> Wave:
        """One traffic wave: clients, per-wave writes, flush, refresh."""
        ann = self.annotate
        with ann("bench.wave"):
            t0 = time.perf_counter()
            with ann("bench.clients"):
                for c in range(len(self.slots)):
                    if self.slots[c] is not None or self.gen.first_wave(c) <= self.traffic_waves:
                        self._advance(c)
                self.gen.wave_writes(self)
            with ann("bench.flush"):
                resolved = self.planner.flush()
            t1 = time.perf_counter()
            with ann("bench.refresh"):
                self.engine.refresh()
            t2 = time.perf_counter()
        entries, self.planner.entries = self.planner.entries, []
        w = Wave(phase, entries, t0, t1, t2, resolved)
        self.waves.append(w)
        self.traffic_waves += 1
        return w

    def read_wave(self, phase: str, ops: list[tuple]) -> Wave:
        """A wave of the given reads alone: (op, key, limit) triples."""
        t0 = time.perf_counter()
        for op, key, limit in ops:
            enqueue = getattr(self.planner, PLANNER_READS[op])
            enqueue(key) if limit is None else enqueue(key, limit)
        resolved = self.planner.flush()
        t1 = time.perf_counter()
        entries, self.planner.entries = self.planner.entries, []
        w = Wave(phase, entries, t0, t1, t1, resolved)
        self.waves.append(w)
        return w

    def write_wave(self, phase: str, admits=(), unlinks=()) -> Wave:
        """A wave of the given writes alone: (path, record) admissions and
        unlinks, then the refresh that makes them visible."""
        t0 = time.perf_counter()
        for path, rec in admits:
            self.admit(path, rec)
        for path in unlinks:
            self.unlink(path)
        resolved = self.planner.flush()
        t1 = time.perf_counter()
        self.engine.refresh()
        t2 = time.perf_counter()
        entries, self.planner.entries = self.planner.entries, []
        w = Wave(phase, entries, t0, t1, t2, resolved)
        self.waves.append(w)
        return w


def annotate_engine(engine, annotate, calls: list) -> None:
    """Wrap the engine's batched calls on the instance: each runs inside
    ``annotate("bench.<call>")`` and is logged as (call, args, kwargs,
    result) for the work counts of the rooflines."""
    for name in ENGINE_CALLS:
        fn = getattr(engine, name)

        def wrapped(*args, _fn=fn, _name=name, **kwargs):
            with annotate("bench." + _name):
                out = _fn(*args, **kwargs)
            calls.append((_name, args, kwargs, out))
            return out

        setattr(engine, name, wrapped)
