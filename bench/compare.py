"""The comparison that decides ``correct``: every answer the timed path gave,
against the plain reference replayed wave by wave under Δ = 1.

A wave's reads must equal the reference state after every earlier wave's
writes and before its own (the planner runs a wave's reads first, and the
refresh between waves makes its writes visible).  Its writes must be
acknowledged as the reference acknowledges them, applied in the planner's
order: maximal runs of one kind in enqueue order, admissions parents-first
and unlinks children-first within a run.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import refkv

READS = ("q1_get", "q2_ls", "q3_navigate", "q4_search", "q4_contains")
WRITES = ("w_admit", "w_update", "w_unlink")


def canon(rec) -> tuple | None:
    """A program record in the reference's tuple form."""
    if rec is None:
        return None
    m = rec.meta
    if rec.type == refkv.FILE:
        return (refkv.FILE, rec.name, rec.text, m.version, m.confidence,
                tuple(m.sources), m.last_verified, m.access_count)
    return (refkv.DIR, rec.name, tuple(rec.sub_dirs), tuple(rec.files),
            rec.summary, m.updated_at, m.entry_count, m.access_count)


def _depth(path: str) -> int:
    return sum(1 for s in path.split("/") if s)


def planner_order(writes: list) -> list:
    """Writes in the order the planner applies them."""
    out, i = [], 0
    while i < len(writes):
        j = i
        while j < len(writes) and writes[j][0] == writes[i][0]:
            j += 1
        run = writes[i:j]
        if run[0][0] == "w_admit":
            run = sorted(run, key=lambda e: _depth(e[1]))
        elif run[0][0] == "w_unlink":
            run = sorted(run, key=lambda e: -_depth(e[1]))
        out.extend(run)
        i = j
    return out


@dataclass
class Tally:
    reads: int = 0
    read_mismatches: int = 0
    writes: int = 0
    write_mismatches: int = 0
    unresolved: int = 0
    examples: list = field(default_factory=list)

    def miss(self, what: str, kind: str) -> None:
        if kind == "read":
            self.read_mismatches += 1
        else:
            self.write_mismatches += 1
        if len(self.examples) < 5:
            self.examples.append(what)


class Checker:
    def __init__(self, items, tokens=()):
        self.ref = refkv.RefKV(items, tokens)
        self._canon: dict[int, tuple | None] = {}
        self.tallies: dict[str, Tally] = {}

    def _c(self, rec):
        key = id(rec)
        got = self._canon.get(key)
        if got is None:
            got = self._canon[key] = canon(rec)
        return got

    def _answer(self, op: str, value):
        if op == "q1_get":
            return None if value is None else self._c(value)
        if op == "q2_ls":
            return None if value is None else (self._c(value[0]), list(value[1]))
        if op == "q3_navigate":
            return [self._c(r) for r in value]
        return list(value)

    def _expected(self, op: str, arg, extra):
        ref = self.ref
        if op == "q1_get":
            return ref.get(arg)
        if op == "q2_ls":
            return ref.ls(arg)
        if op == "q3_navigate":
            return ref.navigate(arg)
        if op == "q4_search":
            return ref.search(arg, extra)
        return ref.contains(arg, extra)

    def _apply(self, op: str, arg, extra):
        """The reference's acknowledgement of a write, or RefError."""
        if op == "w_admit":
            return self.ref.admit(arg, canon(extra))
        if op == "w_update":
            return self.ref.update(arg, extra.spec)
        return self.ref.unlink(arg)

    def wave(self, phase: str, entries: list) -> None:
        t = self.tallies.setdefault(phase, Tally())
        for op, arg, extra, fut, _ in entries:
            if op not in READS:
                continue
            t.reads += 1
            if not fut.done:
                t.unresolved += 1
                continue
            want = self._expected(op, arg, extra)
            if self._answer(op, fut.value) != want:
                t.miss(f"{phase} {op}({arg!r}, {extra!r}): got "
                       f"{_short(fut.value)} want {_short(want)}", "read")
        writes = [e for e in entries if e[0] in WRITES]
        for op, arg, extra, fut, _ in planner_order(writes):
            t.writes += 1
            try:
                want = self._apply(op, arg, extra)
            except refkv.RefError:
                want = refkv.RefError
            if not fut.done:
                t.unresolved += 1
                continue
            got = fut.value
            if want is refkv.RefError:
                ok = isinstance(got, Exception)
            elif isinstance(got, Exception):
                ok = False
            elif op == "w_unlink":
                ok = got is want
            else:
                ok = self._c(got) == want
            if not ok:
                t.miss(f"{phase} {op}({arg!r}): got {_short(got)} "
                       f"want {_short(want)}", "write")


def _short(x, n: int = 160) -> str:
    s = repr(x)
    return s if len(s) <= n else s[:n] + "..."


def replay(items, waves) -> Checker:
    """Replay ``waves`` (objects with ``phase`` and ``entries``) over the
    reference built from the initial ``items``: the checker holds a tally
    per phase, and the reference at the state after the last wave."""
    tokens = {e[1].lower() for w in waves for e in w.entries if e[0] == "q4_contains"}
    checker = Checker(items, tokens)
    for w in waves:
        checker.wave(w.phase, w.entries)
    return checker


def written_paths(waves) -> list[str]:
    """Every path the writes of ``waves`` wrote or unlinked, then every
    ancestor directory of them (an admission links its parent and creates
    missing ancestors; an unlink rewrites its parent), each once."""
    paths = list(dict.fromkeys(e[1] for w in waves for e in w.entries if e[0] in WRITES))
    seen = set(paths)
    dirs = dict.fromkeys(a for p in paths for a in _ancestors(p))
    return paths + [d for d in dirs if d not in seen]


def _ancestors(path: str) -> list[str]:
    out = []
    while path != refkv.ROOT:
        path = refkv.parent(path)
        out.append(path)
    return out


def durable(checker: Checker, get, paths: list[str]) -> None:
    """Each path's record as ``get`` reads it from a reopened store against
    the reference's after the last wave: the checker's ``durable`` tally."""
    t = checker.tallies["durable"] = Tally()
    for p in paths:
        t.reads += 1
        got, want = canon(get(p)), checker.ref.get(p)
        if got != want:
            t.miss(f"durable get({p!r}): got {_short(got)} want {_short(want)}", "read")
