"""The plain reference: WikiKV's path-keyed semantics as a dict and a sorted
key list.  It imports nothing of the program under test.

Records are tuples, compared by value:

    ("file", name, text, version, confidence, sources, last_verified, access_count)
    ("dir",  name, sub_dirs, files, summary, updated_at, entry_count, access_count)

with ``sources``, ``sub_dirs`` and ``files`` as tuples.  Reads follow the
query operators of the paper (§II-B): Q1 get, Q2 ls, Q3 navigate, Q4
prefix search and Q4 keyword search over segment tokens.  Writes follow the
single-writer protocol (§IV-C): an admission writes the child and then links
it into its parent's child list (creating missing parent directories), an
update rewrites a file's text and bumps its version, an unlink removes the
child from its parent's list and then deletes it.  Paths are absolute and
normalized; the generators make no other kind.
"""
from __future__ import annotations

import bisect
from typing import Iterable

FILE, DIR = "file", "dir"
ROOT, SEP = "/", "/"
#: writer clock: directory ``updated_at`` stamps (the benchmark runs the
#: writer with a constant clock so that every stamp is known)
CLOCK = 0.0


class RefError(Exception):
    """An operation the protocol refuses (the program resolves it to an
    exception value)."""


def file_record(name: str, text: str, version: int = 0) -> tuple:
    return (FILE, name, text, version, 1.0, (), 0.0, 0)


def dir_record(name: str, sub_dirs=(), files=(), summary: str = "") -> tuple:
    return (DIR, name, tuple(sub_dirs), tuple(files), summary, 0.0, 0, 0)


def parent(path: str) -> str:
    head = path.rsplit(SEP, 1)[0]
    return head or ROOT


def basename(path: str) -> str:
    return path.rsplit(SEP, 1)[1]


def child(path: str, segment: str) -> str:
    return SEP + segment if path == ROOT else path + SEP + segment


def is_prefix(prefix: str, path: str) -> bool:
    """Segment-aware: ``/a`` is a prefix of ``/a/b`` but not of ``/ab``."""
    return prefix == ROOT or path == prefix or path.startswith(prefix + SEP)


def segment_tokens(path: str) -> set[str]:
    """Keyword tokens of a path: each segment lowercased, whole and split on
    ``_`` (with ``-`` read as ``_``)."""
    out: set[str] = set()
    for seg in path.split(SEP):
        if not seg:
            continue
        low = seg.lower()
        out.add(low)
        out.update(t for t in low.replace("-", "_").split("_") if t)
    return out


def apply_text(text: str, spec: tuple) -> str:
    """An update's new text: ``("field", j, value, width)`` replaces the j-th
    field of ``width`` characters; ``j`` None replaces the whole text."""
    _, j, value, width = spec
    if j is None:
        return value
    return text[:j * width] + value + text[(j + 1) * width:]


class RefKV:
    """Path → record dict plus the sorted path list (the ordered namespace).

    ``tokens`` names the keywords whose match lists are kept, sorted, for
    Q4 keyword search; a keyword outside it is answered by a full scan."""

    def __init__(self, items: Iterable[tuple[str, tuple]],
                 tokens: Iterable[str] = ()):
        self.recs: dict[str, tuple] = dict(items)
        self.keys: list[str] = sorted(self.recs)
        self.index: dict[str, list[str]] = {t: [] for t in tokens}
        if self.index:
            for p in self.keys:
                for t in segment_tokens(p) & self.index.keys():
                    self.index[t].append(p)

    # -- reads ---------------------------------------------------------------
    def get(self, path: str) -> tuple | None:
        return self.recs.get(path)

    def ls(self, path: str) -> tuple[tuple, list[str]] | None:
        rec = self.recs.get(path)
        if rec is None or rec[0] != DIR:
            return None
        return rec, [child(path, s) for s in rec[2] + rec[3]]

    def navigate(self, path: str) -> list[tuple]:
        chain = [ROOT]
        segs = [s for s in path.split(SEP) if s]
        for i in range(1, len(segs) + 1):
            chain.append(SEP + SEP.join(segs[:i]))
        out = []
        for p in chain:
            rec = self.recs.get(p)
            if rec is None:
                break
            out.append(rec)
        return out

    def search(self, prefix: str, limit: int | None = None) -> list[str]:
        prefix = prefix if prefix.startswith(SEP) else SEP + prefix
        seg_prefix = prefix.rstrip(SEP) or ROOT
        out: list[str] = []
        i = bisect.bisect_left(self.keys, prefix)
        while i < len(self.keys) and self.keys[i].startswith(prefix):
            p = self.keys[i]
            i += 1
            if p == prefix or is_prefix(seg_prefix, p):
                out.append(p)
                if limit is not None and len(out) >= limit:
                    break
        return out

    def contains(self, token: str, limit: int | None = None) -> list[str]:
        token = token.lower()
        hits = self.index.get(token)
        if hits is None:
            hits = [p for p in self.keys if token in segment_tokens(p)]
        return list(hits if limit is None else hits[:limit])

    # -- writes --------------------------------------------------------------
    def _put(self, path: str, rec: tuple) -> None:
        if path not in self.recs:
            bisect.insort(self.keys, path)
            for t in segment_tokens(path) & self.index.keys():
                bisect.insort(self.index[t], path)
        self.recs[path] = rec

    def _delete(self, path: str) -> None:
        if self.recs.pop(path, None) is None:
            return
        del self.keys[bisect.bisect_left(self.keys, path)]
        for t in segment_tokens(path) & self.index.keys():
            lst = self.index[t]
            del lst[bisect.bisect_left(lst, path)]

    def _link(self, par: str, segment: str, is_dir: bool) -> None:
        prec = self.recs.get(par)
        if prec is None:
            prec = dir_record("" if par == ROOT else basename(par))
            self._put(par, prec)
            if par != ROOT:
                self._link(parent(par), basename(par), True)
        if prec[0] != DIR:
            raise RefError(f"parent {par!r} is not a directory")
        sub_dirs, files = prec[2], prec[3]
        if is_dir and segment not in sub_dirs:
            sub_dirs += (segment,)
        elif not is_dir and segment not in files:
            files += (segment,)
        self._put(par, (DIR, prec[1], sub_dirs, files, prec[4], CLOCK,
                        len(sub_dirs) + len(files), prec[7]))

    def admit(self, path: str, rec: tuple) -> tuple:
        """Admission; acknowledged with the admitted record."""
        if path == ROOT:
            self._put(path, rec)
            return rec
        par = parent(path)
        prec = self.recs.get(par)
        if prec is not None and prec[0] != DIR:
            raise RefError(f"parent {par!r} is not a directory")
        self._put(path, rec)
        self._link(par, basename(path), rec[0] == DIR)
        return rec

    def update(self, path: str, spec: tuple) -> tuple:
        """Rewrite a file's text; acknowledged with the new record."""
        rec = self.recs.get(path)
        if rec is None or rec[0] != FILE:
            raise RefError(f"no file record at {path!r}")
        new = (FILE, rec[1], apply_text(rec[2], spec), rec[3] + 1) + rec[4:]
        self._put(path, new)
        return new

    def unlink(self, path: str) -> bool:
        """Remove a node; acknowledged with whether it existed."""
        if path == ROOT:
            raise RefError("the root has no parent")
        existed = path in self.recs
        prec = self.recs.get(parent(path))
        if prec is not None and prec[0] == DIR:
            seg = basename(path)
            sub_dirs = tuple(s for s in prec[2] if s != seg)
            files = tuple(s for s in prec[3] if s != seg)
            self._put(parent(path), (DIR, prec[1], sub_dirs, files, prec[4],
                                     prec[5], len(sub_dirs) + len(files),
                                     prec[7]))
        self._delete(path)
        return existed
