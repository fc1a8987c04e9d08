"""Reduction of a profiler trace (``*.xplane.pb``) to device busy time, idle
time and their attribution to the host's annotations.

Device work is the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane:
one event per program execution.  Host annotations are the events named
``bench.*`` that the harness writes with ``jax.profiler.TraceAnnotation``.

The device's timestamps are offset from the host's by about a millisecond
(1.2-1.6 ms in a recording on a TPU v5 lite), far more than an engine call's
device time.  Each execution's ``run_id`` also marks the host events that
enqueued it (``DoEnqueueProgram``) and that completed it
(``CompleteCallbacks``), which bound the offset: the device timeline is
shifted by the middle of the band that every execution allows.  Device time
is given to an engine call when the host enqueued it inside the call's
annotation window: by launch point, not by kernel names, so the attribution
holds when a kernel is replaced.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass

DEVICE_PLANE = "/device:TPU:"
MODULES_LINE = "XLA Modules"
ANNOTATION = "bench."


ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"


@dataclass
class Trace:
    #: per device plane: sorted (start_ns, end_ns, program name, host
    #: enqueue ns or None), on the host's timeline
    devices: dict[str, list[tuple]]
    #: host annotations: (start_ns, end_ns, name) sorted by start
    annotations: list[tuple[float, float, str]]
    #: host time minus device time, as estimated
    offset_ns: float = 0.0

    def window(self, name: str = "bench.window") -> tuple[float, float] | None:
        spans = [(s, e) for s, e, n in self.annotations if n == name]
        return spans[0] if spans else None


def program_name(event_name: str) -> str:
    """``jit_path_lookup(2666184606650437483)`` → ``jit_path_lookup``."""
    return event_name.split("(", 1)[0]


def offset(programs, enqueued: dict, completed: dict) -> float:
    """Host-minus-device clock offset: each execution started after its
    enqueue began and ended before its completion callback ran."""
    lows, highs = [], []
    for ordinal, run_id, s, e in programs:
        key = (ordinal, run_id)
        if key in enqueued and key in completed:
            lows.append(enqueued[key] - s)
            highs.append(completed[key] - e)
    if not lows:
        return 0.0
    lo, hi = max(lows), min(highs)
    if lo <= hi:
        return (lo + hi) / 2
    mids = sorted((a + b) / 2 for a, b in zip(lows, highs))
    return mids[len(mids) // 2]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw: dict[str, list] = {}
    enqueued: dict = {}
    completed: dict = {}
    annotations: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ordinal = int(plane.name[len(DEVICE_PLANE):])
            evs = raw.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        st = dict(e.stats)
                        evs.append((ordinal, st.get("run_id"), e.start_ns, e.end_ns,
                                    program_name(e.name)))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION):
                    annotations.append((e.start_ns, e.end_ns, e.name))
                elif e.name in (ENQUEUE, COMPLETE):
                    st = dict(e.stats)
                    key = (st.get("device_ordinal", 0), st.get("run_id"))
                    book = enqueued if e.name == ENQUEUE else completed
                    book.setdefault(key, e.start_ns)
    delta = offset([(o, r, s, e) for evs in raw.values() for o, r, s, e, _ in evs],
                   enqueued, completed)
    devices = {name: sorted((s + delta, e + delta, n, enqueued.get((o, r)))
                            for o, r, s, e, n in evs)
               for name, evs in raw.items()}
    annotations.sort()
    return Trace(devices, annotations, delta)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy(trace: Trace, lo: float, hi: float) -> float:
    """Nanoseconds in [lo, hi] in which a program ran, averaged over the
    device planes; 0 when the trace holds no device."""
    if not trace.devices:
        return 0.0
    return sum(length(clip(merge(evs), lo, hi))
               for evs in trace.devices.values()) / len(trace.devices)


def busy_inside(trace: Trace, names: set[str], lo: float, hi: float) -> float:
    """Device nanoseconds of the programs enqueued inside the annotations
    ``names`` within [lo, hi], averaged over the device planes.  A program
    with no enqueue event counts where its (shifted) middle falls."""
    if not trace.devices:
        return 0.0
    windows = merge(clip([(s, e) for s, e, n in trace.annotations if n in names],
                         lo, hi))
    starts = [s for s, _ in windows]
    total = 0.0
    for evs in trace.devices.values():
        mine = []
        for s, e, _, enq in evs:
            t = (s + e) / 2 if enq is None else enq
            k = bisect.bisect_right(starts, t) - 1
            if k >= 0 and t < windows[k][1]:
                mine.append((s, e))
        total += length(merge(mine))
    return total / len(trace.devices)


def annotated(trace: Trace, names: set[str], lo: float, hi: float) -> float:
    """Host nanoseconds inside the annotations ``names`` within [lo, hi]."""
    return length(merge(clip([(s, e) for s, e, n in trace.annotations
                              if n in names], lo, hi)))


def top_programs(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[program, seconds]] of the programs that ran longest, averaged over
    the device planes."""
    totals: dict[str, float] = {}
    for evs in trace.devices.values():
        for s, e, name, _ in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                totals[name] = totals.get(name, 0.0) + d
    k = max(1, len(trace.devices))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def flatten(annotations) -> list[tuple[float, float, str]]:
    """Properly nested (start, end, name) annotations → disjoint segments,
    each labelled with the innermost annotation open over it."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []
    t = 0.0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for s, e, name in sorted(annotations, key=lambda a: (a[0], -a[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            emit(t, end, nm)
            t = max(t, end)
        if stack:
            emit(t, s, stack[-1][1])
        stack.append((e, name))
        t = s
    while stack:
        end, nm = stack.pop()
        emit(t, end, nm)
        t = max(t, end)
    return out


def idle_by_host(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[host activity, seconds]]: the device's idle time in [lo, hi] by the
    innermost host annotation open over it (``bench.`` dropped; ``other``
    where none is), averaged over the device planes, largest first."""
    segs = flatten([a for a in trace.annotations
                    if a[2] != "bench.window" and a[1] > lo and a[0] < hi])
    starts = [s for s, _, _ in segs]
    totals: dict[str, float] = {}
    for evs in trace.devices.values():
        gaps, t = [], lo
        for s, e in clip(merge(evs), lo, hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for g0, g1 in gaps:
            covered = 0.0
            k = max(0, bisect.bisect_right(starts, g0) - 1)
            while k < len(segs) and segs[k][0] < g1:
                s, e, name = segs[k]
                d = min(e, g1) - max(s, g0)
                if d > 0:
                    label = name[len(ANNOTATION):]
                    totals[label] = totals.get(label, 0.0) + d
                    covered += d
                k += 1
            totals["other"] = totals.get("other", 0.0) + (g1 - g0) - covered
    k = max(1, len(trace.devices))
    ranked = sorted(((name, ns) for name, ns in totals.items() if ns > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]
