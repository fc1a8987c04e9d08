"""The program's own spans: in the span ring a traced run reads, and in the
profiler trace beside the harness's annotations and the device's ops.

With tracing on, every ``repro.obs`` span also enters a
``jax.profiler.TraceAnnotation`` of its name, so the ``*.xplane.pb`` holds
it on the host clock that ``xplane.load`` maps device time onto.  This
module reads those events by the program's span namespaces, the device's
per-op events (the ``XLA Ops`` line, where a ``pallas_call``'s ``name``
becomes its op name), and the ring's tags (``h2d_bytes``, ``d2h_bytes``).
"""
from __future__ import annotations

from dataclasses import dataclass

import xplane

#: name prefixes of the program's spans (``repro.obs`` call sites)
NAMESPACES = ("device.", "host.", "planner.", "serving.", "wal.", "lsm.", "gc.")
OPS_LINE = "XLA Ops"
#: the stages of a batched device read (``DeviceEngine.q1_get`` ...)
STAGES = ("device.prep", "device.wait", "device.map")
READ_CALLS = ("device.q1_get", "device.q2_ls", "device.q3_navigate",
              "device.q4_search", "device.q4_contains")


# -- the span ring (``RunView.spans``) ----------------------------------------
def self_us(events: list[dict], name: str) -> float | None:
    """Summed self time, in µs, of the ring's spans named ``name``: each
    span's duration less that of the spans directly nested in it on its
    thread.  None where no span has the name."""
    by_tid: dict = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(e)
    total, seen = 0.0, False

    def close(frame):
        nonlocal total, seen
        end, ev, children = frame
        if ev["name"] == name:
            total += ev["dur"] - children
            seen = True

    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list[list] = []
        for e in evs:
            while stack and e["ts"] >= stack[-1][0]:
                close(stack.pop())
            if stack:
                stack[-1][2] += e["dur"]
            stack.append([e["ts"] + e["dur"], e, 0.0])
        while stack:
            close(stack.pop())
    return total if seen else None


def tag_sum(events: list[dict], tag: str) -> int | None:
    """Sum of a tag over the spans that carry it; None where none does."""
    vals = [e["args"][tag] for e in events if tag in (e.get("args") or {})]
    return sum(vals) if vals else None


# -- the profiler trace -------------------------------------------------------
@dataclass
class ProgramTrace:
    #: the program's spans: (start_ns, end_ns, name) on the host's timeline
    spans: list[tuple[float, float, str]]
    #: per device plane: (start_ns, end_ns, op name) on the host's timeline
    ops: dict[str, list[tuple[float, float, str]]]


def op_name(event_name: str) -> str:
    """``%prefix_search_scan.1 = s8[...] custom-call(...)`` →
    ``prefix_search_scan``."""
    head = event_name.split(" ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def load(path: str, trace: xplane.Trace, data=None) -> ProgramTrace:
    """The program's spans and the device's ops of the trace at ``path``
    (``data`` its ``ProfileData`` where already parsed), shifted by the
    clock offset ``xplane.load`` found for ``trace``."""
    from jax.profiler import ProfileData

    data = data or ProfileData.from_file(path)
    spans: list = []
    ops: dict = {}
    for plane in data.planes:
        if plane.name.startswith(xplane.DEVICE_PLANE):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.start_ns + trace.offset_ns, e.end_ns + trace.offset_ns,
                                op_name(e.name)) for e in line.events)
            continue
        for line in plane.lines:
            spans.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith(NAMESPACES))
    spans.sort()
    return ProgramTrace(spans, {k: sorted(v) for k, v in ops.items()})


def idle_by_span(trace: xplane.Trace, prog: ProgramTrace, lo: float, hi: float,
                 n: int = 10) -> list:
    """``xplane.idle_by_host`` with the program's spans beside the harness's
    annotations: each idle gap goes to the innermost event of either kind
    open over it, a program span under its own name.  (``idle_by_host``
    strips ``bench.`` from a label, so the spans go in with it.)"""
    both = xplane.Trace(trace.devices, sorted(
        trace.annotations + [(s, e, xplane.ANNOTATION + name) for s, e, name in prog.spans]),
        trace.offset_ns)
    return xplane.idle_by_host(both, lo, hi, n)


def op_seconds(prog: ProgramTrace, lo: float, hi: float, n: int = 10) -> list:
    """[[op, seconds]] of the device ops that ran longest in [lo, hi],
    averaged over the device planes."""
    totals: dict[str, float] = {}
    for evs in prog.ops.values():
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                totals[name] = totals.get(name, 0.0) + d
    k = max(1, len(prog.ops))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def program_share(trace: xplane.Trace, part: str, other: str, lo: float, hi: float
                  ) -> float | None:
    """Percent of the device time of programs ``part`` and ``other`` in
    [lo, hi] that ``part`` took; None where neither ran."""
    def device_ns(program):
        return xplane.length(xplane.merge(xplane.clip(
            [(s, e) for evs in trace.devices.values() for s, e, name, _ in evs
             if name == program], lo, hi)))

    a, b = device_ns(part), device_ns(other)
    return 100.0 * a / (a + b) if a + b > 0 else None
