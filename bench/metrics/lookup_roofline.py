"""Share of the lookup roofline: the least time the chip needs for the work
of every Q1/Q2/Q3/Q4-contains engine call of the window (roofline.py),
over the device's busy time inside those calls' annotations."""
import roofline
import xplane

CALLS = {"q1_get": "n_paths", "q2_ls": "n_paths", "q3_navigate": "n_paths",
         "q4_contains": "n_tokens"}


def _keys(name, keys):
    if name == "q3_navigate":
        return sum(1 + sum(1 for s in p.split("/") if s) for p in keys)
    return len(keys)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = xplane.busy_inside(run.trace, {"bench." + c for c in CALLS}, lo, hi)
    least = sum(roofline.least_seconds(
        *roofline.lookup_work(_keys(name, args[0]), run.table[CALLS[name]]), run.peak)
        for name, args, _, _ in run.calls if name in CALLS)
    if busy <= 0 or least <= 0:
        return None
    return 100.0 * least / (busy / 1e9)
