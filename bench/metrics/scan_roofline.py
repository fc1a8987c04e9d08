"""Share of the prefix-scan roofline: the least time the chip needs for the
work of every Q4 prefix-search engine call of the window (roofline.py),
over the device's busy time inside those calls' annotations."""
import roofline
import xplane


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = xplane.busy_inside(run.trace, {"bench.q4_search"}, lo, hi)
    least = 0.0
    for name, args, _, out in run.calls:
        if name != "q4_search":
            continue
        least += roofline.least_seconds(*roofline.scan_work(
            [len(p.encode()) for p in args[0]],
            [sum(len(x.encode()) for x in r) for r in out],
            run.table["n_paths"], run.table["mean_path_bytes"]), run.peak)
    if busy <= 0 or least <= 0:
        return None
    return 100.0 * least / (busy / 1e9)
