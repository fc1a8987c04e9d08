"""Planner futures resolved in the window, reads and writes, per second of
the window (from the first wave's start to the last wave's end)."""


def read(run):
    if not run.waves:
        return None
    return sum(w.resolved for w in run.waves) / run.window_s
