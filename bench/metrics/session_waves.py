"""Planner waves per request (navigation session) finished in the window:
how many storage round trips the Navigator's plan takes."""


def read(run):
    if not run.finished:
        return None
    return sum(f.waves for f in run.finished) / len(run.finished)
