"""``planner_flush_ms`` in the cells that report ``op_p95_ms.update``: the
mean duration of the program's ``planner.flush`` span over the window's
waves."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "planner.flush"]
    return sum(durs) / len(durs) / 1e3 if durs else None
