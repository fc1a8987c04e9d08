"""Mean duration of the program's ``planner.flush`` span over the window's
waves: the BatchPlanner's work, engine calls included."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "planner.flush"]
    return sum(durs) / len(durs) / 1e3 if durs else None
