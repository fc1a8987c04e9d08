"""Mean duration of the program's ``device.refresh`` span per refresh that
applied writes: delta build from the store, patch and device upload."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "device.refresh"]
    return sum(durs) / len(durs) / 1e3 if durs else None
