"""Host time of DeviceEngine calls per wave: the time inside the harness's
annotation of each batched engine call (reads and writes) less the device's
busy time inside it."""
import xplane
from loop import ENGINE_CALLS

NAMES = {"bench." + c for c in ENGINE_CALLS}


def read(run):
    if run.trace is None or not run.waves:
        return None
    lo, hi = run.trace_window
    host = (xplane.annotated(run.trace, NAMES, lo, hi)
            - xplane.busy_inside(run.trace, NAMES, lo, hi))
    return host / len(run.waves) / 1e6
