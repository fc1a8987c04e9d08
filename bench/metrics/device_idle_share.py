"""Share of the traced window in which no program ran on the device."""
import xplane


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - xplane.busy(run.trace, lo, hi) / (hi - lo))
