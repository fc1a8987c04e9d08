"""DeviceEngine result mapping per wave: the self time of the program's
``device.map`` spans (row ids to records or paths, the bitmap's nonzero,
sorts, token overlays and limits), less the collector passes nested in
them."""
import program_spans


def read(run):
    us = program_spans.self_us(run.spans, "device.map")
    if us is None or not run.waves:
        return None
    return us / len(run.waves) / 1e3
