"""Time in Python's cyclic collector per wave: the program's ``gc.collect``
spans (one per collector pass, start to stop) summed over the window."""
import program_spans


def read(run):
    us = program_spans.self_us(run.spans, "gc.collect")
    if us is None or not run.waves:
        return None
    return us / len(run.waves) / 1e3
