"""DeviceEngine staging per wave: the self time of the program's
``device.prep`` spans (normalize, hash, pack, pad and upload of each batched
read), less the collector passes nested in them."""
import program_spans


def read(run):
    us = program_spans.self_us(run.spans, "device.prep")
    if us is None or not run.waves:
        return None
    return us / len(run.waves) / 1e3
