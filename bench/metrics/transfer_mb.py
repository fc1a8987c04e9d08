"""Megabytes copied between host and device per wave: the ``h2d_bytes`` tags
of the program's ``device.prep`` and ``device.refresh.upload`` spans and the
``d2h_bytes`` tags of its ``device.wait`` spans, summed over the window."""
import program_spans


def read(run):
    parts = [program_spans.tag_sum(run.spans, t) for t in ("h2d_bytes", "d2h_bytes")]
    if all(p is None for p in parts) or not run.waves:
        return None
    return sum(p or 0 for p in parts) / 1e6 / len(run.waves)
