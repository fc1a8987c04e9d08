"""Builds of the prefix scan's packed table per wave: the ``scan_packs`` tags
of the program's ``device.q4_pack`` spans, summed over the window.  One
build serves every Q4 prefix search of an epoch, so a cell with one patch
refresh a wave reads 1."""
import program_spans


def read(run):
    packs = program_spans.tag_sum(run.spans, "scan_packs")
    if packs is None or not run.waves:
        return None
    return packs / len(run.waves)
