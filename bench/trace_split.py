"""One traced run of a cell, with the program's spans in its breakdown.

    python bench/trace_split.py --workload <cell> --seed <n> --seconds <s>

Runs ``harness.py --trace 1`` and prints its lines, with the cell's
end-to-end metrics read beside the per-layer ones (the cost of tracing is
traced against untraced), then one more JSON line, ``{"program": {...}}``,
from the same trace:

- ``idle_gaps``: the device's idle time by the innermost annotation or
  program span open over it (``program_spans.idle_by_span``);
- ``stage_cover``: the share of the ``device.<read>`` spans' time that
  their ``device.prep``, ``device.wait`` and ``device.map`` spans cover;
- ``device_ops``: the device ops that ran longest, by op name;
- ``scan_pack_share``: the share of the Q4 scan's device time spent
  packing the path table: ``jit_pack_scan_table`` (once an epoch) over it
  and ``jit_prefix_search_packed`` (once a call) together.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import program_spans  # noqa: E402
import xplane  # noqa: E402

PACK_PROGRAM, SCAN_PROGRAM = "jit_pack_scan_table", "jit_prefix_search_packed"


def split(path: str, trace: xplane.Trace, data=None) -> dict:
    """The program's part of the breakdown of the trace at ``path``
    (``data`` its parsed ``ProfileData``, where the caller has it)."""
    prog = program_spans.load(path, trace, data)
    lo, hi = trace.window()
    inside = [(s, e, n) for s, e, n in prog.spans if s < hi and e > lo]
    calls = sum(e - s for s, e, n in inside if n in program_spans.READ_CALLS)
    stages = sum(e - s for s, e, n in inside if n in program_spans.STAGES)
    return {"idle_gaps": program_spans.idle_by_span(trace, prog, lo, hi, n=16),
            "stage_cover": stages / calls if calls else None,
            "device_ops": program_spans.op_seconds(prog, lo, hi),
            "scan_pack_share": program_spans.program_share(
                trace, PACK_PROGRAM, SCAN_PROGRAM, lo, hi)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    found: dict = {}
    load, read = xplane.load, harness.read_metrics

    def load_and_split(path, data=None):
        trace = load(path, data)
        found.update(split(path, trace, data))
        return trace

    xplane.load = load_and_split
    harness.read_metrics = lambda specs, view: read(cell.end_to_end + specs, view)
    rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"])
    if rc == 0:
        print(json.dumps({"program": found}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
