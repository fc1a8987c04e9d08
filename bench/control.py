"""The control of the comparison: a benchmark run with the program's own
refresh deferral switched on (``DeviceEngine.refresh_cadence = 2``), which
breaks the configurations' Δ = 1 guarantee, so ``correct`` must read false.

    python bench/control.py --workload <cell> --seed <n> --seconds <s> [--trace 0]

Arguments and output are the harness's.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

CONTROL_CADENCE = 2


def deferred_refresh(engine):
    engine.refresh_cadence = CONTROL_CADENCE
    return engine


if __name__ == "__main__":
    sys.exit(harness.main(engine_hook=deferred_refresh))
