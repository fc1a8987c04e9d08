"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus # section headers).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table4     # one table
    PYTHONPATH=src python -m benchmarks.run --smoke    # cheap CI subset
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import table2_backends  # noqa: E402
import table3_schema  # noqa: E402
import table4_end2end  # noqa: E402
import table5_online  # noqa: E402
import table6_ablation  # noqa: E402
import fig5_scaling  # noqa: E402
import errorbook_bench  # noqa: E402
import roofline_report  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ALL = {
    "table2": lambda: table2_backends.run(),
    "table3": lambda: table3_schema.run(),
    "table4": lambda: table4_end2end.run(),
    "table5": lambda: table5_online.run(),
    "table6": lambda: table6_ablation.run(),
    "fig5": lambda: fig5_scaling.run(),
    "errorbook": lambda: errorbook_bench.run(),
    "roofline": lambda: roofline_report.run(),
}


#: reduced-size runs for CI (scripts/smoke.sh): exercises the engine
#: layer end-to-end — backend sweep incl. host-sharded + device engines,
#: and the batched online path — in well under a minute
SMOKE = {
    "table2": lambda: table2_backends.run(n_iters=50, warmup=10),
    "table5": lambda: table5_online.run(n_queries=128),
}


def main() -> None:
    enable_compile_cache()
    args = sys.argv[1:]
    if args and args[0] == "--smoke":
        for name, fn in SMOKE.items():
            print(f"\n##### {name} (smoke) #####")
            fn()
        return
    which = args or list(ALL)
    for name in which:
        if name not in ALL:
            print(f"unknown benchmark {name!r}; have {sorted(ALL)}")
            continue
        print(f"\n##### {name} #####")
        ALL[name]()


if __name__ == "__main__":
    main()
