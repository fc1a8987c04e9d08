"""WikiKV's benchmark: one cell, one run, one process, one chip.

    python bench/harness.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``, a
layout of data from ``bench/layouts/``) and a traffic mix
(``bench/traffic/<name>.json``); each metric is read by
``bench/metrics/<name>.py``.  A run:

1. builds the configuration's data from ``--seed`` through ``PathStore``;
2. freezes it with ``DeviceEngine.from_store``;
3. warms up: the mix's warm-up waves, then every batch-size bucket of each
   read operation the mix issued (batches are padded to powers of two),
   then a few settling waves, then the key-table sizes the window's
   admissions grow through (admitted and unlinked again), then traffic
   until every request in flight started after those batches;
4. measures for ``--seconds``: waves of ``BatchPlanner.flush()`` and
   ``DeviceEngine.refresh()`` driven by the mix's closed-loop clients
   (with ``--trace 1`` under the profiler, with the program's spans on);
5. reads every written path back, then compares every answer of the run
   with the plain reference (``refkv.py``) replayed wave by wave;
6. prints the compared numbers with their limits, then one JSON line.

A JAX backend that is not a TPU, or fewer chips than the cell asks for, is
an error: the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import compare  # noqa: E402
import refkv  # noqa: E402

#: every compared number must be at most its limit (exact comparisons)
LIMITS = {"read_mismatches": 0, "write_mismatches": 0,
          "readback_mismatches": 0, "unresolved": 0}


def process_start() -> float:
    """Wall-clock start of this process (from /proc), else import time."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        t = time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return T_IMPORT
    return t if 0 <= T_IMPORT - t < 60 else T_IMPORT


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"harness: no {what} named {name!r} in BENCHMARK.json")


@dataclass
class Cell:
    """Everything one cell's run reads from the benchmark's files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> "Cell":
        spec = json.loads(bench_file.read_text())
        w = by_name(spec["workloads"], workload, "workload")
        cfg = by_name(spec["configs"], w["config"], "config")

        def mine(metrics):
            return [m for m in metrics if workload in m.get("workloads", [workload])]

        return cls(name=workload, chips=int(w["chips"]),
                   config=json.loads((ROOT / cfg["file"]).read_text()),
                   traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
                   end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


@dataclass
class RunView:
    """What the metric readers see of a finished run."""

    waves: list
    window_s: float
    finished: list
    setup_s: float
    spans: list = field(default_factory=list)
    trace: object = None
    trace_window: tuple | None = None
    calls: list = field(default_factory=list)
    peak: dict | None = None
    table: dict = field(default_factory=dict)


class CompileCounter:
    """Counts JAX trace, lowering and compile events while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kwargs):
        if self.active and name.startswith("/jax/core/compile/"):
            self.count += 1


class GcPauses:
    """Pauses of Python's cyclic collector while ``active``: (generation, ms)."""

    def __init__(self):
        self.active = False
        self.pauses: list[tuple[int, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.active:
            self.pauses.append((info["generation"], (time.perf_counter() - self._t0) * 1e3))

    def summary(self) -> dict:
        """Per generation: [count, total ms, longest ms]."""
        out = {}
        for g in sorted({g for g, _ in self.pauses}):
            ms = [m for gg, m in self.pauses if gg == g]
            out[g] = [len(ms), round(sum(ms), 1), round(max(ms), 1)]
        return out


def build_engine(layout, config: dict):
    """The system under test: the layout's records through ``PathStore``
    into an in-memory ``MemKV``, frozen by ``DeviceEngine.from_store`` with
    a writer whose clock is the reference's constant."""
    from repro.core.consistency import WikiWriter
    from repro.core.engine import DeviceEngine
    from repro.core.store import MemKV, PathStore

    from loop import to_program

    store = PathStore(MemKV(memtable_limit=int(config["store"]["memtable_limit"])))
    for path, rec in layout.items:
        store.put_record(path, to_program(rec))
    store.flush()
    writer = WikiWriter(store, clock=lambda: refkv.CLOCK)
    return DeviceEngine.from_store(store, writer=writer,
                                   refresh_cadence=int(config["refresh_cadence"]))


def warm_ops(layout, op: str, n: int) -> list[tuple]:
    """n distinct, valid keys for a warm-up batch of ``op``."""
    leaves = layout.populations[next(iter(layout.populations))]
    if op == "q4_search":
        parents = list(dict.fromkeys(refkv.parent(p) for p in leaves[: 256 * n]))
        return [(op, p, 10) for p in parents[:n]]
    if op == "q4_contains":
        return [(op, refkv.basename(p).lower(), 64) for p in leaves[:n]]
    return [(op, p, None) for p in leaves[:n]]


def warm_sizes(op: str, clients: int) -> list[int]:
    """Batch sizes whose padded buckets cover every batch the mix can send:
    up to 4 keys per client for lookups, one prefix per client for scans."""
    top = clients if op == "q4_search" else 4 * clients
    sizes, s = [], 1
    while s < 2 * top:
        sizes.append(s)
        s *= 2
    return sizes


def window_view(waves: list, finished: list, setup_s: float, **extra) -> RunView:
    """The metric readers' view of one window's waves."""
    t0, t1 = waves[0].t_start, waves[-1].t_end
    return RunView(waves=waves, window_s=t1 - t0, setup_s=setup_s,
                   finished=[f for f in finished if t0 <= f.t_done <= t1], **extra)


def read_metrics(specs: list[dict], view: RunView) -> dict:
    """Each metric of ``specs`` that its reader finds, with its unit."""
    out = {}
    for m in specs:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


#: the program pads its key table to this many rows; growing the table by a
#: step of it reaches the next padded shape of every lookup of the table
GROWTH_STEP = 128
#: window waves planned for, over the count at the warm-up's median wave
GROWTH_MARGIN = 1.3
#: the read operations that look paths up in the key table
KEY_TABLE_READS = ("q1_get", "q2_ls", "q3_navigate")


def warm_buckets(loop, layout, seen: list[str]) -> None:
    """A batch of every padded bucket of each read operation in ``seen``."""
    for op in seen:
        for n in warm_sizes(op, loop.gen.clients):
            loop.read_wave("warmup", warm_ops(layout, op, n))


def warm_growth(loop, seen: list[str], layout, timing: list, seconds: float) -> bool:
    """Warm the key-table sizes the window will grow through.  A mix that
    admits records grows the table, and each padded size of it is a new
    shape of every lookup in it.  From the growth per wave and the median
    wave of the ``timing`` waves, plan the window's growth with a margin;
    admit it in steps of ``GROWTH_STEP``, with a Q1 batch of every bucket at
    each step (Q1-Q3 share the lookup), then unlink it all, back to the size
    the window starts at.  True where a refresh on the way rebuilt the
    table rather than patching it (new shapes for every table)."""
    if not timing or not any(op in KEY_TABLE_READS for op in seen):
        return False
    grow = max(sum(1 for e in w.entries if e[0] == "w_admit")
               - sum(1 for e in w.entries if e[0] == "w_unlink") for w in timing)
    wave_s = float(np.median([w.t_end - w.t_start for w in timing]))
    if grow <= 0 or wave_s <= 0:
        return False
    steps = int(np.ceil(GROWTH_MARGIN * grow * seconds / wave_s / GROWTH_STEP))
    grown: list[str] = []
    rebuilt = False

    def last_rebuilt():
        return getattr(loop.engine, "last_refresh_kind", None) != "patch"

    for _ in range(steps):
        items = loop.gen.new_items(GROWTH_STEP)
        loop.write_wave("warmup", admits=items)
        rebuilt |= last_rebuilt()
        grown.extend(p for p, _ in items)
        warm_buckets(loop, layout, ["q1_get"])
    loop.write_wave("warmup", unlinks=grown)
    return rebuilt or last_rebuilt()


#: traffic waves that settling may take before it gives up
MAX_SETTLE_WAVES = 1000


def settle(loop) -> None:
    """Traffic waves until every request in flight started after the waves
    that were not traffic: a request that waited through them would carry
    that wait into the window's latencies."""
    t = time.perf_counter()
    for _ in range(MAX_SETTLE_WAVES):
        if all(slot is None or slot[2] >= t for slot in loop.slots):
            return
        loop.wave("warmup")
    raise RuntimeError(f"requests still in flight after {MAX_SETTLE_WAVES} waves")


def warm_up(loop, layout, traffic: dict, seconds: float) -> None:
    """The mix's warm-up waves, then every padded batch bucket of each read
    operation they issued, then the settling waves, then the key-table
    sizes the window grows through, planned from the settling waves (after
    a rebuild on the way, the buckets once more), then traffic until no
    request in flight started before the last of these."""
    for _ in range(int(traffic["warmup_waves"])):
        loop.wave("warmup")
    seen = sorted({e[0] for w in loop.waves for e in w.entries if e[0] in compare.READS})
    warm_buckets(loop, layout, seen)
    timing = [loop.wave("warmup") for _ in range(int(traffic["settle_waves"]))]
    if warm_growth(loop, seen, layout, timing, seconds):
        warm_buckets(loop, layout, seen)
    settle(loop)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_origin: float | None = None, engine_hook=None, log=print) -> dict:
    """One run of a cell; returns the result object.  ``engine_hook(engine)``
    may change or wrap the engine after the freeze."""
    import jax

    from loadgen import LoadGen
    from loop import WaveLoop, annotate_engine

    t_origin = T_IMPORT if t_origin is None else t_origin
    counter = CompileCounter()
    collector = GcPauses()
    streams = np.random.SeedSequence(seed % 2 ** 64).spawn(2)
    stamps = {"start": time.time()}
    layout = load_module(BENCH / "layouts" / f"{cell.config['layout']}.py").build(
        cell.config, np.random.default_rng(streams[0]))
    stamps["layout"] = time.time()
    engine = build_engine(layout, cell.config)
    stamps["engine"] = time.time()
    if engine_hook is not None:
        engine = engine_hook(engine)
    gen = LoadGen(cell.traffic, layout, np.random.default_rng(streams[1]))
    annotate = jax.profiler.TraceAnnotation if trace else None
    loop = WaveLoop(engine, gen, annotate)

    warm_up(loop, layout, cell.traffic, seconds)
    stamps["warm_up"] = time.time()

    # -- measured window ------------------------------------------------------
    calls: list = []
    tmp = None
    if trace:
        from repro import obs

        tmp = tempfile.mkdtemp(prefix="wikikv-bench-")
        annotate_engine(engine, annotate, calls)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        obs.configure(enabled=True, ring_size=1 << 22)
    first = len(loop.waves)
    counter.active = collector.active = True
    cpu0 = time.process_time()
    t_window = time.time()
    setup_s = t_window - t_origin
    with annotate("bench.window") if trace else nullcontext():
        deadline = time.perf_counter() + seconds
        while loop.wave("window").t_end < deadline:
            pass
    counter.active = collector.active = False
    cpu_s = time.process_time() - cpu0
    waves = loop.waves[first:]
    n_calls = len(calls)
    spans = []
    if trace:
        spans = list(obs.registry().ring)
        obs.configure(enabled=False)
        jax.profiler.stop_trace()
    names = list(stamps)
    window_s = waves[-1].t_end - waves[0].t_start
    log(json.dumps({"compiles_in_window": counter.count, "waves": len(waves),
                    "warmup_waves": first,
                    "setup_parts_s": {b: round(stamps[b] - stamps[a], 3)
                                      for a, b in zip(names, names[1:])},
                    "gc_in_window": collector.summary(),
                    "cpu_per_wall": round(cpu_s / window_s, 3),
                    "wave_flush_refresh_ms": [[round((w.t_flush_end - w.t_start) * 1e3),
                                               round((w.t_end - w.t_flush_end) * 1e3)]
                                              for w in waves]}), flush=True)
    dev = jax.devices()
    memory_peak = max((d.memory_stats().get("peak_bytes_in_use", 0)
                       for d in dev if d.memory_stats()), default=0)

    # -- read-back of every written path --------------------------------------
    written = list(dict.fromkeys(e[1] for w in loop.waves for e in w.entries
                                 if e[0].startswith("w_")))
    chunk = max(warm_sizes("q1_get", gen.clients))
    for lo in range(0, len(written), chunk):
        loop.read_wave("readback", [("q1_get", p, None) for p in written[lo:lo + chunk]])

    # -- metrics --------------------------------------------------------------
    view = window_view(waves, loop.finished, setup_s, spans=spans, calls=calls[:n_calls])
    breakdown = device_extra = None
    if trace:
        import xplane
        import roofline

        files = sorted(Path(tmp).rglob("*.xplane.pb"))
        view.trace = xplane.load(str(files[-1]))
        shutil.rmtree(tmp, ignore_errors=True)
        view.trace_window = view.trace.window()
        view.peak = roofline.peaks(dev[0].device_kind)
        live = [p for p, _ in layout.items]
        net = sum((e[0] == "w_admit") - (e[0] == "w_unlink")
                  for w in loop.waves[:first + len(waves)] for e in w.entries)
        view.table = {
            "n_paths": len(live) + net,
            "n_tokens": len(set().union(*map(refkv.segment_tokens, live))),
            "mean_path_bytes": sum(len(p.encode()) for p in live) / len(live),
        }
        lo, hi = view.trace_window
        device_extra = {"busy_s": xplane.busy(view.trace, lo, hi) / 1e9,
                        "window_s": (hi - lo) / 1e9}
        breakdown = {"device_ops": xplane.top_programs(view.trace, lo, hi),
                     "idle_gaps": xplane.idle_by_host(view.trace, lo, hi)}
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, view)
    attempted = sum(len(w.entries) for w in waves)
    failed = sum(1 for w in waves for e in w.entries
                 if not e[3].done or isinstance(e[3].value, Exception))

    # -- correctness: free the program, replay the reference -----------------
    all_waves = loop.waves
    del loop, engine, gen
    gc.collect()
    tallies = compare.check(layout.items, all_waves)
    checks, correct = judge(tallies)
    log(json.dumps({"after_window_s": round(time.time() - t_window - seconds, 3)}), flush=True)
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(memory_peak)}
    if device_extra:
        device.update(device_extra)
    result = {"correct": correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["tallies"] = {k: {f: getattr(t, f) for f in
                             ("reads", "read_mismatches", "writes",
                              "write_mismatches", "unresolved")}
                         for k, t in tallies.items()}
    result["examples"] = [x for t in tallies.values() for x in t.examples][:5]
    result["checks"] = checks
    return result


def judge(tallies: dict) -> tuple[dict, bool]:
    """The compared numbers of the run (warm-up, the measured window, the
    read-back), each with its limit, and the verdict."""
    run = [t for p, t in tallies.items() if p != "readback"]
    back = tallies.get("readback", compare.Tally())
    values = {
        "read_mismatches": sum(t.read_mismatches for t in run),
        "write_mismatches": sum(t.write_mismatches for t in run),
        "readback_mismatches": back.read_mismatches,
        "unresolved": sum(t.unresolved for t in run) + back.unresolved,
    }
    compared = sum(t.reads + t.writes for t in run) + back.reads
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    return checks, compared > 0 and all(v <= LIMITS[k] for k, v in values.items())


def emit(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result as
    the last line of stdout (``checks`` last in it)."""
    for ex in result.pop("examples", []):
        print(f"mismatch: {ex}", file=sys.stderr)
    checks = result.pop("checks")
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, engine_hook=None) -> int:
    """The command line; ``engine_hook`` as for ``run_cell``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_origin = process_start()
    cell = Cell.load(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"harness: the program (src/repro) is not in {ROOT}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"harness: JAX backend is {backend!r}, not a TPU", file=sys.stderr)
        return 2
    if len(jax.devices()) < cell.chips:
        print(f"harness: {len(jax.devices())} chips, the cell needs {cell.chips}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_origin=t_origin,
                      engine_hook=engine_hook)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
