"""The program's spans: the span-ring metrics on hand-made runs, the trace
reduction with program spans on hand-made and recorded traces, and a CPU
profile of a DeviceEngine wave read back from its ``.xplane.pb``."""
import gc
import json
from pathlib import Path

import pytest

import harness
import program_spans
import roofline
import trace_split
import xplane

ROOT = Path(__file__).resolve().parents[2]
TRACE = Path(__file__).resolve().parent / "data" / "v5e-lookup-scan.xplane.pb"
METRICS = ("gc_ms", "engine_prep_ms", "engine_map_ms", "transfer_mb")


def ev(name, ts, dur, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": tid,
            "args": args}


#: two waves of a run: a q1_get whose prep holds a collector pass, a
#: refresh upload, a q4_search on another thread, and a pass outside all
SPANS = [
    ev("device.q1_get", 0, 1000),
    ev("device.prep", 0, 300, op="q1_get", h2d_bytes=64),
    ev("gc.collect", 100, 50, generation=0),
    ev("device.wait", 300, 400, op="q1_get", d2h_bytes=32),
    ev("device.map", 700, 300, op="q1_get"),
    ev("device.refresh.upload", 2000, 500, h2d_bytes=1_000_000),
    ev("device.q4_search", 0, 5000, tid=2),
    ev("device.prep", 0, 1000, tid=2, op="q4_search", h2d_bytes=400),
    ev("device.wait", 1000, 2000, tid=2, op="q4_search", d2h_bytes=2_000_000),
    ev("device.map", 3000, 2000, tid=2, op="q4_search"),
    ev("gc.collect", 3500, 250, tid=2, generation=1),
    ev("gc.collect", 9000, 700, generation=2),
]


def view(spans, waves=2):
    return harness.RunView(waves=[None] * waves, window_s=1.0, finished=[], setup_s=0.0,
                           spans=spans)


def read(name, run):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(run)


def test_self_time_subtracts_nested_spans_by_thread():
    assert program_spans.self_us(SPANS, "device.prep") == (300 - 50) + 1000
    assert program_spans.self_us(SPANS, "device.map") == 300 + (2000 - 250)
    assert program_spans.self_us(SPANS, "device.q1_get") == 0
    assert program_spans.self_us(SPANS, "gc.collect") == 50 + 250 + 700
    assert program_spans.self_us(SPANS, "wal.commit") is None


def test_metric_readers_by_hand():
    run = view(SPANS)
    assert read("gc_ms", run) == pytest.approx(1000 / 2 / 1e3)
    assert read("engine_prep_ms", run) == pytest.approx(1250 / 2 / 1e3)
    assert read("engine_map_ms", run) == pytest.approx(2050 / 2 / 1e3)
    moved = 64 + 32 + 1_000_000 + 400 + 2_000_000
    assert read("transfer_mb", run) == pytest.approx(moved / 1e6 / 2)


def test_metric_readers_stay_silent_on_a_program_without_the_spans():
    """The spans a program without stage spans, collector span and
    transfer tags records: the readers return nothing and raise nothing."""
    older = [ev("planner.flush", 0, 900, depth=3), ev("device.q1_get", 10, 500),
             ev("device.refresh", 950, 40, kind="patch"),
             ev("device.refresh.apply", 960, 20, rows=1)]
    for name in METRICS:
        assert read(name, view(older)) is None
        assert read(name, view([])) is None


def test_op_name_drops_the_instruction_suffix():
    assert program_spans.op_name("%prefix_search_scan.1 = s8[4,64,128] custom-call(x)") == \
        "prefix_search_scan"
    assert program_spans.op_name("%path_lookup_probe = s32[256,1] custom-call(x)") == \
        "path_lookup_probe"
    assert program_spans.op_name("%copy-start.3 = (u32[8]) copy-start(%pinned_0_.1)") == \
        "copy-start"
    assert program_spans.op_name("%fusion") == "fusion"


def test_idle_by_span_labels_gaps_with_the_innermost_event():
    t = xplane.Trace(devices={"/device:TPU:0": [(10, 20, "p", None), (60, 70, "p", None)]},
                     annotations=[(0, 100, "bench.wave"), (0, 40, "bench.clients"),
                                  (40, 100, "bench.flush")])
    prog = program_spans.ProgramTrace(
        spans=[(40, 100, "planner.flush"), (45, 80, "device.q1_get"),
               (45, 55, "device.prep"), (55, 75, "device.wait"), (75, 80, "device.map"),
               (30, 35, "gc.collect")], ops={})
    assert dict(program_spans.idle_by_span(t, prog, 0, 100)) == pytest.approx(
        {"clients": 25e-9, "gc.collect": 5e-9, "planner.flush": 25e-9,
         "device.prep": 10e-9, "device.wait": 10e-9, "device.map": 5e-9})
    bare = program_spans.ProgramTrace(spans=[], ops={})
    assert program_spans.idle_by_span(t, bare, 0, 100) == xplane.idle_by_host(t, 0, 100)


def test_scan_pack_share_by_hand():
    """The pack's share of the two packed-scan programs' device time, on
    both planes, clipped to the window; other programs do not count."""
    pack, scan = trace_split.PACK_PROGRAM, trace_split.SCAN_PROGRAM
    t = xplane.Trace(devices={
        "/device:TPU:0": [(0, 100, pack, None), (100, 400, scan, None),
                          (400, 900, "jit_path_lookup", None), (900, 1200, scan, None)],
        "/device:TPU:1": [(0, 100, pack, None), (100, 400, scan, None)]}, annotations=[])
    # both planes' packs overlap in time and count once: 100 of 100 + 600
    assert program_spans.program_share(t, pack, scan, 0, 1200) == pytest.approx(100 / 7)
    # the window cuts the last scan to 100 ns
    assert program_spans.program_share(t, pack, scan, 50, 1000) == \
        pytest.approx(100 * 50 / (50 + 300 + 100))
    assert program_spans.program_share(t, pack, scan, 400, 900) is None


@pytest.fixture(scope="module")
def recorded():
    trace = xplane.load(str(TRACE))
    return trace, program_spans.load(str(TRACE), trace)


def test_recorded_trace_reduces_as_before(recorded):
    """The recording holds no program span: the idle attribution is the
    harness's own, and the device ops carry the kernels' names of then."""
    trace, prog = recorded
    assert prog.spans == []
    lo = min(s for s, _, _ in trace.annotations)
    hi = max(e for _, e, _ in trace.annotations)
    assert program_spans.idle_by_span(trace, prog, lo, hi, n=100) == \
        xplane.idle_by_host(trace, lo, hi, n=100)
    names = {n for _, _, n in prog.ops["/device:TPU:0"]}
    assert {"path_lookup", "prefix_search"} <= names
    # every op ran inside a program execution once shifted to the host clock
    runs = trace.devices["/device:TPU:0"]
    for s, e, _ in prog.ops["/device:TPU:0"]:
        assert any(rs - 1 <= s and e <= re + 1 for rs, re, _, _ in runs)
    # a recording from before the packed scan: the pack share reads nothing
    assert program_spans.program_share(trace, trace_split.PACK_PROGRAM,
                                       trace_split.SCAN_PROGRAM, lo, hi) is None


def test_cpu_profile_holds_the_program_spans_inside_the_harness_annotation(tmp_path):
    """A DeviceEngine wave under a CPU ``jax.profiler`` trace with the
    registry on: its spans and a forced collector pass are in the
    ``.xplane.pb``, nested inside the harness's ``bench.*`` annotation."""
    import jax

    from repro import obs
    from repro.core import records as R
    from repro.core.consistency import WikiWriter
    from repro.core.engine import DeviceEngine
    from repro.core.store import MemKV, PathStore

    store = PathStore(MemKV())
    w = WikiWriter(store, clock=lambda: 0.0)
    w.ensure_root("root")
    w.admit("/a", R.DirRecord(name="a"))
    w.admit("/a/b", R.FileRecord(name="b", text="x"))
    eng = DeviceEngine.from_store(store)
    eng.q1_get(["/a/b"])                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    obs.configure(enabled=True)
    try:
        with jax.profiler.TraceAnnotation("bench.q1_get"):
            eng.q1_get(["/a/b", "/a"])
            gc.collect()
    finally:
        obs.configure()
        jax.profiler.stop_trace()
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    trace = xplane.load(path)
    prog = program_spans.load(path, trace)
    (outer,) = [(s, e) for s, e, n in trace.annotations if n == "bench.q1_get"]
    names = [n for s, e, n in prog.spans if outer[0] <= s and e <= outer[1]]
    assert {"device.q1_get", "device.prep", "device.wait", "device.map",
            "gc.collect"} <= set(names)
    (call,) = [(s, e) for s, e, n in prog.spans if n == "device.q1_get"]
    stages = sorted((s, e, n) for s, e, n in prog.spans if n in program_spans.STAGES)
    assert [n for *_, n in stages] == list(program_spans.STAGES)
    assert all(call[0] <= s and e <= call[1] for s, e, _ in stages)


def test_trace_split_reads_a_tiny_traced_run(monkeypatch):
    """The tool's reduction on a tiny kbnav run on the CPU: no device, so no
    device ops; the stages cover the engine calls; the span metrics read."""
    import test_rehearsal

    monkeypatch.setattr(roofline, "peaks", lambda kind: json.loads(
        (ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"])
    found = {}
    load = xplane.load

    def load_and_split(path, data=None):
        trace = load(path, data)
        found.update(trace_split.split(path, trace, data))
        return trace

    monkeypatch.setattr(xplane, "load", load_and_split)
    res = test_rehearsal.run("kbnav-1m.sessions", trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert {"engine_prep_ms", "engine_map_ms", "transfer_mb"} <= set(m)
    assert m["transfer_mb"]["value"] > 0
    assert 0.5 < found["stage_cover"] <= 1.0
    assert found["device_ops"] == [] and found["idle_gaps"] == []
    assert found["scan_pack_share"] is None
