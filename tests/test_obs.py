"""Telemetry layer (ISSUE 8): mergeable histograms, span ring + Perfetto
export, disabled-mode no-op, snapshot schema, durable-counter reset."""
import json

import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import records as R
from repro.core.engine import (D_BLOOM_NEG, D_CACHE_HIT, BatchPlanner,
                               DeviceEngine, HostEngine)
from repro.core.store import MemKV, PathStore
from repro.obs.metrics import NULL_METRIC, Histogram, bucket_of
from repro.obs.trace import NULL_SPAN


@pytest.fixture
def traced():
    """Fresh ENABLED global registry; restores the env default after."""
    reg = obs.configure(enabled=True, ring_size=4096)
    yield reg
    obs.configure()


@pytest.fixture
def untraced():
    """Fresh DISABLED global registry; restores the env default after."""
    reg = obs.configure(enabled=False)
    yield reg
    obs.configure()


# latency-like values spanning 1µs .. 10s in ms units, plus exact zeros
_samples = st.lists(
    st.integers(min_value=0, max_value=10**7).map(lambda n: n / 1000.0),
    min_size=0, max_size=60)


# ---------------------------------------------------------------------------
# histogram: merge ≡ pooled, percentile accuracy
# ---------------------------------------------------------------------------
@settings(max_examples=40)
@given(_samples, _samples)
def test_histogram_merge_equals_pooled(a, b):
    """The load-bearing property: fixed global bucket boundaries make
    merge(h(A), h(B)) identical to h(A + B) — counts, extremes, and every
    percentile, bucket-for-bucket."""
    merged = Histogram(a).merge(Histogram(b))
    pooled = Histogram(a + b)
    assert merged.counts == pooled.counts
    assert merged.n == pooled.n and merged.zeros == pooled.zeros
    if a or b:
        assert merged.vmin == pooled.vmin and merged.vmax == pooled.vmax
    assert merged.total == pytest.approx(pooled.total)
    for q in (0, 10, 50, 90, 99, 99.9, 100):
        assert merged.percentile(q) == pooled.percentile(q)


@settings(max_examples=40)
@given(st.lists(st.integers(min_value=1, max_value=10**7)
                .map(lambda n: n / 1000.0), min_size=1, max_size=60))
def test_histogram_percentile_within_bucket_error(xs):
    """Reported percentiles stay within the ~2.2% half-bucket relative
    error of the exact nearest-rank sample percentile."""
    import math
    h = Histogram(xs)
    ordered = sorted(xs)
    for q in (50, 90, 99):
        exact = ordered[max(1, math.ceil(q / 100.0 * len(xs))) - 1]
        got = h.percentile(q)
        assert got == pytest.approx(exact, rel=0.023)
    assert h.percentile(0) == ordered[0]      # exact at the extremes
    assert h.percentile(100) == ordered[-1]


def test_histogram_zero_and_empty():
    assert Histogram().percentile(50) == 0.0
    assert Histogram().summary()["count"] == 0
    h = Histogram([0.0, 0.0, 0.0, 5.0])
    assert h.zeros == 3
    assert h.percentile(50) == 0.0            # rank 2 of 4 is a zero
    assert h.percentile(100) == 5.0


def test_bucket_width_is_sub16():
    # adjacent bucket boundaries differ by 2^(1/16) ≈ 4.4%
    assert bucket_of(1.0) == 0
    assert bucket_of(2.0 ** (1 / 16) * 1.001) == 1


# ---------------------------------------------------------------------------
# disabled mode: no-op singletons, zero recorded state
# ---------------------------------------------------------------------------
def test_disabled_mode_is_noop(untraced):
    reg = untraced
    assert not obs.enabled()
    # singletons, not fresh allocations
    assert obs.span("x", tag=1) is NULL_SPAN
    assert obs.histogram("h") is NULL_METRIC
    assert obs.counter("c") is NULL_METRIC
    assert obs.gauge("g") is NULL_METRIC
    with obs.span("outer") as sp:
        sp.set(kind="y")
        obs.histogram("h").record(1.0)
        obs.counter("c").inc()
        obs.gauge("g").set(3.0)
        obs.set_context(wave=7)
    assert reg.ring == type(reg.ring)() and len(reg.ring) == 0
    assert reg.histograms == {} and reg.counters == {} and reg.gauges == {}
    assert reg.ctx == {}                      # set_context gated too


def test_default_registry_matches_env(monkeypatch):
    monkeypatch.delenv(obs.TRACE_ENV, raising=False)
    try:
        assert obs.configure().enabled is False
        monkeypatch.setenv(obs.TRACE_ENV, "1")
        assert obs.configure().enabled is True
    finally:
        monkeypatch.delenv(obs.TRACE_ENV, raising=False)
        obs.configure()


# ---------------------------------------------------------------------------
# spans: ring events, histograms, nesting, correlation
# ---------------------------------------------------------------------------
def test_span_records_event_and_histogram(traced):
    reg = traced
    obs.set_context(session="s1")
    with obs.span("outer", a=1):
        with obs.span("inner") as sp:
            sp.set(kind="leaf")
    # a collector pass may land between the two: it is a span of its own
    events = [e for e in reg.ring if e["name"] != "gc.collect"]
    assert [e["name"] for e in events] == ["inner", "outer"]
    inner, outer = events
    assert inner["args"] == {"session": "s1", "kind": "leaf"}
    assert outer["args"] == {"session": "s1", "a": 1}
    assert inner["ts"] >= outer["ts"]
    assert inner["dur"] <= outer["dur"] + 1e-6
    assert reg.histograms["outer"].n == 1
    assert reg.histograms["inner"].n == 1
    assert obs.validate_events(list(reg.ring)) == []


def test_span_ring_is_bounded():
    reg = obs.configure(enabled=True, ring_size=16)
    try:
        for i in range(100):
            with obs.span(f"s{i}"):
                pass
        assert len(reg.ring) == 16
        assert [e for e in reg.ring if e["name"] != "gc.collect"][-1]["name"] == "s99"
    finally:
        obs.configure()


def test_validate_events_flags_overlap_and_requires():
    bad = [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0, "tid": 1},
        {"name": "b", "ph": "X", "ts": 50.0, "dur": 100.0, "tid": 1},
    ]
    problems = obs.validate_events(bad, require=("missing",))
    assert any("overlaps" in p for p in problems)
    assert any("missing" in p for p in problems)
    ok = [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0, "tid": 1},
        {"name": "b", "ph": "X", "ts": 10.0, "dur": 20.0, "tid": 1},
        {"name": "c", "ph": "X", "ts": 40.0, "dur": 20.0, "tid": 1},
    ]
    assert obs.validate_events(ok, require=("a", "b", "c")) == []


def test_trace_export_roundtrip(traced, tmp_path):
    with obs.span("one"):
        with obs.span("two"):
            pass
    out = tmp_path / "trace.json"
    n = obs.export_trace(str(out))
    assert n == len(traced.ring) >= 2
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = obs.load_events(str(out))
    assert obs.validate_events(events, require=("one", "two")) == []


def test_span_nesting_and_correlation_across_refresh_wave(traced, tmp_path):
    """A real write wave over the durable device tier leaves a
    well-nested trace — planner flush → device refresh → WAL commit —
    whose storage-tier spans carry the wave id that caused them."""
    from repro.storage import open_durable_store
    store = open_durable_store(str(tmp_path / "wiki"), sync="none")
    store.put_record("/", R.DirRecord(name=""))
    store.flush()
    dev = DeviceEngine.from_store(store)
    pl = BatchPlanner(dev)
    pl.admit("/d0", R.DirRecord(name="d0"))
    pl.admit("/d0/e0", R.FileRecord(name="e0", text="v0"))
    f = pl.get("/d0/e0")
    pl.flush()
    dev.refresh()
    assert f.done
    events = list(traced.ring)
    assert obs.validate_events(
        events, require=("planner.flush", "device.q1_get",
                         "device.refresh", "wal.commit")) == []
    by_name = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    # correlation: the flush stamped wave=1 before any nested span closed
    assert by_name["device.q1_get"][0]["args"]["wave"] == 1
    assert by_name["wal.commit"][-1]["args"]["wave"] == 1
    # the refresh span knows what the device applied
    refresh = by_name["device.refresh"][-1]
    assert refresh["args"]["kind"] in ("patch", "rebuild")
    assert refresh["args"]["epoch"] == dev.epoch
    # epoch context updated for *subsequent* spans
    assert traced.ctx["epoch"] == dev.epoch
    # the apply span carries the refresh kind (no per-kind histograms)
    apply = by_name["device.refresh.apply"][-1]
    assert apply["args"]["kind"] == refresh["args"]["kind"]
    assert traced.histograms["device.refresh.apply"].n == len(
        by_name["device.refresh.apply"])
    assert not {"device.refresh.patch", "device.refresh.rebuild"} & set(traced.histograms)
    assert "planner.queue_depth" not in traced.gauges
    store.close()


# ---------------------------------------------------------------------------
# snapshot: schema stability + percentile parity
# ---------------------------------------------------------------------------
def _mini_serving(store):
    from repro.configs import get_config
    from repro.core.oracle import HeuristicOracle
    from repro.data.tokenizer import HashTokenizer
    from repro.models import model as M
    from repro.runtime.serving import ServingEngine
    cfg = get_config("wikikv-router").reduced(d_model=32, vocab=512,
                                              n_layers=2)
    tok = HashTokenizer(vocab_size=cfg.vocab).fit(["x"])
    return ServingEngine(cfg, M.init_params(cfg, seed=0), tok, store,
                         HeuristicOracle(), batch_size=2, max_len=64)


def test_stats_snapshot_schema_stable_on_and_off():
    """The top-level key set is a contract: identical with tracing on
    and off, and JSON-able in both modes."""
    store = PathStore(MemKV())
    store.put_record("/", R.DirRecord(name=""))
    eng = _mini_serving(store)
    obs.configure(enabled=True)
    try:
        obs.histogram("serving.request_nav_ms").record(1.25)
        on = eng.stats_snapshot()
        obs.configure(enabled=False)
        off = eng.stats_snapshot()
    finally:
        obs.configure()
    expected = {"trace_enabled", "epoch", "waves", "ops", "dedup_ratio",
                "refresh", "durable", "pending", "latency_ms", "counters",
                "gauges", "pending_writes", "lanes_active"}
    assert set(on) == expected
    assert set(off) == expected
    assert on["trace_enabled"] and not off["trace_enabled"]
    assert on["latency_ms"]["serving.request_nav_ms"]["count"] == 1
    assert off["latency_ms"] == {}            # shape kept, content empty
    json.dumps(on), json.dumps(off)


def test_snapshot_percentiles_match_benchmark_logic(traced):
    """Acceptance: snapshot p50/p99 equal the benchmark tables' shared
    histogram percentile on identical samples (one implementation)."""
    samples = [0.05 * (i % 97) + 0.01 for i in range(500)]
    h = obs.histogram("op_ms")
    for v in samples:
        h.record(v)
    row = traced.metrics_snapshot()["latency_ms"]["op_ms"]
    ref = Histogram(samples)                   # == benchmarks.common.pct
    assert row["p50"] == round(ref.percentile(50), 6)
    assert row["p90"] == round(ref.percentile(90), 6)
    assert row["p99"] == round(ref.percentile(99), 6)
    assert row["max"] == round(max(samples), 6)


# ---------------------------------------------------------------------------
# satellite 1: durable high-water marks reset on store (re)attach
# ---------------------------------------------------------------------------
class _FakeDurable:
    """op_counts-only stand-in for a durable store."""

    def __init__(self, counts):
        self.counts = counts

    def op_counts(self):
        return dict(self.counts)


def test_durable_seen_resets_on_store_swap():
    """Regression: after a store swap (reopen), the fresh store's
    counters restart at 0 — stale high-water marks from the previous
    store must not silently drop its telemetry."""
    eng = HostEngine(PathStore(MemKV()))
    eng.store = _FakeDurable({"bloom_neg": 5, "cache_hit": 3,
                              "cache_miss": 1})
    eng.sync_durable_stats()
    assert eng.stats.ops[D_BLOOM_NEG] == 5
    eng.sync_durable_stats()                   # delta'd: no double count
    assert eng.stats.ops[D_BLOOM_NEG] == 5
    # swap in a reopened store: counters restarted below the old marks
    eng.store = _FakeDurable({"bloom_neg": 2, "cache_hit": 1,
                              "cache_miss": 0})
    eng.sync_durable_stats()
    assert eng.stats.ops[D_BLOOM_NEG] == 7     # 5 + 2, nothing dropped
    assert eng.stats.ops[D_CACHE_HIT] == 4


# ---------------------------------------------------------------------------
# profiler bridge, collector span, DeviceEngine stage spans
# ---------------------------------------------------------------------------
def _gc_hooks() -> list:
    import gc
    return [cb for cb in gc.callbacks
            if getattr(cb, "__func__", None) is obs.Registry._on_gc]


def _small_engine():
    """A DeviceEngine over a two-dimension wiki with its own writer."""
    from repro.core.consistency import WikiWriter
    store = PathStore(MemKV())
    w = WikiWriter(store, clock=lambda: 0.0)
    w.ensure_root("root")
    for d in ("alpha", "beta"):
        w.admit(f"/{d}", R.DirRecord(name=d, summary=f"dim {d}"))
        for i in range(3):
            w.admit(f"/{d}/page{i}", R.FileRecord(name=f"page{i}", text=f"{d} {i}"))
    return store, DeviceEngine.from_store(store, writer=WikiWriter(store, clock=lambda: 0.0))


def _wave_answers(eng) -> list:
    """Every read kind, a write wave with its refresh, every read again."""
    def reads():
        return [eng.q1_get(["/alpha/page1", "/beta", "/nope"]),
                eng.q2_ls(["/alpha", "/"]),
                eng.q3_navigate(["/beta/page2", "/alpha/zz"]),
                eng.q4_search(["/alpha", "beta/page"], limit=2),
                eng.q4_contains(["page1", "Beta", "gamma"], limit=5)]
    out = reads()
    out.append(eng.admit_many([("/gamma", R.DirRecord(name="gamma")),
                               ("/gamma/page9", R.FileRecord(name="page9", text="g"))]))
    eng.refresh()
    out.extend(reads())
    return out


def test_disabled_tracing_makes_no_annotation_and_no_gc_hook(monkeypatch):
    import jax.profiler

    obs.configure(enabled=True)
    try:
        traced_answers = _wave_answers(_small_engine()[1])
    finally:
        obs.configure(enabled=False)
    assert _gc_hooks() == []

    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation made with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    try:
        store, eng = _small_engine()
        answers = _wave_answers(eng)
    finally:
        obs.configure()
    assert answers == traced_answers
    host = HostEngine(store)
    assert answers[6:] == [host.q1_get(["/alpha/page1", "/beta", "/nope"]),
                           host.q2_ls(["/alpha", "/"]),
                           host.q3_navigate(["/beta/page2", "/alpha/zz"]),
                           host.q4_search(["/alpha", "beta/page"], limit=2),
                           host.q4_contains(["page1", "Beta", "gamma"], limit=5)]


def test_enabled_spans_enter_trace_annotations(traced, monkeypatch):
    import jax.profiler

    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    assert [x for x in log if x[1] != "gc.collect"] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]
    _, eng = _small_engine()
    log.clear()
    eng.q1_get(["/alpha/page0"])
    stages = [(k, n) for n in ("device.prep", "device.wait", "device.map")
              for k in ("enter", "exit")]
    assert [x for x in log if x[1] != "gc.collect"] == [
        ("enter", "device.q1_get"), *stages, ("exit", "device.q1_get")]


def test_collector_pass_is_a_span_and_configure_removes_the_hook():
    import gc

    reg = obs.configure(enabled=True)
    try:
        assert _gc_hooks() == [reg._on_gc]
        gc.collect()
        passes = [e for e in reg.ring if e["name"] == "gc.collect"]
        assert passes and passes[-1]["args"]["generation"] == 2
        assert passes[-1]["dur"] > 0
        assert reg.histograms["gc.collect"].n == len(passes)
        again = obs.configure(enabled=True)
        assert _gc_hooks() == [again._on_gc]
        off = obs.configure(enabled=False)
        assert _gc_hooks() == []
        gc.collect()
        assert len(off.ring) == 0
    finally:
        obs.configure()


def _children(events, parent):
    lo, hi = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e is not parent and e["tid"] == parent["tid"]
            and e["name"] != "gc.collect" and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]


def test_device_reads_run_in_three_stage_spans_with_transfer_tags(traced):
    _, eng = _small_engine()
    eng.q1_get(["/alpha/page1", "/beta", "/nope"])       # 3 keys → bucket 8
    eng.q4_search(["/alpha", "beta/page"], limit=2)      # 2 prefixes → bucket 4
    eng.q3_navigate(["/beta/page2"])
    eng.q2_ls(["/alpha"])
    eng.q4_contains(["page1"])
    events = list(traced.ring)
    st = eng.epoch_view()
    n_rows, width = st.ptoks.shape
    for op in ("q1_get", "q2_ls", "q3_navigate", "q4_search", "q4_contains"):
        (call,) = [e for e in events if e["name"] == f"device.{op}"]
        kids = _children(events, call)
        assert [k["name"] for k in kids] == ["device.prep", "device.wait", "device.map"]
        assert all(k["args"]["op"] == op for k in kids)
        prep, wait, _ = kids
        assert prep["ts"] + prep["dur"] <= wait["ts"]
        assert prep["args"]["h2d_bytes"] > 0 and wait["args"]["d2h_bytes"] > 0
    q1 = {e["name"]: e["args"] for e in events
          if e["args"].get("op") == "q1_get"}
    assert q1["device.prep"]["h2d_bytes"] == 2 * 8 * 4     # hi and lo words
    assert q1["device.wait"]["d2h_bytes"] == 8 * 4         # int32 rows
    q4 = {e["name"]: e["args"] for e in events
          if e["args"].get("op") == "q4_search"}
    assert q4["device.prep"]["h2d_bytes"] == 4 * width + 4 * 4
    assert q4["device.wait"]["d2h_bytes"] == 4 * n_rows    # the bool bitmap


def test_refresh_upload_span_counts_the_copied_bytes(traced):
    _, eng = _small_engine()
    eng.admit_many([("/alpha/page7", R.FileRecord(name="page7", text="new"))])
    eng.refresh()
    events = list(traced.ring)
    (apply,) = [e for e in events if e["name"] == "device.refresh.apply"]
    assert apply["args"]["kind"] == "patch"
    (upload,) = [e for e in _children(events, apply)
                 if e["name"] == "device.refresh.upload"]
    st = eng.epoch_view()
    keys = st.khi.nbytes + st.klo.nbytes + sum(p.nbytes for p in st.pinned)
    row = 4 + st.ptoks.shape[1]                           # row id + token row
    moved = upload["args"]["h2d_bytes"] - keys
    assert moved > 0 and moved % row == 0
