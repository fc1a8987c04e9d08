"""Trace reduction, work counts and order statistics, on a trace recorded on
a TPU v5 lite and on hand-worked shapes."""
from pathlib import Path

import pytest

import percentile
import roofline
import xplane

TRACE = Path(__file__).resolve().parent / "data" / "v5e-lookup-scan.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xplane.load(str(TRACE))


def test_recorded_trace_has_one_device_and_the_annotations(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    names = [n for *_, n in trace.annotations]
    assert names.count("bench.q1_get") == 3
    assert names.count("bench.q4_search") == 3
    assert names.count("bench.q4_contains") == 3
    programs = {ev[2] for ev in trace.devices["/device:TPU:0"]}
    assert programs == {"jit_path_lookup", "jit_prefix_search"}


def test_clock_offset_lies_in_every_executions_band(trace):
    """Recorded: run 4 enqueued at 47,603.266 us and completed at
    48,147.856 us on the host for a device execution at 46,333.101-46,346.957
    us, which bounds the offset to [1,270.2, 1,800.9] us; all nine
    executions together bound it to [1,270.2, 1,567.5] us."""
    assert 1_270_165 <= trace.offset_ns <= 1_567_545
    # shifted, every execution starts after the host enqueued it
    for s, e, _, enq in trace.devices["/device:TPU:0"]:
        assert enq is not None and s >= enq


def test_busy_is_the_sum_of_disjoint_programs(trace):
    evs = trace.devices["/device:TPU:0"]
    lo, hi = min(ev[0] for ev in evs), max(ev[1] for ev in evs)
    assert xplane.busy(trace, lo, hi) == pytest.approx(sum(e - s for s, e, *_ in evs))
    assert xplane.busy(trace, lo, lo) == 0


def test_device_time_goes_to_the_enclosing_annotation(trace):
    """Every program of the recording ran inside the annotation of the
    engine call that launched it: the scans inside ``bench.q4_search``,
    the lookups inside ``bench.q1_get`` and ``bench.q4_contains``."""
    evs = trace.devices["/device:TPU:0"]
    lo, hi = 0.0, float("inf")
    scans = sum(e - s for s, e, n, _ in evs if n == "jit_prefix_search")
    lookups = sum(e - s for s, e, n, _ in evs if n == "jit_path_lookup")
    assert xplane.busy_inside(trace, {"bench.q4_search"}, lo, hi) == pytest.approx(scans)
    both = xplane.busy_inside(trace, {"bench.q1_get", "bench.q4_contains"}, lo, hi)
    assert both == pytest.approx(lookups)
    host = xplane.annotated(trace, {"bench.q1_get"}, lo, hi)
    assert host > xplane.busy_inside(trace, {"bench.q1_get"}, lo, hi) > 0


def test_idle_attribution_adds_up(trace):
    evs = trace.devices["/device:TPU:0"]
    lo = min(s for s, _, _ in trace.annotations)
    hi = max(e for _, e, _ in trace.annotations)
    idle = sum(s for _, s in xplane.idle_by_host(trace, lo, hi, n=100))
    assert idle == pytest.approx((hi - lo - xplane.busy(trace, lo, hi)) / 1e9)
    top = xplane.top_programs(trace, lo, hi)
    assert sum(s for _, s in top) == pytest.approx(
        sum(e - s for s, e, *_ in evs) / 1e9)


def test_flatten_gives_the_innermost_annotation():
    segs = xplane.flatten([(0, 100, "wave"), (0, 30, "clients"), (30, 80, "flush"),
                           (40, 50, "q1"), (80, 100, "refresh")])
    assert segs == [(0, 30, "clients"), (30, 40, "flush"), (40, 50, "q1"),
                    (50, 80, "flush"), (80, 100, "refresh")]


def test_merge_and_clip_by_hand():
    assert xplane.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def test_idle_by_host_on_a_hand_made_trace():
    t = xplane.Trace(devices={"/device:TPU:0": [(10, 20, "p", None), (60, 70, "p", None)]},
                     annotations=[(0, 100, "bench.wave"), (0, 40, "bench.clients"),
                                  (40, 100, "bench.flush")])
    assert dict(xplane.idle_by_host(t, 0, 100)) == pytest.approx(
        {"clients": 30e-9, "flush": 50e-9})
    assert xplane.busy(t, 0, 100) == 20


def test_lookup_work_by_hand():
    ops, nbytes = roofline.lookup_work(q=256, n=1 << 20)
    assert ops == 256 * 20
    assert nbytes == 256 * (8 + 4 + 128 * 8) + (1 << 20) // 128 * 8
    assert roofline.lookup_work(0, 100) == (0.0, 0.0)


def test_scan_work_by_hand():
    ops, nbytes = roofline.scan_work([8, 12], [40, 0], n=1024, mean_path_bytes=30.0)
    assert nbytes == (8 + 10 * 30 + 40) + (12 + 10 * 30 + 0)
    assert ops == (10 * 2 + 10) + (10 * 3 + 0)


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    t = roofline.least_seconds(1e6, 819e9, p)
    assert t == pytest.approx(1.0)


def test_nearest_rank_by_hand():
    xs = list(range(1, 101))
    assert percentile.nearest_rank(xs, 95) == 95
    assert percentile.nearest_rank([3, 1, 2], 50) == 2
    assert percentile.nearest_rank([7], 95) == 7
    assert percentile.nearest_rank([], 95) is None


def test_quartile_spread_by_hand():
    assert percentile.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
