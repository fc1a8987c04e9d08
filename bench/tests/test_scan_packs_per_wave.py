"""``scan_packs_per_wave`` on hand-made span rings, and on a tiny e-scan run
of the program on the CPU."""
from pathlib import Path

import pytest

import harness

ROOT = Path(__file__).resolve().parents[2]


def ev(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": 1,
            "args": args}


def read(spans, waves):
    run = harness.RunView(waves=[None] * waves, window_s=1.0, finished=[],
                          setup_s=0.0, spans=spans)
    mod = harness.load_module(ROOT / "bench" / "metrics" / "scan_packs_per_wave.py")
    return mod.read(run)


@pytest.mark.parametrize("packs,waves", [(1, 1), (3, 4), (29, 29)])
def test_reads_packs_over_waves(packs, waves):
    spans = []
    for i in range(packs):
        spans += [ev("device.q4_pack", 10 * i, 1, scan_packs=1),
                  ev("device.q4_search", 10 * i + 1, 8),
                  ev("device.wait", 10 * i + 2, 6, op="q4_search", d2h_bytes=8)]
    spans.append(ev("device.q4_search", 10 * packs, 9))
    assert read(spans, waves) == pytest.approx(packs / waves)


def test_silent_on_a_program_without_the_span():
    older = [ev("planner.flush", 0, 900, depth=3), ev("device.q4_search", 10, 500),
             ev("device.wait", 20, 400, op="q4_search", d2h_bytes=64)]
    assert read(older, 2) is None
    assert read([], 2) is None


def test_a_tiny_traced_e_scan_run_reads_about_one_pack_a_wave(monkeypatch):
    import roofline
    import test_rehearsal

    monkeypatch.setattr(roofline, "peaks", lambda kind: roofline.json.loads(
        (ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"])
    res = test_rehearsal.run("ycsb-paths-1m.e-scan", trace=True)
    assert res["correct"], res["examples"]
    assert 0 < res["metrics"]["scan_packs_per_wave"]["value"] <= 1.05
