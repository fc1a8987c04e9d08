"""Every cell's traffic through the harness's own functions on the CPU at a
tiny scale, against the plain reference; and the timed path broken in each
way a cell can break, which must make ``correct`` false."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import control
import harness
import roofline

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 31 + 77


def tiny(name: str) -> harness.Cell:
    cell = harness.Cell.load(name)
    cfg = cell.config
    if cfg["layout"] == "hierarchy":
        cfg["pages"] = 2 * cfg["topics_per_dimension"] * cfg["pages_per_topic"]
    else:
        cfg["recordcount"], cfg["groups"] = 4096, 64
    cell.traffic["clients"] = 32
    return cell


def run(name, hook=None, trace=False, seconds=1.0, log=lambda *a, **k: None):
    return harness.run_cell(tiny(name), SEED, seconds, trace, engine_hook=hook, log=log)


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct_at_tiny_scale(name):
    lines = []
    res = run(name, log=lambda line, **k: lines.append(json.loads(line)))
    assert [ln["compiles_in_window"] for ln in lines if "compiles_in_window" in ln] == [0]
    assert res["correct"], res["examples"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["tallies"]["window"]["reads"] > 0
    assert res["tallies"]["readback"]["reads"] > 0
    assert set(res["metrics"]) == {m["name"] for m in tiny(name).end_to_end}
    assert list(res)[-1] == "checks"


def test_settle_runs_traffic_until_no_request_started_before_it():
    class Loop:
        """Clients whose requests take 1, 2 and 3 waves."""
        def __init__(self):
            self.slots = [["s", None, 0.0, 0] for _ in range(3)]
            self.left = [1, 2, 3]
            self.waves = 0

        def wave(self, phase):
            self.waves += 1
            for c in range(3):
                self.left[c] -= 1
                if self.left[c] == 0:
                    self.slots[c] = ["s", None, harness.time.perf_counter(), 0]
                    self.left[c] = 99

    loop = Loop()
    harness.settle(loop)
    assert loop.waves == 3


# -- faults planted under the harness ---------------------------------------
READ_CALLS = ("q1_get", "q2_ls", "q3_navigate", "q4_search", "q4_contains")


def _wrap(engine, names, make):
    for name in names:
        setattr(engine, name, make(getattr(engine, name)))
    return engine


def stale(engine):
    """A refresh that returns with its state unchanged."""
    engine.refresh = lambda force=False: engine.epoch
    return engine


def half_batch(engine):
    """Reads that answer the first half of each batch and leave the rest out."""
    def make(fn):
        def call(keys, *a, **k):
            out = fn(keys, *a, **k)
            half = (len(out) + 1) // 2
            return out[:half] + [type(x)() if isinstance(x, list) else None
                                 for x in out[half:]]
        return call
    return _wrap(engine, READ_CALLS, make)


def _altered(x):
    if isinstance(x, list):
        return x[:-1] if x else ["/altered"]
    if isinstance(x, tuple):
        return (_altered(x[0]), x[1])
    if x is None:
        return None
    return dataclasses.replace(x, name=x.name + "~")


def altered_answer(engine):
    """Each read batch's first non-empty answer altered where it is made."""
    def make(fn):
        def call(keys, *a, **k):
            out = list(fn(keys, *a, **k))
            for i, x in enumerate(out):
                if x:
                    out[i] = _altered(x)
                    break
            return out
        return call
    return _wrap(engine, READ_CALLS, make)


def half_writes(engine):
    """Write batches that apply their first half and acknowledge all."""
    def make(fn):
        def call(items, *a, **k):
            half = (len(items) + 1) // 2
            done = fn(items[:half], *a, **k)
            skipped = [it[1] if isinstance(it, tuple) and not callable(it[1]) else True
                       for it in items[half:]]
            if fn.__name__ == "update_many":
                skipped = [done[0]] * (len(items) - half) if done else skipped
            return list(done) + skipped
        return call
    return _wrap(engine, ("admit_many", "update_many", "unlink_many"), make)


FAULTS = {"stale": stale, "half_batch": half_batch,
          "altered_answer": altered_answer, "half_writes": half_writes}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault):
    res = run(name, hook=FAULTS[fault])
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails_where_the_sound_run_passes(name):
    """Refresh cadence 2 (the program's own deferral, Δ = 2) against Δ = 1."""
    res = run(name, hook=control.deferred_refresh)
    assert not res["correct"]
    assert res["checks"]["read_mismatches"]["value"] > 0


def test_traced_run_reads_spans_and_reports_a_breakdown(monkeypatch):
    """On the CPU the trace holds no device, so the device metrics stay
    silent; the span and counter metrics are read."""
    monkeypatch.setattr(roofline, "peaks", lambda kind: roofline.json.loads(
        (ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"])
    res = run("kbnav-1m.sessions", trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert {"session_waves", "planner_flush_ms", "refresh_ms"} <= set(m)
    assert "lookup_roofline" not in m and "device_idle_share" not in m
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/harness.py", "--workload", CELLS[0],
                           "--seed", "5", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_backend_that_is_not_a_tpu():
    p = _cli(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
