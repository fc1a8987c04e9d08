"""chip_smoke.py's phases at a small size on the CPU.

The script itself runs only on a TPU; its phases are functions, so the
same store comparison (DeviceEngine vs HostEngine before and after a
patch refresh), write-wave check and serving loop run here on a ~4k-path
wiki with a reduced LM.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

_SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def wiki(cs):
    return cs.build_wiki(4096, seed=0, fanout=16)


@pytest.fixture(scope="module")
def stored(cs, wiki):
    return cs.store_phase(wiki, seed=0)


def test_build_wiki_shape(cs):
    wiki = cs.build_wiki(100, seed=3, fanout=4)
    # ceil(100 / 16) dimensions, each with 4 topics of 4 pages
    assert len(wiki.dims) == 7 and len(wiki.topics) == 7 * 4
    assert len(wiki.pages) == 7 * 16
    assert wiki.n_paths == 1 + 7 + 28 + 112
    assert wiki.store.count() == wiki.n_paths
    # every segment below the root carries vocabulary words
    page = wiki.pages[45].rsplit("/", 1)[1]
    assert page.split("_")[0] in wiki.vocab


def test_store_phase_device_matches_host(cs, stored):
    _, report = stored
    assert cs.check_store(report) == []
    for when in ("mismatches_before", "mismatches_after"):
        assert set(report[when]) == set(cs.READ_OPS)
        assert not any(report[when].values())
    assert report["writes"] == 64 + 16 + 16
    assert report["failed_writes"] == 0
    assert report["writes_visible"] == report["writes"]
    assert report["refresh_kind"] == "patch"
    json.dumps(report)


def test_read_batches_mix_hits_and_misses(cs, wiki, stored):
    import numpy as np
    device, _ = stored
    keys = cs.read_batches(wiki, np.random.default_rng(5))
    assert all(len(v) == cs.BATCH for v in keys.values())
    got = device.q1_get(keys["q1_get"])
    hits = sum(r is not None for r in got)
    assert 0 < hits < len(got)


def test_check_store_flags_failures(cs, stored):
    _, report = stored
    bad = dict(report, failed_writes=2, refresh_kind="rebuild",
               mismatches_after=dict(report["mismatches_after"], q2_ls=3))
    errors = cs.check_store(bad)
    assert len(errors) == 3


def test_serving_phase_reaches_device_engine(cs, wiki, stored):
    device, _ = stored
    cfg = get_config("wikikv-router").reduced(d_model=32, vocab=512,
                                              n_layers=2)
    report = cs.serving_phase(device, wiki, cfg, seed=0, max_len=64)
    assert cs.check_serving(report) == []
    assert report["served"] == 4 and report["engine_calls"] > 0
    dec = cs.decode_check(cfg, batch=4, max_len=64, seed=0)
    assert dec["within_tol"], dec


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    import jax
    from repro.launch import compile_cache as cc
    flags = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {f: getattr(jax.config, f) for f in flags}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cc.enable_compile_cache()
        if from_env:
            # JAX reads the variable itself; the code sets no directory
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == saved[flags[0]]
        else:
            assert got == str(_SCRIPT.parent / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        for f, v in saved.items():
            jax.config.update(f, v)


def test_entry_point_refuses_a_non_tpu_backend(cs, capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "not a TPU" in out.err
