"""QueryEngine layer: host/device parity, sharding transparency, planner
batching (ISSUE 1 acceptance: every Q1–Q4 op through one engine; batched
navigation ≡ unbatched navigation with strictly fewer round trips)."""
import random

import pytest

from repro.core import paths as P
from repro.core import records as R
from repro.core.consistency import WikiWriter
from repro.core.engine import (BatchPlanner, DeviceEngine, HostEngine,
                               ShardedPathStore)
from repro.core.navigate import Navigator, UnitBudget
from repro.core.oracle import HeuristicOracle
from repro.core.store import MemKV, PathStore


# ---------------------------------------------------------------------------
# randomized wiki construction through the §IV-C write protocol
# ---------------------------------------------------------------------------
def _random_wiki(store, seed: int) -> dict:
    """Admit a random tree (protocol-respecting), leave some orphans via
    partial admissions, unlink some nodes.  Returns query material."""
    rng = random.Random(seed)
    w = WikiWriter(store, clock=lambda: 0.0)  # deterministic meta timestamps
    w.ensure_root("root")
    dims = [f"d{i}" for i in range(rng.randint(2, 4))]
    live, orphans = [], []
    for d in dims:
        w.admit(f"/{d}", R.DirRecord(name=d, summary=f"dim {d}"))
        for e in range(rng.randint(1, 5)):
            path = f"/{d}/ent_{e}_{rng.randint(0, 9)}"
            as_dir = rng.random() < 0.3
            rec = (R.DirRecord(name=P.basename(path), summary=f"sub of {d}")
                   if as_dir else
                   R.FileRecord(name=P.basename(path),
                                text=f"text {d} {e} {rng.random():.3f}"))
            if rng.random() < 0.15:
                # orphan: child written, parent update never happens
                steps = w.admit_steps(path, rec)
                next(steps)
                orphans.append(path)
            else:
                w.admit(path, rec)
                live.append(path)
                if as_dir:
                    sub = path + f"/sub{rng.randint(0, 3)}"
                    w.admit(sub, R.FileRecord(name=P.basename(sub),
                                              text=f"sub {sub}"))
                    live.append(sub)
    # a few deletions (reverse-order unlink keeps the store consistent)
    for path in rng.sample(live, min(2, len(live))):
        w.unlink(path)
        live.remove(path)
    missing = [f"/{d}/nope_{i}" for i, d in enumerate(dims)] + ["/zz/yy"]
    return {"rng": rng, "dims": dims, "live": live, "orphans": orphans,
            "missing": missing}


def _query_batches(mat):
    rng = mat["rng"]
    pool = mat["live"] + mat["orphans"] + mat["missing"] + ["/"]
    q1 = [rng.choice(pool) for _ in range(24)]
    q2 = ["/"] + [P.SEP + d for d in mat["dims"]] + q1[:8]
    q3 = [rng.choice(pool) for _ in range(8)]
    prefixes = ["/", P.SEP + mat["dims"][0], "/zz",
                rng.choice(pool), mat["dims"][-1]]  # last: no leading slash
    tokens = ["ent", "sub", "nothere", mat["dims"][0],
              P.basename(rng.choice(mat["live"] or ["/x"]))]
    return q1, q2, q3, prefixes, tokens


@pytest.mark.parametrize("seed", range(5))
def test_host_device_parity_randomized(seed):
    """Property: HostEngine and DeviceEngine frozen from the same store
    agree on every Q1–Q4 batch — hits, misses, orphans, deletions."""
    store = ShardedPathStore(n_shards=3, memtable_limit=64)
    mat = _random_wiki(store, seed)
    host = HostEngine(store)
    dev = DeviceEngine.from_store(store)
    q1, q2, q3, prefixes, tokens = _query_batches(mat)

    assert host.q1_get(q1) == dev.q1_get(q1)
    assert host.q2_ls(q2) == dev.q2_ls(q2)
    assert host.q3_navigate(q3) == dev.q3_navigate(q3)
    assert host.q4_search(prefixes) == dev.q4_search(prefixes)
    assert host.q4_search(prefixes, limit=3) == dev.q4_search(prefixes, limit=3)
    assert host.q4_contains(tokens) == dev.q4_contains(tokens)
    assert host.q4_contains(tokens, limit=2) == dev.q4_contains(tokens, limit=2)
    # each batch was one engine call on both sides
    assert host.stats.calls == dev.stats.calls
    assert host.stats.max_batch["q1_get"] == len(q1)


@pytest.mark.parametrize("seed", [0, 3])
def test_sharding_is_transparent(seed):
    """Digest-range sharding changes data placement, never results."""
    plain = PathStore(MemKV())
    sharded = ShardedPathStore(n_shards=4, memtable_limit=32)
    mat_p = _random_wiki(plain, seed)
    _random_wiki(sharded, seed)
    q1, q2, q3, prefixes, tokens = _query_batches(mat_p)
    he_p, he_s = HostEngine(plain), HostEngine(sharded)
    assert he_p.q1_get(q1) == he_s.q1_get(q1)
    assert he_p.q2_ls(q2) == he_s.q2_ls(q2)
    assert he_p.q3_navigate(q3) == he_s.q3_navigate(q3)
    assert he_p.q4_search(prefixes) == he_s.q4_search(prefixes)
    assert he_p.q4_contains(tokens) == he_s.q4_contains(tokens)
    assert plain.all_paths() == sharded.all_paths()
    # the namespace really is spread: no shard holds everything
    per_shard = [s.count() for s in sharded.shards]
    assert sum(per_shard) == sharded.count()
    assert max(per_shard) < sharded.count()


def test_q4_long_prefix_parity():
    """Prefixes at/over the packed path width (96 B) can't be decided by
    the kernel's truncated token matrix — the device engine must resolve
    them exactly from the host-side path list."""
    store = PathStore(MemKV())
    w = WikiWriter(store, clock=lambda: 0.0)
    w.ensure_root()
    seg = "s" * 60
    w.admit(f"/{seg}", R.DirRecord(name=seg))
    w.admit(f"/{seg}/{seg}", R.DirRecord(name=seg))
    w.admit(f"/{seg}/{seg}/leaf_a", R.FileRecord(name="leaf_a", text="a"))
    w.admit(f"/{seg}/{seg}/leaf_b", R.FileRecord(name="leaf_b", text="b"))
    host, dev = HostEngine(store), DeviceEngine.from_store(store)
    probes = [f"/{seg}/{seg}",            # 122 B — over the packed width
              f"/{seg}/{seg}/leaf_a",     # exact long path
              f"/{seg}", "/"]
    assert host.q4_search(probes) == dev.q4_search(probes)
    assert host.q4_search(probes, limit=1) == dev.q4_search(probes, limit=1)


def test_planner_dedups_and_batches():
    store = ShardedPathStore(n_shards=2)
    _random_wiki(store, 1)
    eng = HostEngine(store)
    pl = BatchPlanner(eng)
    f1 = pl.get("/d0")
    f2 = pl.get("/d0")            # deduplicated into one batch slot
    f3 = pl.ls("/")
    f4 = pl.search("/d0", limit=4)
    f5 = pl.contains("ent", limit=8)
    assert not f1.done
    resolved = pl.flush()
    assert resolved == 5
    assert f1.done and f1.value == f2.value
    assert f3.value is not None
    assert isinstance(f4.value, list) and isinstance(f5.value, list)
    # one engine call per operator kind, not per op
    assert eng.stats.total_calls() == 4
    assert eng.stats.ops["q1_get"] == 1  # deduped
    # a second flush with nothing pending is free
    assert pl.flush() == 0


def _nav_outputs(pairs):
    return [([(r.kind, r.path, r.text) for r in results],
             (t.tool_calls, t.llm_calls, t.pages_read, t.route,
              t.budget_exhausted, t.accessed))
            for results, t in pairs]


def test_batched_navigation_matches_unbatched(built_wiki):
    """Multi-session run ≡ per-query runs, with strictly fewer engine
    round trips (the planner's whole point)."""
    pipe, questions = built_wiki
    qs = [q.text for q in questions[:10]]

    solo_nav = Navigator(pipe.store, HeuristicOracle())
    solo = [solo_nav.nav(q, UnitBudget(400)) for q in qs]

    many_nav = Navigator(pipe.store, HeuristicOracle())
    many = many_nav.nav_many(qs, [UnitBudget(400) for _ in qs])

    assert _nav_outputs(solo) == _nav_outputs(many)
    assert many_nav.engine.stats.total_calls() < solo_nav.engine.stats.total_calls()
    # sessions actually shared batches: some engine call served many ops
    assert max(many_nav.engine.stats.max_batch.values()) > 1


def test_batched_navigation_device_engine(built_wiki):
    """The same multi-session run against the DeviceEngine (Pallas path
    off-TPU = jnp reference) returns identical navigation results."""
    pipe, questions = built_wiki
    qs = [q.text for q in questions[:6]]
    solo = [Navigator(pipe.store, HeuristicOracle()).nav(q, UnitBudget(400))
            for q in qs]
    dev = DeviceEngine.from_store(pipe.store)
    many = Navigator(dev, HeuristicOracle()).nav_many(
        qs, [UnitBudget(400) for _ in qs])
    assert _nav_outputs(solo) == _nav_outputs(many)
    assert dev.stats.total_calls() > 0


# ---------------------------------------------------------------------------
# ISSUE 2: online write path — batched admissions, epoch-pinned reads,
# incremental DeviceEngine refresh (Δ = 1 wave)
# ---------------------------------------------------------------------------
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.consistency import CASConflict, InvalidationBus  # noqa: E402
from repro.core.engine import admit_wave, unlink_wave  # noqa: E402
from repro.core.navigate import UnitBudget as _UB  # noqa: E402,F401


def _seed_store(n_dims=2, n_leaves=3):
    store = PathStore(MemKV())
    w = WikiWriter(store, clock=lambda: 0.0)
    w.ensure_root("root")
    for d in range(n_dims):
        w.admit(f"/d{d}", R.DirRecord(name=f"d{d}", summary=f"dim {d}"))
        for e in range(n_leaves):
            w.admit(f"/d{d}/e{e}", R.FileRecord(name=f"e{e}", text=f"{d}:{e}"))
    return store


def _engine_pair():
    store = _seed_store()
    host = HostEngine(PathStore(MemKV()))
    # host over its own copy of the same logical state
    for p in store.all_paths():
        host.store.put_record(p, store.get(p))
    dev = DeviceEngine.from_store(store)
    return host, dev


@pytest.mark.parametrize("make", ["host", "device"])
def test_write_ops_are_batched_round_trips(make):
    store = _seed_store()
    eng = (HostEngine(store) if make == "host"
           else DeviceEngine.from_store(store))
    pl = BatchPlanner(eng)
    futs = admit_wave(pl, [(f"/d0/new{i}", R.FileRecord(name=f"new{i}",
                                                        text=str(i)))
                           for i in range(8)])
    futs += unlink_wave(pl, ["/d1/e0"])
    pl.flush()
    assert all(f.done for f in futs)
    # ONE admit round trip for 8 admissions, one unlink round trip
    assert eng.stats.calls["w_admit"] == 1
    assert eng.stats.ops["w_admit"] == 8
    assert eng.stats.served["w_admit"] == 8
    assert eng.stats.calls["w_unlink"] == 1
    eng.refresh()
    assert eng.q1_get(["/d0/new3"])[0].text == "3"
    assert eng.q1_get(["/d1/e0"]) == [None]


def test_device_epoch_pinning_and_delta_refresh():
    """A wave's reads execute against the epoch pinned at wave start —
    same-wave writes are invisible; refresh() commits exactly one epoch
    (Δ = 1 wave) via an incremental TensorDelta, no full re-freeze."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    pl = BatchPlanner(dev)
    pinned = dev.epoch
    r_before = pl.get("/d0/w0")
    pl.admit("/d0/w0", R.FileRecord(name="w0", text="wave-write"))
    # read enqueued AFTER the write still sees the pinned epoch
    r_after = pl.ls("/d0")
    pl.flush()
    assert r_before.value is None                       # not yet visible
    assert "/d0/w0" not in (r_after.value[1] if r_after.value else [])
    assert dev.epoch == pinned                          # mid-wave: unchanged
    assert dev.refresh() == pinned + 1                  # Δ = 1 wave
    assert dev.q1_get(["/d0/w0"])[0].text == "wave-write"
    assert "/d0/w0" in dev.q2_ls(["/d0"])[0][1]
    # the refresh was a delta, and it carried the child + its parent row
    (delta,) = dev.delta_log
    assert delta.epoch == pinned + 1
    assert {"/d0/w0", "/d0"} <= {p for p, _ in delta.upserts}
    # a clean refresh is a no-op
    assert dev.refresh() == pinned + 1


def test_incremental_refresh_matches_full_refreeze():
    """After an arbitrary admit/update/unlink mix, the delta-refreshed
    engine answers every Q1–Q4 batch identically to a fresh freeze."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    pl = BatchPlanner(dev)
    pl.admit("/d0/sub", R.DirRecord(name="sub"))
    pl.admit("/d0/sub/leaf", R.FileRecord(name="leaf", text="deep"))
    pl.admit("/d2/fresh_dim", R.FileRecord(name="fresh_dim", text="x"))
    pl.update("/d0/e0", lambda r: R.FileRecord(name=r.name,
                                               text="rewritten", meta=r.meta))
    pl.unlink("/d1/e1")
    pl.flush()
    dev.refresh()
    fresh = DeviceEngine.from_store(store)
    paths = store.all_paths() + ["/d1/e1", "/nope"]
    assert dev.q1_get(paths) == fresh.q1_get(paths)
    assert dev.q2_ls(paths) == fresh.q2_ls(paths)
    assert dev.q3_navigate(paths) == fresh.q3_navigate(paths)
    assert dev.q4_search(["/", "/d0", "/d2"]) == fresh.q4_search(
        ["/", "/d0", "/d2"])
    assert dev.q4_contains(["leaf", "sub", "e1", "fresh"]) == fresh.q4_contains(
        ["leaf", "sub", "e1", "fresh"])


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["admit", "unlink"]),
                          st.integers(0, 1), st.integers(0, 5)),
                min_size=1, max_size=12))
def test_interleaved_write_read_waves_never_partial(wave_writes):
    """Property (the acceptance invariant): interleaved admissions/unlinks
    and navigation waves never observe a partial subtree — every read
    wave sees EXACTLY the epoch it pinned, which equals the shadow model
    of the store as of the previous refresh."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    pl = BatchPlanner(dev)
    # shadow model of the pinned epoch: logical path -> text/None
    def snapshot():
        return {p: store.get(p) for p in store.all_paths()}
    pinned_model = snapshot()
    for i, (kind, d, e) in enumerate(wave_writes):
        path = f"/d{d}/p{e}"
        # enqueue this wave's reads: full q1 sweep + every dir listing
        probe = sorted(set(pinned_model) | {path})
        f_get = [pl.get(p) for p in probe]
        f_ls = [pl.ls(p) for p in probe]
        # enqueue this wave's write
        if kind == "admit":
            pl.admit(path, R.FileRecord(name=f"p{e}", text=f"w{i}"))
        else:
            pl.unlink(path)
        pl.flush()
        # 1) exact-epoch reads: every get matches the pinned model
        for p, f in zip(probe, f_get):
            assert f.value == pinned_model.get(p)
        # 2) no partial subtree: every advertised child resolves in the
        #    same pinned epoch (skip-on-miss never needed on device)
        for p, f in zip(probe, f_ls):
            if f.value is None:
                continue
            _, children = f.value
            for cp in children:
                assert pinned_model.get(cp) is not None
        new_epoch = dev.refresh()
        assert new_epoch == dev.epoch
        pinned_model = snapshot()       # Δ = 1 wave: next wave sees all
    # convergence: final engine state == fresh freeze of the store
    fresh = DeviceEngine.from_store(store)
    paths = store.all_paths()
    assert dev.q1_get(paths) == fresh.q1_get(paths)


def test_partial_read_property_host_engine():
    """Host side of the acceptance invariant: ls + child gets issued in
    ONE wave never observe an advertised-but-missing child, even with
    admissions and unlinks riding the same wave."""
    store = _seed_store()
    host = HostEngine(store)
    pl = BatchPlanner(host)
    for wave in range(6):
        f_ls = pl.ls("/d0")
        # child gets for everything advertised as of the last wave
        known = host.q2_ls(["/d0"])[0][1]
        f_get = [pl.get(c) for c in known]
        pl.admit(f"/d0/w{wave}", R.FileRecord(name=f"w{wave}", text="x"))
        if wave >= 2:
            pl.unlink(f"/d0/w{wave - 2}")
        pl.flush()
        host.refresh()
        rec, children = f_ls.value
        got = dict(zip(known, [f.value for f in f_get]))
        for cp in children:
            if cp in got:               # advertised AND probed this wave
                assert got[cp] is not None
    assert host.epoch > 0


def test_unlink_under_navigation_device(built_wiki):
    """Navigation sessions keep returning consistent (pinned-epoch)
    results while records are unlinked between waves; no session ever
    reads a half-removed subtree."""
    pipe, questions = built_wiki
    # private copy — built_wiki is session-scoped
    store = PathStore(MemKV())
    for p in pipe.store.all_paths():
        store.put_record(p, pipe.store.get(p))
    dev = DeviceEngine.from_store(store)
    nav = Navigator(dev, HeuristicOracle())
    qs = [q.text for q in questions[:6]]
    victims = [p for p in store.all_paths()
               if P.depth(p) >= 2][:6]
    for wave in range(3):
        for v in victims[wave * 2:(wave + 1) * 2]:
            nav.planner.unlink(v)
        outs = nav.nav_many(qs, [UnitBudget(400) for _ in qs])
        for results, trace in outs:
            # every emitted result was readable in the pinned epoch
            assert all(r.text is not None for r in results)
        # session scheduler refreshed at wave end: unlinks are now visible
        for v in victims[wave * 2:(wave + 1) * 2]:
            assert dev.q1_get([v]) == [None]
    fresh = DeviceEngine.from_store(store)
    paths = store.all_paths()
    assert dev.q1_get(paths) == fresh.q1_get(paths)


def test_cas_conflict_and_retry_through_engine():
    store = _seed_store()
    host = HostEngine(store)
    pl = BatchPlanner(host)

    real_get = store.get
    state = {"bumps": 1, "n": 0}

    def transient_stale_get(path):
        rec = real_get(path)
        if path == "/d0/e0" and state["bumps"] > 0 and isinstance(
                rec, R.FileRecord):
            state["bumps"] -= 1
            state["n"] += 1
            from dataclasses import replace
            # a version that moves on every read — the writer can never
            # observe the same version twice, as under a racing writer
            return replace(rec, meta=replace(rec.meta,
                                             version=100 + state["n"]))
        return rec

    # one transient stale read: the engine's CAS loop retries and wins
    store.get = transient_stale_get
    fut = pl.update("/d0/e0", lambda r: R.FileRecord(name=r.name,
                                                     text=r.text + "!",
                                                     meta=r.meta))
    pl.flush()
    assert isinstance(fut.value, R.FileRecord) and fut.value.text.endswith("!")

    # permanent conflict: resolves to the CASConflict, batch survives
    state["bumps"] = 10 ** 9
    f_bad = pl.update("/d0/e0", lambda r: r)
    f_good = pl.update("/d1/e0", lambda r: R.FileRecord(name=r.name,
                                                        text="fine",
                                                        meta=r.meta))
    pl.flush()
    store.get = real_get
    assert isinstance(f_bad.value, CASConflict)
    assert isinstance(f_good.value, R.FileRecord) and f_good.value.text == "fine"


def test_evolution_and_errorbook_flow_to_device():
    """Out-of-band writers sharing the engine's bus (evolution pass,
    errorbook repair) reach the tensor index at the next refresh."""
    from repro.core.errorbook import ErrorBook, detect_errors, deterministic_repair
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    w = dev.writer                      # the shared CAS/invalidation path
    w.put_record("/d0/e0", R.FileRecord(
        name="e0", text="see [[/missing/target]] here"))
    dev.refresh()
    assert "[[/missing/target]]" in dev.q1_get(["/d0/e0"])[0].text
    book = ErrorBook()
    report = detect_errors(store, book)
    assert report.found.get("dangling_wikilink")
    deterministic_repair(w, book, report)
    dev.refresh()
    assert "[[" not in dev.q1_get(["/d0/e0"])[0].text   # repair is visible
    fresh = DeviceEngine.from_store(store)
    paths = store.all_paths()
    assert dev.q1_get(paths) == fresh.q1_get(paths)


def test_cross_kind_write_order_preserved():
    """unlink-then-readmit of one path in one wave must leave the new
    record alive: the planner batches writes as same-kind RUNS in enqueue
    order, never admissions-then-unlinks wholesale."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    pl = BatchPlanner(dev)
    f_u = pl.unlink("/d0/e0")
    f_a = pl.admit("/d0/e0", R.FileRecord(name="e0", text="reborn"))
    pl.flush()
    dev.refresh()
    assert f_u.value is True
    assert f_a.done
    assert store.get("/d0/e0").text == "reborn"
    assert dev.q1_get(["/d0/e0"])[0].text == "reborn"
    # and the engine saw two unlink-run/admit-run round trips, in order
    assert dev.stats.calls["w_unlink"] == 1
    assert dev.stats.calls["w_admit"] == 1


def test_unlink_everything_root_survives():
    """Unlinking the whole namespace in one wave: every non-root unlink
    lands, the root unlink resolves to a PathError (no parent to unlink
    from) instead of poisoning the batch, and the refreshed table still
    holds the root — never an empty (unrepresentable) TensorWiki."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    pl = BatchPlanner(dev)
    futs = {p: pl.unlink(p) for p in store.all_paths()}
    pl.flush()
    dev.refresh()
    assert isinstance(futs["/"].value, P.PathError)
    assert all(v.value is True for p, v in futs.items() if p != "/")
    assert dev.wiki.paths == ["/"]
    assert store.all_paths() == ["/"]


def test_apply_delta_refuses_to_empty_the_table():
    from repro.core import tensorstore as TS
    store = _seed_store()
    wiki, recs = TS.freeze_with_records(store)
    delta = TS.TensorDelta(epoch=1, unlinks=list(wiki.paths))
    with pytest.raises(ValueError, match="empty table"):
        TS.apply_delta(wiki, recs, delta)


# ---------------------------------------------------------------------------
# ISSUE 3: durable tier under the engines — epoch-consistent restart
# ---------------------------------------------------------------------------
def test_durable_restart_loses_at_most_uncommitted_wave(tmp_path):
    """Acceptance: recovery after a simulated mid-wave crash loses at
    most the uncommitted wave — the Δ = 1-wave staleness invariant holds
    across restart.  Committed waves are exact; the engine resumes the
    committed epoch sequence."""
    from repro.storage import open_durable_store
    root = str(tmp_path / "wiki")
    store = open_durable_store(root, n_shards=2, sync="none")
    host = HostEngine(store)
    pl = BatchPlanner(host)
    committed: dict[str, str] = {}
    pl.admit("/d0", R.DirRecord(name="d0"))
    for wave in range(3):
        for i in range(2):
            path = f"/d0/w{wave}_{i}"
            pl.admit(path, R.FileRecord(name=P.basename(path),
                                        text=f"{wave}:{i}"))
            committed[path] = f"{wave}:{i}"
        pl.flush()
        host.refresh()                     # wave boundary = WAL commit
    committed_epoch = host.epoch
    # mid-wave crash: writes executed (live view sees them) but refresh —
    # the group commit — never runs
    pl.admit("/d0/lost", R.FileRecord(name="lost", text="x"))
    pl.flush()
    assert store.get("/d0/lost") is not None
    del pl, host, store                    # crash: no close(), no commit

    reopened = open_durable_store(root, sync="none")
    host2 = HostEngine(reopened)
    assert host2.epoch == committed_epoch  # epoch rehydrated, not reset
    assert reopened.get("/d0/lost") is None
    for path, text in committed.items():
        assert reopened.get(path).text == text
    # the next wave continues the epoch sequence exactly one ahead
    pl2 = BatchPlanner(host2)
    pl2.admit("/d0/after", R.FileRecord(name="after", text="y"))
    pl2.flush()
    assert host2.refresh() == committed_epoch + 1
    reopened.close()


def test_durable_device_rehydration_epoch_consistent(tmp_path):
    """DeviceEngine over a reopened durable store: the committed-but-
    never-device-applied dirty paths journaled in the WAL surface as the
    rehydration work list, the restored epoch matches the store's last
    commit, and the rehydrated engine answers every Q1–Q4 batch
    identically to the host over the same reopened state."""
    from repro.storage import open_durable_store
    root = str(tmp_path / "wiki")
    store = open_durable_store(root, sync="none")
    store.put_record("/", R.DirRecord(name=""))
    store.flush()
    # the real mirror topology: the device engine attaches the WAL
    # journal (only a device consumer may — its DEVMARKs clear it); the
    # host engine shares its writer/bus and commits write waves, but the
    # device mirror never refreshes before the crash
    dev = DeviceEngine.from_store(store)
    host = HostEngine(store, writer=dev.writer)
    pl = BatchPlanner(host)
    pl.admit("/d0", R.DirRecord(name="d0"))
    pl.admit("/d0/e0", R.FileRecord(name="e0", text="v0"))
    pl.flush()
    host.refresh()
    assert store.pending_invalidations()   # journaled, not device-applied
    del pl, host, dev, store               # crash

    reopened = open_durable_store(root, sync="none")
    pending_before = set(reopened.pending_invalidations())
    assert {"/d0", "/d0/e0"} <= pending_before
    dev = DeviceEngine.from_store(reopened)
    assert dev.epoch == 1
    assert set(dev.rehydrated_paths) == pending_before
    # the fresh freeze subsumed the pending deltas: journal now applied
    assert reopened.pending_invalidations() == []
    host2 = HostEngine(reopened)
    probe = reopened.all_paths() + ["/d0/ghost"]
    assert dev.q1_get(probe) == host2.q1_get(probe)
    assert dev.q4_search(["/", "/d0"]) == host2.q4_search(["/", "/d0"])
    # Δ = 1 wave still holds post-restart: same-wave writes invisible,
    # visible after exactly one refresh, and the DEVMARK makes the next
    # reopen rehydrate nothing
    pl2 = BatchPlanner(dev)
    pl2.admit("/d0/e1", R.FileRecord(name="e1", text="v1"))
    f_read = pl2.get("/d0/e1")
    pl2.flush()
    assert f_read.value is None
    assert dev.refresh() == 2
    assert dev.q1_get(["/d0/e1"])[0].text == "v1"
    reopened.close()
    again = open_durable_store(root, sync="none")
    dev2 = DeviceEngine.from_store(again)
    assert dev2.epoch == 2 and dev2.rehydrated_paths == []
    assert dev2.q1_get(["/d0/e1"])[0].text == "v1"
    again.close()


def test_per_item_write_failures_never_poison_the_wave():
    """Invalid writes resolve their own futures to the exception; every
    other write in the wave lands and every future resolves."""
    store = _seed_store()
    host = HostEngine(store)
    pl = BatchPlanner(host)
    f_deep = pl.admit("/a/b/c/d/e/f", R.FileRecord(name="f", text="x"))
    f_ok = pl.admit("/d0/fine", R.FileRecord(name="fine", text="ok"))
    f_upd_missing = pl.update("/d0/never_there", lambda r: r)
    f_bad_unlink = pl.unlink("relative/path")
    f_ok_unlink = pl.unlink("/d1/e0")
    pl.flush()
    host.refresh()
    assert isinstance(f_deep.value, P.PathError)        # depth budget 5
    assert isinstance(f_ok.value, R.FileRecord)
    assert isinstance(f_upd_missing.value, KeyError)
    assert isinstance(f_bad_unlink.value, P.PathError)
    assert f_ok_unlink.value is True
    assert store.get("/d0/fine").text == "ok"
    assert store.get("/d1/e0") is None
    # all futures resolved — nothing dangles
    for f in (f_deep, f_ok, f_upd_missing, f_bad_unlink, f_ok_unlink):
        assert f.done


# ---------------------------------------------------------------------------
# ISSUE 6: double-buffered epoch swap + refresh cadence
# ---------------------------------------------------------------------------
def test_epoch_view_unaffected_by_patch_swap():
    """Double-buffer contract: a reader that captured epoch e's view keeps
    answering from epoch e, bit-for-bit, after e+1 is patch-installed —
    the swap is one reference assignment and never writes e's buffers."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store)
    st_e = dev.epoch_view()
    probe = ["/", "/d0", "/d0/e0", "/d1/e2", "/missing"]
    before_q1 = dev.q1_get(probe)
    before_search = dev.q4_search(["/d0", "/d1"])
    before_tok = dev.q4_contains(["e0", "d1", "e2"])
    pl = BatchPlanner(dev)
    pl.admit("/d0/e0", R.FileRecord(name="e0", text="overwritten"))
    pl.admit("/d0/extra", R.FileRecord(name="extra", text="new"))
    pl.admit("/d9", R.DirRecord(name="d9", summary="new dimension"))
    pl.unlink("/d1/e2")
    pl.flush()
    dev.refresh()
    assert dev.last_refresh_kind == "patch"
    st_next = dev.epoch_view()
    assert st_next is not st_e
    # epoch e+1 sees the writes (including the pinned-set change: /d9 is a
    # new depth-1 row, so the VMEM hot-set staging was rebuilt)
    assert dev.q1_get(["/d0/e0"])[0].text == "overwritten"
    assert dev.q1_get(["/d9"])[0].summary == "new dimension"
    assert dev.q1_get(["/d1/e2"]) == [None]
    assert "/d0/extra" in dev.q4_search(["/d0"])[0]
    # ...while the captured epoch-e view still answers exactly as before
    dev._st = st_e
    try:
        assert dev.q1_get(probe) == before_q1
        assert dev.q4_search(["/d0", "/d1"]) == before_search
        assert dev.q4_contains(["e0", "d1", "e2"]) == before_tok
    finally:
        dev._st = st_next


def test_q4_scan_table_packed_once_per_epoch():
    """The prefix scan's packed table is built by an epoch's first Q4
    search and reused by the rest; a patch refresh's new epoch builds its
    own, which holds the new path, while epoch e's view keeps its table
    and its answers."""
    from repro import obs

    reg = obs.configure(enabled=True, ring_size=4096)
    try:
        store = _seed_store()
        dev = DeviceEngine.from_store(store)
        st_e = dev.epoch_view()
        assert st_e.scan_tab is None
        before = dev.q4_search(["/d0"])
        tab_e = st_e.scan_tab
        assert tab_e is not None
        for limit in (1, 2, None):
            assert dev.q4_search(["/d0", "/d1"], limit=limit) == [
                before[0][:limit], dev.q4_search(["/d1"])[0][:limit]]
        assert st_e.scan_tab is tab_e

        def packs():
            return sum(e["args"].get("scan_packs", 0) for e in reg.ring
                       if e["name"] == "device.q4_pack")

        assert packs() == 1
        pl = BatchPlanner(dev)
        pl.admit("/d0/fresh", R.FileRecord(name="fresh", text="new"))
        pl.flush()
        dev.refresh()
        assert dev.last_refresh_kind == "patch"
        st_next = dev.epoch_view()
        assert st_next is not st_e and st_next.scan_tab is None
        after = dev.q4_search(["/d0"])
        assert after[0] == sorted(before[0] + ["/d0/fresh"])
        assert dev.q4_search(["/d0"], limit=2) == [after[0][:2]]
        assert packs() == 2
        assert st_next.scan_tab is not tab_e and st_e.scan_tab is tab_e
        # a reader holding epoch e still scans epoch e's table
        dev._st = st_e
        try:
            assert dev.q4_search(["/d0"]) == before
        finally:
            dev._st = st_next
        assert packs() == 2
    finally:
        obs.configure()


def test_q4_concurrent_first_searches_of_an_epoch_agree():
    """Readers racing to build an epoch's scan table may each build it;
    every build is the same, so every reader gets the same answers."""
    import sys
    import threading

    store = _seed_store(n_dims=3, n_leaves=4)
    dev = DeviceEngine.from_store(store)
    probes = ["/d0", "/d1", "/d2", "/", "/nope"]
    want = HostEngine(store).q4_search(probes)
    got, errors = [], []

    def reader():
        try:
            got.append(dev.q4_search(probes))
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reader) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert got == [want] * 8
    assert dev.epoch_view().scan_tab is not None


def test_refresh_cadence_batches_visibility():
    """refresh_cadence=3: writes stay invisible through the first two
    refresh requests and commit on the third — ONE epoch bump for the
    whole batch (staleness Δ = cadence waves); force=True drains now."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store, refresh_cadence=3)
    pl = BatchPlanner(dev)
    e0 = dev.epoch
    pl.admit("/d0/cad", R.FileRecord(name="cad", text="v"))
    pl.flush()
    assert dev.refresh() == e0
    assert dev.q1_get(["/d0/cad"]) == [None]
    assert dev.refresh() == e0
    assert dev.q1_get(["/d0/cad"]) == [None]
    assert dev.refresh() == e0 + 1              # third wave commits
    assert dev.q1_get(["/d0/cad"])[0].text == "v"
    # a clean refresh stays a no-op and doesn't consume the cadence
    assert dev.refresh() == e0 + 1
    # force=True overrides the cadence (snapshot/drain path)
    pl.admit("/d0/cad2", R.FileRecord(name="cad2", text="w"))
    pl.flush()
    assert dev.refresh(force=True) == e0 + 2
    assert dev.q1_get(["/d0/cad2"])[0].text == "w"


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4))
def test_refresh_cadence_staleness_bound(cadence):
    """Property: with refresh_cadence=k, a wave's writes become visible at
    exactly the k-th subsequent refresh request — never earlier, never
    later (the Δ = cadence staleness bound)."""
    store = _seed_store()
    dev = DeviceEngine.from_store(store, refresh_cadence=cadence)
    pl = BatchPlanner(dev)
    pl.admit("/d0/w", R.FileRecord(name="w", text="x"))
    pl.flush()
    for lag in range(1, cadence + 1):
        dev.refresh()
        visible = dev.q1_get(["/d0/w"])[0] is not None
        assert visible == (lag == cadence)


def test_patch_refresh_parity_with_rebuild_engine():
    """The same write mix answered by a patch-mode engine and a
    rebuild-mode engine is indistinguishable across every Q1–Q4 batch."""
    store_a = _seed_store()
    store_b = _seed_store()
    # fixed clocks so record timestamps can't differ between the runs
    dev_p = DeviceEngine.from_store(
        store_a, writer=WikiWriter(store_a, clock=lambda: 1.0,
                                   bus=InvalidationBus()),
        refresh_mode="patch")
    dev_r = DeviceEngine.from_store(
        store_b, writer=WikiWriter(store_b, clock=lambda: 1.0,
                                   bus=InvalidationBus()),
        refresh_mode="rebuild")
    for dev in (dev_p, dev_r):
        pl = BatchPlanner(dev)
        pl.admit("/d0/sub", R.DirRecord(name="sub"))
        pl.admit("/d0/sub/leaf", R.FileRecord(name="leaf", text="deep"))
        pl.update("/d0/e0", lambda r: R.FileRecord(
            name=r.name, text="rewritten", meta=r.meta))
        pl.unlink("/d1/e1")
        pl.flush()
        dev.refresh()
    assert dev_p.last_refresh_kind == "patch"
    assert dev_r.last_refresh_kind == "rebuild"
    paths = store_a.all_paths() + ["/d1/e1", "/nope"]
    assert dev_p.q1_get(paths) == dev_r.q1_get(paths)
    assert dev_p.q2_ls(paths) == dev_r.q2_ls(paths)
    assert dev_p.q3_navigate(paths) == dev_r.q3_navigate(paths)
    assert dev_p.q4_search(["/", "/d0", "/d0/sub"]) == dev_r.q4_search(
        ["/", "/d0", "/d0/sub"])
    assert dev_p.q4_contains(["leaf", "sub", "e1", "e0"]) == dev_r.q4_contains(
        ["leaf", "sub", "e1", "e0"])


def test_host_engine_surfaces_durable_read_counters(tmp_path):
    """ISSUE 7: bloom-negative and block-cache counters from the durable
    tier surface through ``QueryEngine.stats`` (delta'd — refresh after
    refresh never double-counts), and stay absent over volatile stores."""
    from repro.core.engine import D_BLOOM_NEG, D_CACHE_HIT, D_CACHE_MISS
    from repro.storage import open_durable_store

    root = str(tmp_path / "wiki")
    store = open_durable_store(root, n_shards=2, sync="none",
                               memtable_limit=8, level_ratio=100)
    eng = HostEngine(store)
    for i in range(48):
        # varied names: FNV digests of near-identical short paths skew,
        # and both shards must end up holding segments
        eng.admit_many([(f"/d{i % 4}/ent_{i * 37}",
                         R.FileRecord(name=f"ent_{i * 37}",
                                      text=f"body {i}"))])
        if i % 8 == 7:
            eng.refresh(force=True)       # wave commit → spill
    eng.refresh(force=True)
    assert all(sh.engine.level_counts() for sh in store.shards), \
        "setup: every shard must hold at least one segment"

    misses = [f"/d{i % 4}/absent_{i * 53}" for i in range(16)]
    assert eng.q1_get(misses) == [None] * 16
    eng.sync_durable_stats()
    negs = eng.stats.ops.get(D_BLOOM_NEG, 0)
    assert negs > 0, "miss probes produced no bloom negatives"
    eng.sync_durable_stats()              # idempotent: no new reads
    assert eng.stats.ops.get(D_BLOOM_NEG, 0) == negs

    hit = eng.q1_get(["/d3/ent_111"])[0]  # repeated hits warm the cache
    assert hit is not None and eng.q1_get(["/d3/ent_111"])[0] is not None
    eng.sync_durable_stats()
    assert eng.stats.ops.get(D_CACHE_HIT, 0) + \
        eng.stats.ops.get(D_CACHE_MISS, 0) > 0
    store.close()

    mem_eng = HostEngine(ShardedPathStore(n_shards=2))
    mem_eng.q1_get(["/nope"])
    mem_eng.sync_durable_stats()
    assert D_BLOOM_NEG not in mem_eng.stats.ops
    assert D_CACHE_HIT not in mem_eng.stats.ops
