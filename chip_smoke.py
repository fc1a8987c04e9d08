"""On-chip smoke run of WikiKV's main path on one TPU.

    python chip_smoke.py [--seed 0] [--paths 1048576]

One process, one chip, data made from ``--seed`` alone:

  store phase    build a seeded root/dimension/topic/page hierarchy
                 through ``PathStore`` (fan-out ~64, a word vocabulary in
                 the segment names), freeze it with
                 ``DeviceEngine.from_store``, and answer Q1 get, Q2 ls, Q3
                 navigate, Q4 prefix search and Q4 keyword in batches of
                 256 through a ``BatchPlanner`` — hits and misses mixed —
                 comparing every answer with a ``HostEngine`` over the same
                 store.  Then one write wave (64 admits, 16 updates, 16
                 unlinks) through the planner, a ``patch`` refresh, and the
                 same comparison again.
  serving phase  ``ServingEngine`` over that ``DeviceEngine`` with the
                 full-width ``wikikv-router`` answers 4 requests; one
                 ``decode_attention`` call at the serving shapes is checked
                 against its float32 reference.
  kernel proof   the lowered programs of ``path_lookup``,
                 ``prefix_search`` and ``decode_attention`` hold a
                 ``tpu_custom_call``, i.e. the kernels run compiled.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failed
check, or a JAX backend that is not a TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass, replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import records as R  # noqa: E402
from repro.core.engine import BatchPlanner, DeviceEngine, HostEngine  # noqa: E402
from repro.core.oracle import HeuristicOracle  # noqa: E402
from repro.core.store import MemKV, PathStore  # noqa: E402
from repro.data.tokenizer import HashTokenizer  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.runtime.serving import Request, ServingEngine  # noqa: E402

#: operations per engine batch (each op kind is one engine call)
BATCH = 256
#: the store's memtable holds a million entries before it freezes a run,
#: so bulk loading sorts a few large runs instead of merging many small ones
MEMTABLE_LIMIT = 1 << 20
READ_OPS = ("q1_get", "q2_ls", "q3_navigate", "q4_search", "q4_contains")


@dataclass
class Wiki:
    """The seeded hierarchy: every path by level, plus its word vocabulary."""

    store: PathStore
    vocab: list[str]
    dims: list[str]
    topics: list[str]
    pages: list[str]

    @property
    def n_paths(self) -> int:
        return 1 + len(self.dims) + len(self.topics) + len(self.pages)


def _vocabulary(rng: np.random.Generator, n: int) -> list[str]:
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    words: set[str] = set()
    while len(words) < n:
        k = int(rng.integers(4, 9))
        words.add(bytes(letters[rng.integers(0, 26, size=k)]).decode())
    return sorted(words)


def build_wiki(n_paths: int, seed: int, fanout: int = 64,
               n_words: int = 1024) -> Wiki:
    """Root → dimensions → topics → pages, ``fanout`` topics per dimension
    and pages per topic, with enough dimensions for ≥ ``n_paths`` pages.
    Segment names join vocabulary words and a level id with underscores,
    so every path carries keyword tokens for Q4 routing."""
    rng = np.random.default_rng(seed)
    vocab = _vocabulary(rng, n_words)
    n_dims = -(-n_paths // (fanout * fanout))
    store = PathStore(MemKV(memtable_limit=MEMTABLE_LIMIT))
    words = rng.integers(0, n_words, size=(n_dims, fanout + 1, fanout, 2))
    dim_names = [f"{vocab[words[d, fanout, 0, 0]]}_d{d}" for d in range(n_dims)]
    store.put_record("/", R.DirRecord(name="root", sub_dirs=dim_names,
                                      summary="seeded wiki"))
    dims, topics, pages = [], [], []
    for d, dname in enumerate(dim_names):
        dpath = "/" + dname
        tnames = [f"{vocab[words[d, t, 0, 0]]}_t{t}" for t in range(fanout)]
        store.put_record(dpath, R.DirRecord(name=dname, sub_dirs=tnames,
                                            summary=f"dimension {dname}"))
        dims.append(dpath)
        for t, tname in enumerate(tnames):
            tpath = f"{dpath}/{tname}"
            pnames = [f"{vocab[a]}_{vocab[b]}_p{j}"
                      for j, (a, b) in enumerate(words[d, t])]
            store.put_record(tpath, R.DirRecord(name=tname, files=pnames,
                                                summary=f"topic {tname}"))
            topics.append(tpath)
            for pname in pnames:
                ppath = f"{tpath}/{pname}"
                text = " ".join(pname.split("_")[:2])
                store.put_record(ppath, R.FileRecord(
                    name=pname, text=f"{text} is filed under {tname}."))
                pages.append(ppath)
    store.flush()
    return Wiki(store, vocab, dims, topics, pages)


# ---------------------------------------------------------------------------
# store phase
# ---------------------------------------------------------------------------
def read_batches(wiki: Wiki, rng: np.random.Generator,
                 extra_hits: list[str] = (), extra_misses: list[str] = ()
                 ) -> dict[str, list[str]]:
    """BATCH distinct keys per read op, about a quarter of them misses.
    ``extra_*`` are paths that must be among the keys (a write wave's
    admissions and unlinks)."""
    def pick(pool, n):
        n = min(n, len(pool))
        return [pool[i] for i in rng.choice(len(pool), size=n, replace=False)]

    def fill(keys, pool):
        seen = list(dict.fromkeys(keys))
        while len(seen) < BATCH:
            seen = list(dict.fromkeys(seen + pick(pool, BATCH - len(seen))))
        return seen[:BATCH]

    extra = list(extra_hits) + list(extra_misses)
    n_miss = BATCH // 4
    missing_pages = [p[:-1] + "_x" for p in pick(wiki.pages, n_miss)]
    missing_topics = [t + "_x/page" for t in pick(wiki.topics, n_miss)]
    dirs = ["/"] + wiki.dims + wiki.topics
    prefixes = (pick(wiki.dims, BATCH // 8) + pick(wiki.topics, BATCH // 4)
                + pick(wiki.pages, BATCH // 8)
                # a cut segment must not match: "/abc" never matches "/abcd"
                + [t[:-1] for t in pick(wiki.topics, BATCH // 8)]
                + pick(missing_topics, BATCH // 8))
    segments = [p.rsplit("/", 1)[1] for p in extra + pick(wiki.pages, BATCH // 8)]
    keywords = (segments + pick(wiki.vocab, BATCH // 2)
                + [w + "zz" for w in pick(wiki.vocab, BATCH // 4)])
    return {
        "q1_get": fill(extra + pick(missing_pages, n_miss),
                       dirs + wiki.pages),
        "q2_ls": fill(extra + pick(missing_pages, n_miss // 2)
                      + pick(wiki.pages, n_miss // 2), dirs),
        "q3_navigate": fill(extra + pick(missing_topics, n_miss // 2)
                            + pick(missing_pages, n_miss // 2), wiki.pages),
        "q4_search": fill(extra + prefixes, wiki.topics),
        "q4_contains": fill(keywords, wiki.vocab),
    }


def _run_reads(planner: BatchPlanner, keys: dict[str, list[str]]) -> dict:
    enqueue = {"q1_get": planner.get, "q2_ls": planner.ls,
               "q3_navigate": planner.navigate, "q4_search": planner.search,
               "q4_contains": planner.contains}
    futs = {op: [enqueue[op](k) for k in ks] for op, ks in keys.items()}
    planner.flush()
    return {op: [f.result() for f in fs] for op, fs in futs.items()}


def compare_reads(device: DeviceEngine, host: HostEngine,
                  keys: dict[str, list[str]]) -> dict[str, int]:
    """Mismatch count per read op between the device and host engines,
    each answering the same batches through its own planner (one engine
    call per op kind)."""
    got = _run_reads(BatchPlanner(device), keys)
    want = _run_reads(BatchPlanner(host), keys)
    return {op: sum(a != b for a, b in zip(got[op], want[op]))
            for op in READ_OPS}


def write_wave(planner: BatchPlanner, wiki: Wiki, rng: np.random.Generator,
               n_admit: int = 64, n_update: int = 16, n_unlink: int = 16
               ) -> dict:
    """One planner wave of admits, OCC updates and unlinks.  Returns the
    touched paths and how many futures resolved to a failure."""
    admit_topics = [wiki.topics[i] for i in
                    rng.choice(len(wiki.topics), size=n_admit)]
    admitted = [f"{t}/{wiki.vocab[int(rng.integers(len(wiki.vocab)))]}_new{i}"
                for i, t in enumerate(admit_topics)]
    chosen = rng.choice(len(wiki.pages), size=n_update + n_unlink,
                        replace=False)
    updated = [wiki.pages[i] for i in chosen[:n_update]]
    unlinked = [wiki.pages[i] for i in chosen[n_update:]]
    futs = [planner.admit(p, R.FileRecord(name=p.rsplit("/", 1)[1],
                                          text=f"new page {i}"))
            for i, p in enumerate(admitted)]
    futs += [planner.update(p, lambda rec: replace(rec, text=rec.text
                                                    + " revised"))
             for p in updated]
    unlink_futs = [planner.unlink(p) for p in unlinked]
    planner.flush()
    # a failed write resolves to its exception; an unlink that found
    # nothing resolves to False
    failed = (sum(isinstance(f.result(), BaseException) for f in futs)
              + sum(f.result() is not True for f in unlink_futs))
    return {"admitted": admitted, "updated": updated, "unlinked": unlinked,
            "n_writes": len(futs) + len(unlink_futs), "failed": failed}


def store_phase(wiki: Wiki, seed: int) -> tuple[DeviceEngine, dict]:
    """Freeze the wiki onto the device, then compare the device and host
    engines before and after one write wave and its patch refresh."""
    rng = np.random.default_rng(seed + 1)
    t0 = time.perf_counter()
    device = DeviceEngine.from_store(wiki.store)
    st = device.epoch_view()
    jax.block_until_ready((st.khi, st.klo, st.ptoks, st.tok_hi, st.tok_lo))
    freeze_s = time.perf_counter() - t0
    host = HostEngine(wiki.store)
    t0 = time.perf_counter()
    before = compare_reads(device, host, read_batches(wiki, rng))
    first_wave_s = time.perf_counter() - t0
    wave = write_wave(BatchPlanner(device), wiki, rng)
    epoch = device.refresh()
    keys = read_batches(wiki, rng, extra_hits=wave["admitted"] + wave["updated"],
                        extra_misses=wave["unlinked"])
    after = compare_reads(device, host, keys)
    # the wave is visible on the device: admissions and updates read back,
    # unlinked pages are gone
    written = device.q1_get(wave["admitted"] + wave["updated"])
    revised = written[len(wave["admitted"]):]
    visible = (sum(r is not None for r in written)
               - sum(r is None or not r.text.endswith(" revised")
                     for r in revised))
    gone = sum(r is None for r in device.q1_get(wave["unlinked"]))
    st = device.epoch_view()
    return device, {
        "paths": wiki.n_paths,
        "device_rows": device.wiki.n,
        "device_tables": {"keys": list(st.khi.shape),
                          "path_tokens": list(st.ptoks.shape),
                          "token_keys": list(st.tok_hi.shape),
                          "device": str(st.ptoks.devices().pop())},
        "freeze_s": freeze_s,
        "first_read_wave_s": first_wave_s,
        "batch": BATCH,
        "mismatches_before": before,
        "writes": wave["n_writes"],
        "failed_writes": wave["failed"],
        "epoch": epoch,
        "refresh_kind": device.last_refresh_kind,
        "writes_visible": visible + gone,
        "mismatches_after": after,
    }


def check_store(report: dict) -> list[str]:
    errors = [f"{when}: {op} has {n} mismatches"
              for when in ("mismatches_before", "mismatches_after")
              for op, n in report[when].items() if n]
    if report["failed_writes"]:
        errors.append(f"{report['failed_writes']} write futures failed")
    if report["writes_visible"] != report["writes"]:
        errors.append(f"{report['writes'] - report['writes_visible']} "
                      "writes are not visible after the refresh")
    if report["refresh_kind"] != "patch":
        errors.append(f"refresh kind {report['refresh_kind']!r}, not 'patch'")
    if report["device_rows"] < report["paths"]:
        errors.append("device table holds fewer rows than the store")
    return errors


# ---------------------------------------------------------------------------
# serving phase
# ---------------------------------------------------------------------------
def serving_phase(device: DeviceEngine, wiki: Wiki, cfg, seed: int,
                  n_requests: int = 4, max_new_tokens: int = 8,
                  max_len: int = 256) -> dict:
    """Answer ``n_requests`` through ``ServingEngine`` over the device
    engine: navigation rides the planner into the device kernels, decode
    runs the LM (random weights from ``seed``)."""
    rng = np.random.default_rng(seed + 2)
    tok = HashTokenizer(vocab_size=cfg.vocab).fit([" ".join(wiki.vocab)])
    params = M.init_params(cfg, seed=seed)
    calls_before = device.stats.total_calls()
    engine = ServingEngine(cfg, params, tok, device, HeuristicOracle(),
                           batch_size=n_requests, max_len=max_len)
    words = [wiki.vocab[i] for i in rng.choice(len(wiki.vocab),
                                                size=2 * n_requests)]
    reqs = [Request(rid=f"r{i}", query=f"what is {words[2 * i]} "
                                       f"{words[2 * i + 1]}?",
                    max_new_tokens=max_new_tokens)
            for i in range(n_requests)]
    t0 = time.perf_counter()
    done = engine.run(reqs)
    return {
        "requests": n_requests,
        "served": len(done),
        "answered": sum(bool(r.answer) for r in done),
        "tool_calls": sum(r.trace.tool_calls for r in done),
        "engine_calls": device.stats.total_calls() - calls_before,
        "serve_s": time.perf_counter() - t0,
    }


def check_serving(report: dict) -> list[str]:
    errors = []
    if report["served"] != report["requests"]:
        errors.append(f"served {report['served']} of {report['requests']}")
    if not report["engine_calls"]:
        errors.append("navigation never reached the device engine")
    return errors


def decode_check(cfg, batch: int, max_len: int, seed: int) -> dict:
    """One ``ops.decode_attention`` call at the serving shapes against
    ``ref.decode_attention_ref`` at HIGHEST matmul precision."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = cfg.d_head
    q = jax.random.normal(kq, (batch, cfg.n_heads, d), jnp.float32)
    k = jax.random.normal(kk, (batch, cfg.n_kv_heads, max_len, d), jnp.float32)
    v = jax.random.normal(kv, (batch, cfg.n_kv_heads, max_len, d), jnp.float32)
    lengths = jnp.asarray([(max_len // 2 + 37 * i) % max_len + 1
                           for i in range(batch)], jnp.int32)
    got = np.asarray(ops.decode_attention(q, k, v, lengths))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.decode_attention_ref(q, k, v, lengths))
    err = float(np.max(np.abs(got - want)))
    return {"shape": [batch, cfg.n_heads, cfg.n_kv_heads, max_len, d],
            "max_abs_err": err,
            "within_tol": bool(np.allclose(got, want, atol=3e-5, rtol=3e-5))}


# ---------------------------------------------------------------------------
# kernel proof
# ---------------------------------------------------------------------------
def kernel_proof(device: DeviceEngine, cfg, batch: int, max_len: int
                 ) -> dict:
    """Lower the three storage/serving ops at the shapes the run used and
    look for the Pallas TPU custom call; report each compile's seconds."""
    st = device.epoch_view()
    q = jnp.zeros((BATCH,), jnp.uint32)
    L = st.ptoks.shape[1]
    d = cfg.d_head
    progs = {
        "path_lookup": (lambda kh, kl, qh, ql, pin: ops.path_lookup(
            kh, kl, qh, ql, pinned=pin), (st.khi, st.klo, q, q, st.pinned)),
        "prefix_search": (ops.prefix_search, (
            st.ptoks, jnp.zeros((BATCH, L), jnp.uint8),
            jnp.zeros((BATCH,), jnp.int32))),
        "decode_attention": (ops.decode_attention, (
            jnp.zeros((batch, cfg.n_heads, d), jnp.float32),
            jnp.zeros((batch, cfg.n_kv_heads, max_len, d), jnp.float32),
            jnp.zeros((batch, cfg.n_kv_heads, max_len, d), jnp.float32),
            jnp.ones((batch,), jnp.int32))),
    }
    out = {}
    for name, (fn, args) in progs.items():
        lowered = jax.jit(fn).lower(*args)
        t0 = time.perf_counter()
        lowered.compile()
        out[name] = {"tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
                     "compile_s": time.perf_counter() - t0}
    return out


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paths", type=int, default=1 << 20,
                    help="pages in the seeded wiki (directories come on top)")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not a TPU",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device_kind": dev.device_kind, "compile_cache": cache_dir}))
    cfg = get_config("wikikv-router")
    n_requests, max_len = 4, 256

    t0 = time.perf_counter()
    wiki = build_wiki(args.paths, args.seed)
    print(json.dumps({"phase": "build", "paths": wiki.n_paths,
                      "build_s": time.perf_counter() - t0}), flush=True)
    device, store = store_phase(wiki, args.seed)
    print(json.dumps({"phase": "store", **store}), flush=True)
    serving = serving_phase(device, wiki, cfg, args.seed,
                            n_requests=n_requests, max_len=max_len)
    serving["decode_attention"] = decode_check(cfg, n_requests, max_len,
                                               args.seed)
    print(json.dumps({"phase": "serving", **serving}), flush=True)
    proof = kernel_proof(device, cfg, n_requests, max_len)
    print(json.dumps({"phase": "kernels", **proof}), flush=True)

    errors = check_store(store) + check_serving(serving)
    if not serving["decode_attention"]["within_tol"]:
        errors.append("decode_attention is outside float32 tolerance")
    errors += [f"{name} has no tpu_custom_call" for name, p in proof.items()
               if not p["tpu_custom_call"]]
    if errors:
        for e in errors:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
