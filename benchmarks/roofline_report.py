"""§Roofline report: the three-term model per (arch × shape × mesh),
read from the dry-run artifacts (no recompilation).

    compute   = HLO_FLOPs(per device)     / peak_FLOP/s
    memory    = HLO_bytes(per device)     / HBM_bw
    collective= collective_bytes(per dev) / link_bw

Flags the dominant term, the MODEL_FLOPS/HLO_FLOPS 'useful compute'
ratio, and per-device memory vs the 16 GiB v5e HBM budget.

A second section times the *storage* kernels live: the real Pallas
paths — ``path_lookup`` with its VMEM pinned probe, and
``prefix_search`` — through ``kernels.ops`` (compiled on a TPU backend;
interpret mode elsewhere, under ``REPRO_FORCE_PALLAS=1``) against the
jitted XLA references called directly.  Row names carry the mode the
kernels ran in, read from the backend.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

import numpy as np

from common import ARTIFACTS, emit

HBM_BUDGET = 16 * 2**30


def load_cells(mesh: str | None = None):
    cells = []
    for fn in sorted((ARTIFACTS / "dryrun").glob("*.json")):
        rec = json.loads(fn.read_text())
        if mesh and rec.get("mesh") != mesh:
            continue
        cells.append(rec)
    return cells


def _timed_ms(fn, iters: int = 5) -> float:
    fn()  # warmup (trace/compile)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1000)
    return float(np.median(ts))


def storage_kernel_rows(n_keys: int = 2048, n_q: int = 512,
                        n_pin: int = 8, iters: int = 5) -> list[tuple]:
    """Time the storage kernels against their references (see module
    docstring)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.path_lookup import pad_pinned

    rng = np.random.default_rng(0)
    keys = np.sort(rng.choice(2**62, size=n_keys, replace=False)
                   .astype(np.uint64))
    khi = jnp.asarray((keys >> np.uint64(32)).astype(np.uint32))
    klo = jnp.asarray((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    # query mix: half hits (some pinned), half misses
    hit = keys[rng.integers(0, n_keys, size=n_q // 2)]
    miss = rng.choice(2**62, size=n_q - n_q // 2).astype(np.uint64) | 1
    q = np.concatenate([hit, miss])
    qhi = jnp.asarray((q >> np.uint64(32)).astype(np.uint32))
    qlo = jnp.asarray((q & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    pin_idx = np.sort(rng.choice(n_keys, size=n_pin, replace=False))
    pinned = tuple(jnp.asarray(a) for a in pad_pinned(
        (keys[pin_idx] >> np.uint64(32)).astype(np.uint32),
        (keys[pin_idx] & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        pin_idx.astype(np.int32)))
    L = 96
    toks = np.zeros((n_keys, L), dtype=np.uint8)
    for i in range(n_keys):
        p = f"/dim{i % 16}/doc{i}".encode()
        toks[i, :len(p)] = np.frombuffer(p, dtype=np.uint8)
    toks_j = jnp.asarray(toks)
    prefs = np.full((8, L), 255, dtype=np.uint8)
    lens = np.full((8,), 1, dtype=np.int32)
    for i in range(8):
        p = f"/dim{i}".encode()
        prefs[i, :len(p)] = np.frombuffer(p, dtype=np.uint8)
        lens[i] = len(p)
    prefs_j, lens_j = jnp.asarray(prefs), jnp.asarray(lens)

    lookup_ref = jax.jit(ref.path_lookup_pinned_ref)
    prefix_ref = jax.jit(jax.vmap(ref.prefix_search_ref, in_axes=(None, 0, 0)))
    fns = {
        ("lookup", "pallas"): lambda: ops.path_lookup(
            khi, klo, qhi, qlo, pinned=pinned),
        ("lookup", "ref"): lambda: lookup_ref(khi, klo, qhi, qlo, *pinned),
        ("prefix", "pallas"): lambda: ops.prefix_search(
            toks_j, prefs_j, lens_j),
        ("prefix", "ref"): lambda: prefix_ref(toks_j, prefs_j, lens_j),
    }
    prev = os.environ.get("REPRO_FORCE_PALLAS")
    os.environ["REPRO_FORCE_PALLAS"] = "1"
    try:
        ms = {key: _timed_ms(lambda f=f: np.asarray(f()), iters)
              for key, f in fns.items()}
    finally:
        if prev is None:
            os.environ.pop("REPRO_FORCE_PALLAS")
        else:
            os.environ["REPRO_FORCE_PALLAS"] = prev
    kind = "compiled" if jax.default_backend() == "tpu" else "interpret"
    rows = []
    for op in ("lookup", "prefix"):
        p_ms, r_ms = ms[(op, "pallas")], ms[(op, "ref")]
        rows.append((f"roofline_storage_{op}_pallas_{kind}",
                     round(p_ms, 3),
                     f"ms;keys={n_keys};q={n_q};pinned={n_pin}"))
        rows.append((f"roofline_storage_{op}_ref_compiled",
                     round(r_ms, 3), "ms;jitted_xla_reference"))
        rows.append((f"roofline_storage_{op}_{kind}_vs_compiled",
                     round(p_ms / max(r_ms, 1e-9), 2),
                     "x;pallas_over_ref"))
    return rows


def run(mesh: str = "16x16"):
    rows = []
    for rec in load_cells(mesh):
        tag = f"{rec['arch']}__{rec['shape']}"
        if rec["status"] == "skip":
            rows.append((f"roofline_{tag}", "skip",
                         rec.get("skip_reason", "")[:40]))
            continue
        if rec["status"] != "ok":
            rows.append((f"roofline_{tag}", "error", rec.get("error", "")[:60]))
            continue
        r = rec["roofline"]
        mem = rec["memory"]["total_per_device"]
        rows.append((
            f"roofline_{tag}",
            round(max(r["t_compute_s"], r["t_memory_s"],
                      r["t_collective_s"]), 4),
            f"dom={r['dominant']};tc={r['t_compute_s']:.3e};"
            f"tm={r['t_memory_s']:.3e};tx={r['t_collective_s']:.3e};"
            f"useful={r['useful_flops_ratio']:.2f};"
            f"mem={mem/2**30:.1f}GiB;"
            f"fits16G={'Y' if mem <= HBM_BUDGET else 'N'}"))
    emit(rows, header=f"Roofline terms per cell ({mesh})")
    kernel_rows = storage_kernel_rows()
    emit(kernel_rows, header="Storage kernels: Pallas path vs XLA reference")
    return rows + kernel_rows


if __name__ == "__main__":
    run()
