import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first statements of this module — jax
locks the device count on first init, and the production meshes need 512
placeholder host devices.  (Only the dry-run sets this; tests and benches
see 1 device.)

Per cell this script:
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. builds abstract params/opt-state/batch (ShapeDtypeStruct — nothing is
     allocated, ever, for the full configs),
  3. ``jax.jit(step, in_shardings=…, out_shardings=…).lower(...)`` and
     ``.compile()`` — sharding mismatches, compile-time OOM and
     unsupported collectives all fail HERE,
  4. records ``compiled.memory_analysis()``, ``cost_analysis()`` and the
     per-collective byte counts parsed from the optimized HLO into
     ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_ids, get_config
from repro.launch import hlo_walk
from repro.launch.mesh import dp_axes, dp_size, make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig, adamw_init

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# v5e hardware constants for the roofline terms (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link


def _opt_cfg(cfg: ModelConfig) -> AdamWConfig:
    # trillion-param configs need quantized moments to fit (see configs/kimi)
    if cfg.name.startswith("kimi"):
        return AdamWConfig(state_dtype="int8")
    if cfg.name.startswith(("dbrx", "jamba")):
        return AdamWConfig(state_dtype="bfloat16")
    return AdamWConfig(state_dtype="float32")


def batch_shardings(cfg: ModelConfig, shape: M.ShapeSpec, mesh):
    dpa = dp_axes(mesh)
    dps = dp_size(mesh)
    specs = {}
    b_ok = shape.global_batch % dps == 0 and shape.global_batch >= dps
    bspec = dpa if b_ok else None
    for k, v in M.input_specs(cfg, shape).items():
        spec = [None] * len(v.shape)
        if len(v.shape) >= 1:
            spec[0] = bspec
        # decode with batch 1: shard the cache/context length instead
        if not b_ok and k in ("enc_out",) and len(v.shape) == 3:
            spec[1] = "model"
        specs[k] = NamedSharding(mesh, P(*spec))
    return specs


def skip_reason(cfg: ModelConfig, shape: M.ShapeSpec) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("SKIP(full-attn): 512k dense attention/KV is out of reach "
                "for a quadratic arch; DESIGN.md §4")
    return None


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               *, compile_: bool = True) -> dict:
    cfg = get_config(arch)
    shape = M.SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "status": "skip", "skip_reason": reason}
    if reason is not None:
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    abstract = M.abstract_params(cfg)
    pspecs = M.spec_tree(cfg)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda s: isinstance(s, P))
    bshard = batch_shardings(cfg, shape, mesh)
    binputs = M.input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = _opt_cfg(cfg)
        opt_abstract = jax.eval_shape(
            lambda p: adamw_init(p, opt_cfg), abstract)
        ospecs = M.opt_spec_tree(pspecs, opt_cfg, cfg, abstract=abstract)
        oshard = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                              is_leaf=lambda s: isinstance(s, P))
        step = M.make_train_step(cfg, opt_cfg, mesh)
        jitted = jax.jit(step,
                         in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None))
        args = (abstract, opt_abstract, binputs)
    elif shape.kind == "prefill":
        step = M.make_prefill_step(cfg, mesh)
        jitted = jax.jit(step, in_shardings=(pshard, bshard),
                         out_shardings=None)
        args = (abstract, binputs)
    else:  # decode
        state_abstract = M.abstract_decode_state(
            cfg, shape.global_batch, shape.seq_len)
        sspecs = M.decode_state_specs(
            cfg, shape.global_batch, dp=dp_axes(mesh), dp_size=dp_size(mesh),
            cache_layout=os.environ.get("REPRO_CACHE_LAYOUT", "auto"),
            tp_size=mesh.shape["model"])
        sshard = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                              is_leaf=lambda s: isinstance(s, P))
        step = M.make_serve_step(cfg, mesh)
        jitted = jax.jit(step, in_shardings=(pshard, sshard, bshard),
                         out_shardings=(None, None, sshard))
        args = (abstract, state_abstract, binputs)

    with mesh:
        lowered = jitted.lower(*args)
        result["lower_s"] = round(time.time() - t0, 2)
        if not compile_:
            result["status"] = "lowered"
            return result
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 2)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        result["memory"] = _mem_dict(mem)
        result["cost_analysis_raw"] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and _keep_cost(k)}
        hlo = compiled.as_text()
        # trip-count-aware walk (XLA cost_analysis counts while bodies once)
        walked = hlo_walk.walk(hlo)
        result["walk"] = walked.as_dict()
        result["cost"] = {"flops": walked.flops, "bytes accessed": walked.bytes}
        result["collectives"] = dict(walked.collectives,
                                     total_bytes=walked.collective_bytes)
        result["hlo_ops"] = op_histogram(hlo)
        result["status"] = "ok"
        result.update(roofline_terms(result, cfg, shape, mesh))
    return result


def _keep_cost(k: str) -> bool:
    return k in ("flops", "bytes accessed", "transcendentals",
                 "utilization") or k.startswith("bytes accessed")


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("generated_code_size_in_bytes",
                 "argument_size_in_bytes", "output_size_in_bytes",
                 "alias_size_in_bytes", "temp_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    out["total_per_device"] = (out.get("argument_size_in_bytes", 0)
                               + out.get("output_size_in_bytes", 0)
                               + out.get("temp_size_in_bytes", 0)
                               - out.get("alias_size_in_bytes", 0))
    return out


_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
    re.M)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Sum result-shape bytes of every collective op in the optimized HLO.
    (Result shape ≈ operand shape for AR/AG outputs; a consistent proxy
    across ops — the §Roofline collective term divides by chip count so
    only relative magnitudes across candidate layouts matter.)"""
    out: dict[str, dict] = {}
    for m in _COLL_RE.finditer(hlo):
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


def op_histogram(hlo: str) -> dict:
    """Counts of interesting ops (fusion inspection for §Perf)."""
    ops = {}
    for name in ("fusion", "dot", "convolution", "scatter", "gather",
                 "while", "sort", "rng", "copy", "transpose", "reshape"):
        ops[name] = len(re.findall(rf"= \S+ {name}\(", hlo))
    return ops


def roofline_terms(result: dict, cfg: ModelConfig, shape: M.ShapeSpec,
                   mesh) -> dict:
    chips = int(np.prod(list(mesh.shape.values())))
    flops = result["cost"].get("flops", 0.0)
    byts = result["cost"].get("bytes accessed", 0.0)
    coll = result["collectives"].get("total_bytes", 0)
    # cost_analysis is per-program (per device under SPMD)
    t_compute = flops / PEAK_FLOPS
    t_memory = byts / HBM_BW
    t_coll = coll / ICI_BW
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))[1]
    mf = M.model_flops(cfg, shape)
    return {
        "roofline": {
            "chips": chips,
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "dominant": dominant,
            "model_flops_total": mf,
            "model_flops_per_chip": mf / chips,
            "useful_flops_ratio": (mf / chips) / flops if flops else 0.0,
        }
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: Path = ARTIFACTS) -> dict:
    multi = mesh_kind == "multi"
    try:
        res = lower_cell(arch, shape_name, multi)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        res = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi else "16x16",
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    mesh_name = res["mesh"]
    fn = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    fn.write_text(json.dumps(res, indent=1, default=str))
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    archs = all_arch_ids() if args.all or args.arch is None else [args.arch]
    shapes = list(M.SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    ok = bad = skip = 0
    for arch in archs:
        for shape_name in shapes:
            for mk in meshes:
                mesh_name = "2x16x16" if mk == "multi" else "16x16"
                fn = ARTIFACTS / f"{arch}__{shape_name}__{mesh_name}.json"
                if args.skip_existing and fn.exists():
                    prev = json.loads(fn.read_text())
                    if prev.get("status") in ("ok", "skip"):
                        print(f"[cached] {arch} {shape_name} {mesh_name} "
                              f"{prev['status']}", flush=True)
                        ok += prev["status"] == "ok"
                        skip += prev["status"] == "skip"
                        continue
                t0 = time.time()
                res = run_cell(arch, shape_name, mk)
                dt = time.time() - t0
                st = res["status"]
                ok += st == "ok"
                bad += st == "error"
                skip += st == "skip"
                line = f"[{st:5s}] {arch:18s} {shape_name:12s} {mesh_name:8s} {dt:7.1f}s"
                if st == "ok":
                    r = res["roofline"]
                    line += (f" dom={r['dominant']:10s}"
                             f" tc={r['t_compute_s']:.3e}"
                             f" tm={r['t_memory_s']:.3e}"
                             f" tx={r['t_collective_s']:.3e}"
                             f" mem={res['memory']['total_per_device']/2**30:.1f}GiB")
                elif st == "error":
                    line += " " + res["error"][:160]
                print(line, flush=True)
    print(f"\nDRYRUN SUMMARY ok={ok} skip={skip} error={bad}", flush=True)
    return 0 if bad == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
