import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
# The lines above MUST run before any other import (jax locks the device
# count on first init); they keep whatever flags the caller set.  Everything
# below may import jax.  Nothing on the serving path imports this module.

import argparse
import json
import re
import time
import traceback
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.configs import ARCHS, SHAPES, get_arch, supports_shape
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, make_cell  # noqa: F401 (public API)

# ---------------------------------------------------------------------------
# v5e hardware constants (per chip)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_LINK_BW = 50e9           # bytes/s per link

_DTYPES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f16": 2, "bf16": 2,
           "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8,
           "s64": 8, "u64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPES) + r")\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _result_bytes(line: str) -> int:
    """Sum result-buffer bytes on an HLO instruction line (lhs of '=')."""
    lhs = line.split(" = ", 1)
    text = lhs[1] if len(lhs) == 2 else line
    # result types appear before the op name; operands are %refs (no types)
    head = text.split("(", 1)[0]
    total = 0
    for dt, dims in _SHAPE_RE.findall(head):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device operand bytes of every collective op in the HLO.

    all-gather: operand = result / group_size; reduce-scatter: operand =
    result * group_size; others: operand = result.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("%") or stripped.startswith("ROOT"):
            op = next((c for c in _COLLECTIVES if f" {c}(" in stripped
                       or f" {c}-start(" in stripped), None)
            if op is None:
                continue
            rb = _result_bytes(stripped)
            m = _GROUP_RE.search(stripped)
            gsz = int(m.group(2)) if m else 1
            if op == "all-gather":
                rb = rb / max(gsz, 1)
            elif op == "reduce-scatter":
                rb = rb * gsz
            out[op] += rb
    return out


def analyse_lowerable(low, mesh) -> Dict:
    with jax.set_mesh(mesh):
        jitted = jax.jit(low.fn, in_shardings=low.in_shardings,
                         out_shardings=low.out_shardings,
                         donate_argnums=low.donate or ())
        t0 = time.time()
        lowered = jitted.lower(*low.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "name": low.name,
        "multiplier": low.multiplier,
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_total": float(sum(coll.values())),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "lower_s": t_lower,
        "compile_s": t_compile,
    }


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             variant: str = "baseline") -> Dict:
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, reason = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = make_cell(cfg, shape, mesh, variant=variant)
    parts = []
    for low in cell:
        parts.append(analyse_lowerable(low, mesh))

    step = parts[0]
    flops = step["flops"] + sum(p["flops"] * p["multiplier"] for p in parts[1:])
    mem_bytes = step["bytes_accessed"] + sum(
        p["bytes_accessed"] * p["multiplier"] for p in parts[1:])
    coll = step["collective_total"] + sum(
        p["collective_total"] * p["multiplier"] for p in parts[1:])

    n_chips = int(np.prod(mesh.devices.shape))
    # model FLOPs (per device): 6·N·D train / 2·N·D forward, MoE uses active N
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    model_flops_per_dev = model_flops / n_chips

    compute_s = flops / PEAK_FLOPS
    memory_s = mem_bytes / HBM_BW
    collective_s = coll / ICI_LINK_BW
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda kv: kv[1])[0]
    hbm_per_dev = (step["memory"]["argument_bytes"] + step["memory"]["temp_bytes"]
                   + step["memory"]["output_bytes"])
    return {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "step": step["name"],
        "chips": n_chips,
        "per_device": {
            "flops": flops, "bytes_accessed": mem_bytes,
            "collective_bytes": coll,
            "argument_bytes": step["memory"]["argument_bytes"],
            "temp_bytes": step["memory"]["temp_bytes"],
            "output_bytes": step["memory"]["output_bytes"],
            "hbm_total_bytes": hbm_per_dev,
        },
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dominant,
            "model_flops_per_dev": model_flops_per_dev,
            "useful_flops_ratio": (model_flops_per_dev / flops) if flops else 0.0,
        },
        "fits_hbm": bool(hbm_per_dev <= 16e9),
        "collective_breakdown": {
            k: step["collective_bytes"][k] + sum(
                p["collective_bytes"][k] * p["multiplier"] for p in parts[1:])
            for k in step["collective_bytes"]},
        "parts": parts,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod compile-only dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--variant", choices=["baseline", "opt", "opt-zmlp"], default="baseline")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results: List[Dict] = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                meshname = "2x16x16" if mp else "16x16"
                if (arch, shape, meshname) in done:
                    continue
                t0 = time.time()
                try:
                    res = run_cell(arch, shape, mp, variant=args.variant)
                except Exception as e:  # a failure here is a bug in the system
                    res = {"arch": arch, "shape": shape, "mesh": meshname,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                res["wall_s"] = time.time() - t0
                res["variant"] = args.variant
                results.append(res)
                _summ(res)
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"\nwrote {args.out} ({len(results)} cells)")


def _summ(res: Dict) -> None:
    tag = f"{res['arch']:24s} {res['shape']:12s} {res['mesh']:8s}"
    if res["status"] == "skipped":
        print(f"{tag} SKIP ({res['reason'][:60]}...)")
        return
    if res["status"] == "error":
        print(f"{tag} ERROR {res['error'][:100]}")
        return
    r = res["roofline"]
    pd = res["per_device"]
    print(f"{tag} ok  hbm/dev={pd['hbm_total_bytes']/1e9:6.2f}GB "
          f"compute={r['compute_s']*1e3:8.3f}ms memory={r['memory_s']*1e3:8.3f}ms "
          f"coll={r['collective_s']*1e3:8.3f}ms dom={r['dominant']:10s} "
          f"useful={r['useful_flops_ratio']*100:5.1f}% [{res['wall_s']:.0f}s]")


if __name__ == "__main__":
    main()
