"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape x mesh) cell, print memory/cost analysis, parse
collective bytes from the compiled HLO, and emit the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi    # 2x16x16 only
Results accumulate in dryrun_results.json (one record per cell x mesh).
`--force-devices N` (default 512, 0 = leave XLA_FLAGS alone) sets the forced
host device count from `main()` only, so importing this module never
re-shapes the importer's devices.
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.launch.mesh import (
    HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh, mesh_size)
from repro.launch.profile_cell import _force_host_devices

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[8,128,256]{...}' -> byte count (0 for tuples/tokens)."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op, by kind.

    Parses lines like
      `%ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups=...`
    including tuple-shaped outputs `(f32[4], f32[8]) all-reduce(...)`.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s*"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start|-done)?\(", line)
        if not m:
            continue
        shapes_str, kind, phase = m.groups()
        if phase == "-done":
            continue  # counted at -start
        total = 0
        for sh in re.findall(r"[a-z0-9]+\[[0-9,]*\]", shapes_str):
            total += _shape_bytes(sh)
        out[kind] += total
        counts[kind] += 1
    return out, counts


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   n_chips: int):
    t_compute = flops / (n_chips * PEAK_FLOPS_BF16)
    t_memory = bytes_accessed / (n_chips * HBM_BW)
    t_collective = coll_bytes / (n_chips * ICI_BW)
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dom = max(terms, key=terms.get)
    return terms, dom


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True):
    from repro.launch.steps import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh_size(mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        plan = build_cell(arch, shape, mesh)
        jitted = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                         out_shardings=plan.out_shardings,
                         donate_argnums=plan.donate_argnums)
        lowered = jitted.lower(*plan.args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze
    tot = analyze(hlo)  # trip-count-scaled, per-chip (SPMD partition module)
    coll, coll_counts = tot.coll_bytes, tot.coll_counts
    coll_total = tot.coll_total
    flops = tot.flops
    bytes_acc = tot.mem_bytes
    xla_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    xla_bytes = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    terms = {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll_total / ICI_BW,
    }
    dom = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "step": plan.step_name,
        "compile_s": round(t_compile, 1),
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_acc,
        "xla_flops_flat": xla_flops,
        "xla_bytes_flat": xla_bytes,
        "collective_bytes_per_chip": coll_total,
        "collective_breakdown": coll,
        "collective_counts": coll_counts,
        "model_flops": plan.model_flops,
        "flops_ratio_model_over_hlo": (
            plan.model_flops / (flops * n_chips) if flops else None),
        "roofline": terms,
        "bottleneck": dom,
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    }
    if verbose:
        print(f"[{rec['mesh']}] {arch} x {shape} ({plan.step_name}): "
              f"compile {t_compile:.1f}s | {flops:.3g} FLOP/chip | "
              f"{bytes_acc:.3g} B/chip | coll {coll_total:.3g} B | "
              f"bottleneck {dom}")
        print("  memory_analysis:", rec["memory_analysis"])
        print("  cost_analysis keys:", sorted(cost.keys())[:12] if cost else None)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--include-wharf", action="store_true",
                    help="also dry-run the wharf-stream config")
    ap.add_argument("--force-devices", type=int, default=512,
                    help="forced host platform device count for the dry-run "
                         "meshes (0 = don't touch XLA_FLAGS)")
    args = ap.parse_args()
    if args.force_devices:  # before the first device query initializes XLA
        _force_host_devices(args.force_devices)

    from repro.configs import all_cells, get_arch

    cells = [c for c in all_cells()]
    if not args.include_wharf:
        cells = [c for c in cells if get_arch(c[0]).family != "wharf"]
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    try:
        with open(args.out) as f:
            results = json.load(f)
    except (OSError, json.JSONDecodeError):
        results = {}

    failures = []
    for arch, shape in cells:
        for multi in meshes:
            key = f"{arch}|{shape}|{'multi' if multi else 'single'}"
            try:
                rec = run_cell(arch, shape, multi)
                results[key] = rec
            except Exception as e:  # noqa: BLE001
                failures.append((key, repr(e)))
                print(f"FAILED {key}: {e}")
                traceback.print_exc()
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    print(f"\n{len(results)} cells recorded in {args.out}; "
          f"{len(failures)} failures")
    for k, e in failures:
        print("  FAIL", k, e)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
