#!/usr/bin/env python3
"""Controls and faults at a cell's own size, on the chip: in one process,
run the cell once per (fault, seed) with the fault planted (faults.py;
"none" for sound runs) and print one JSON line per run with `correct` and
every compared number. With --controls, the line also holds each named
reference control's numbers on that run's final graph (walk_reference
CONTROLS), beside their limits. The benchmark's own runs never plant a
fault or read a control.

  python3 benchmarks/chip/control.py --workload deepwalk-er.update \
      --seeds 1,2,3 --faults none,mav_deletes_only --controls bf16_draw \
      --seconds 1
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none")
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    spec = bench.load_spec()
    run.raise_compiler_stack()
    import jax  # noqa: F401  (after the compiler flag)
    import faults
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            controls = {}
            with faults.plant(fault), faults.reference_controls(
                    [c for c in args.controls.split(",") if c], seed,
                    controls):
                res = run.run_cell(spec, args.workload, seed, args.seconds,
                                   False)
            if res is None:
                return 3
            out = json.loads(res[0])
            limits = {k: v["limit"] for k, v in out["checks"].items()}
            print(json.dumps({
                "fault": fault, "seed": seed, "correct": out["correct"],
                "attempted": out["attempted"],
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "controls": {k: [v, limits[k.split(".", 1)[1]]]
                             for k, v in controls.items()},
                "metrics": {k: v["value"] for k, v in
                            out["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
