"""Benchmark harness entry point: one module per paper table/figure.
Prints `name,us_per_call,derived` CSV (harness contract) and writes
bench_results.csv. `--only <name>` runs a single module."""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks import common  # noqa: E402

MODULES = [
    "bench_throughput",   # Fig 6 + Fig 7
    "bench_walk",         # order-2 samplers: rejection vs factorized (§8)
    "bench_memory",       # Fig 8 + §7.5 DE + id distribution
    "bench_scaling",      # Fig 9 + Fig 10
    "bench_skew",         # Fig 11
    "bench_search",       # Fig 12
    "bench_merge",        # Fig 14 / App. A
    "bench_pmin",         # Fig 15 / App. B-C
    "bench_kernels",      # kernel micro-benches
    "bench_downstream",   # Fig 13 + Fig 1
    "bench_freshness",    # §7.6 closed loop: co-scheduled maintainer
    "bench_serve",        # §11 serving frontend under a live stream
]


# bench_throughput in smoke mode runs the pipelined-driver comparison plus
# the metrics-overhead "observability" cell (the CI observability smoke)
SMOKE_MODULES = ["bench_memory", "bench_search", "bench_walk",
                 "bench_throughput"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="bench_results.csv")
    ap.add_argument("--smoke", action="store_true",
                    help="quick mode: shrunken workloads, core modules only "
                         "(the CI benchmark smoke job)")
    ap.add_argument("--check-regressions", action="store_true",
                    help="after the selected modules finish, diff the "
                         "recorded BENCH_*.json cells against the committed "
                         "benchmarks/baselines/ under the sentinel "
                         "thresholds (fails on a gating regression)")
    ap.add_argument("--skip-benches", action="store_true",
                    help="run no bench modules (with --check-regressions: "
                         "sentinel-only over already-produced BENCH files)")
    args = ap.parse_args()

    from repro.launch.compile_cache import (enable_compile_cache,
                                            raise_compiler_stack)
    raise_compiler_stack()
    enable_compile_cache()
    if args.smoke:
        common.SMOKE = True
    if args.skip_benches:
        mods = []
    elif args.only is not None:
        # --only selects from the full module list (combined with --smoke it
        # runs that one module with shrunken workloads)
        mods = [m for m in MODULES if m == args.only]
        if not mods:
            sys.exit(f"unknown benchmark module {args.only!r}; "
                     f"expected one of {MODULES}")
    else:
        mods = SMOKE_MODULES if args.smoke else MODULES
    print("name,us_per_call,derived")
    failures = []
    for name in mods:
        t0 = time.time()
        print(f"# == {name} ==", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if not args.skip_benches:
        with open(args.out, "w") as f:
            f.write("name,us_per_call,derived\n")
            f.write("\n".join(common.ROWS) + "\n")
        print(f"# {len(common.ROWS)} rows -> {args.out}; "
              f"{len(failures)} failures")
    for n, e in failures:
        print(f"# FAILED {n}: {e}")
    if failures:
        sys.exit(1)
    if args.check_regressions:
        from benchmarks import check_regression
        sys.exit(check_regression.run_check(args.smoke))


if __name__ == "__main__":
    main()
