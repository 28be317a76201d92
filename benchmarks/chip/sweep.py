#!/usr/bin/env python3
"""Knee sweep of an open-loop read mix on a configuration, on the chip: set
the snapshot up once, then offer the mix's requests at each rate for --seconds and print
one JSON line per rate: latency percentiles from the due times, the
device-and-host busy share (summed service time over the window), and
whether the backlog grew (the last fifth's median latency against the
first fifth's). The knee is the highest rate whose backlog does not grow.

  python3 benchmarks/chip/sweep.py --config deepwalk-er \
      --traffic point-reads --seed 5 --rates 100,200,400 --seconds 10
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    run.raise_compiler_stack()
    import jax
    run.enable_compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    spec = bench.load_spec()
    cfg = bench.load_config(spec, args.config)
    mix = bench.load_traffic(args.traffic)
    ref = bench.load_reference(cfg)
    import system as sysm
    snap = run.read_setup(jax, sysm, ref, cfg, mix, args.seed)
    cap = mix["walks_of_capacity"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        sched = traffic.read_schedule(m, args.seconds, args.seed + i)
        reqs = traffic.read_requests(m, sched, snap["walks"],
                                     cfg["n_walks_per_vertex"],
                                     args.seed + i)
        for kind in m["mix"]:
            if kind in sched["kind"]:
                run.serve_one(snap["svc"], cap, kind,
                              reqs[sched["kind"].index(kind)])
        t0, due, done, _, _, errors, _ = run.open_loop(
            jax, snap["svc"], cap, sched, reqs, set())
        lat = bench.open_loop_latencies(due, done, t0 + args.seconds + 60)
        start, prev = [], t0
        for u, d in zip(due, done):
            start.append(max(u, prev))
            prev = d if d is not None else prev
        service = sum(d - s for s, d in zip(start, done) if d is not None)
        fifth = max(1, len(lat) // 5)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat), "failed": len(errors),
            "p50_ms": 1e3 * bench.percentile(lat, 50),
            "p95_ms": 1e3 * bench.percentile(lat, 95),
            "max_ms": 1e3 * max(lat),
            "busy_share": service / (max(d for d in done if d) - t0),
            "mean_service_ms": 1e3 * service / len(lat),
            "first_fifth_median_ms": 1e3 * statistics.median(lat[:fifth]),
            "last_fifth_median_ms": 1e3 * statistics.median(lat[-fifth:]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
