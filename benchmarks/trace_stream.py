#!/usr/bin/env python3
"""Device-time breakdown of one stream batch of chip_smoke.py's deployment.

  python benchmarks/trace_stream.py [--seed 0]

Builds chip_smoke's main deployment (order 1, metrics and the divergence
auditor on, on-demand merges) at 2^15 and 2^14 vertices, compiles its
build and a one-batch run_stream step concurrently, and runs one batch of
10k inserts + 2k deletes at each, timed on the host clock. At 2^15 it then
runs a second batch (the first to merge the pending block) under the JAX
profiler, reduces the trace's device plane to the device busy time and the
self time of each XLA op (largest first), and times one whole-corpus
walk-matrix read. One JSON object per line; the reduced breakdown also
goes to chiprun_out/trace_stream/breakdown.json, and the raw trace beside
it when it is small. Needs a TPU: exits 2 without one.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

import chip_smoke as smoke  # noqa: E402
from repro.core.corpus import WalkConfig  # noqa: E402
from repro.launch.compile_cache import (enable_compile_cache,  # noqa: E402
                                        raise_compiler_stack)

TRACE_LOG2_N = 15          # one step above chip_smoke.LOG2_N
TIME_LOG2_N = (14,)        # sizes timed without a trace
TOP = 40
OUT = os.path.join(ROOT, "chiprun_out", "trace_stream")


def device_breakdown(xplane: str, top: int) -> dict:
    """Busy time and per-op self time (ops named by their HLO text) of the
    first TPU's "XLA Ops" line.

    Busy is the union of the op intervals; an op's self time is its
    duration less that of the ops nested inside it (a while loop's body)."""
    data = jax.profiler.ProfileData.from_file(xplane)
    plane = next(p for p in data.planes if p.name.startswith("/device:TPU:0"))
    line = next(x for x in plane.lines if x.name == "XLA Ops")
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events), key=lambda x: (x[0], -x[1]))
    self_ns = collections.Counter()
    calls = collections.Counter()
    busy, stack, cover_end = 0.0, [], float("-inf")
    for s, e, name in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1][2]] -= min(e, stack[-1][1]) - s
        self_ns[name] += e - s
        calls[name] += 1
        stack.append((s, e, name))
        if e > cover_end:
            busy += e - max(s, cover_end)
            cover_end = e
    window = (max(e for _, e, _ in evs) - evs[0][0]) if evs else 0
    return {
        "ops": len(evs),
        "device_window_ns": window,
        "device_busy_ns": busy,
        "top_ops": [dict(name=n, self_ns=ns, calls=calls[n])
                    for n, ns in self_ns.most_common(top)],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    raise_compiler_stack()   # before the backend starts
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"trace_stream: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    smoke.emit(phase="setup", compile_cache=enable_compile_cache(),
               kind=dev.device_kind, count=len(jax.devices()))
    deps = {n: smoke.Deployment(n, WalkConfig(
        smoke.N_WALKS_PER_VERTEX, smoke.LENGTH, metrics=True, audit_k=4),
        smoke.MAX_PENDING) for n in [TRACE_LOG2_N, *TIME_LOG2_N]}
    jobs = []
    for n, dep in deps.items():
        js, (_, _, state, ov) = smoke.stream_jobs(dep, f"n{n}", 1)
        jobs += js
        if n == TRACE_LOG2_N:
            jobs += [smoke.overlay_job(state, f"n{n}"),
                     smoke.walk_matrix_job(ov, f"n{n}")]
    smoke.warm_up(jobs)

    os.makedirs(OUT, exist_ok=True)
    for n, dep in sorted(deps.items(), key=lambda kv: kv[0] != TRACE_LOG2_N):
        graph, store = smoke.build(jax.random.PRNGKey(args.seed),
                                   **dep.build_kw())
        engine = dep.engine(graph, store)
        del graph, store
        smoke.run_window(engine, args.seed, 10, 1, f"n{n}/batch1")
        if n != TRACE_LOG2_N:
            del engine
            continue
        with tempfile.TemporaryDirectory() as tmp:
            t0 = time.perf_counter()
            with jax.profiler.trace(tmp):
                smoke.run_window(engine, args.seed, 11, 1, f"n{n}/traced")
            wall = time.perf_counter() - t0
            xplane = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                               recursive=True)[0]
            bd = device_breakdown(xplane, TOP)
            if os.path.getsize(xplane) < 32 << 20:
                shutil.copy(xplane, os.path.join(OUT, "raw.xplane.pb"))
        bd.update(log2_n=n, traced_wall_s=wall)
        with open(os.path.join(OUT, "breakdown.json"), "w") as f:
            json.dump(bd, f, indent=1)
        smoke.emit(phase="trace", **{k: bd[k] for k in (
            "log2_n", "traced_wall_s", "device_window_ns", "device_busy_ns",
            "ops")})
        for op in bd["top_ops"]:
            smoke.emit(phase="op", **op)
        with smoke.timed(f"n{n}/walk_matrix") as out:
            wm = smoke.batched.walk_matrix_all(
                engine.overlay(), n_w=smoke.N_WALKS_PER_VERTEX,
                backend=smoke.packed_store.get_default_backend())
            out["shape"] = list(jax.block_until_ready(wm).shape)
        del engine, wm
    smoke.emit(phase="done", peak_bytes_in_use=smoke.peak_bytes())
    return 0


if __name__ == "__main__":
    sys.exit(main())
