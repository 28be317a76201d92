"""Paper Fig. 9 + Fig. 10: scalability in batch size and in graph size,
including the from-scratch-regeneration floor (the paper's black line) —
plus the device-count scaling curve of the explicitly partitioned engine.

Two driver columns per cell (results in BENCH_SCALING.json):

  * "per_batch"  — the legacy per-batch driver (one jitted call per update;
    what the seed-era bench measured) for wharf and the IncrementalIndex
    baseline;
  * "pipelined"  — the PR-2 `run_stream` scan driver: the whole
    [n_batches, batch] stream inside ONE jitted scan (DESIGN.md §5), the
    production streaming path. Scaling claims are read off this column;
    per_batch stays as the dispatch-overhead reference.

The "device_scaling" section runs the shard_map engine (distr/sharded.py)
at 1/2/4/8 devices on a mixed insert+delete stream against the single-host
`run_stream` reference. On TPU every row runs in this process over
`jax.devices()[:n]` (one process holds the chips); on the CPU each row is a
subprocess with that many forced host devices (XLA's host-device count is
process-global). HONEST CPU CAVEAT: forced
host devices time-slice the same CPU cores, so this curve measures the
collective/partition OVERHEAD of the explicit sharding, not parallel
speedup — speedups < 1x are expected and recorded as-is; real scaling
needs real accelerators.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

# standalone invocation (`python benchmarks/bench_scaling.py --smoke`):
# mirror run.py's path bootstrap
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks import common
from benchmarks.common import (BenchGraph, DEFAULT_CFG, build_engines, emit,
                               merge_json, scratch_throughput,
                               stream_throughput, update_throughput)

STREAM_BATCHES = 4


def _wharf_factory(bg: BenchGraph, cfg):
    def make():
        _, engines = build_engines(bg, cfg, which=("wharf",))
        return engines["wharf"]
    return make


def _cell(bg: BenchGraph, batch: int, label: str) -> dict:
    """One (graph, batch-size) cell: legacy per-batch cells for wharf + ii,
    plus the pipelined run_stream cell for wharf."""
    out = {}
    # fresh engines per cell: merge cadence must not leak across
    _, engines = build_engines(bg, DEFAULT_CFG, which=("wharf", "ii"))
    for ename, eng in engines.items():
        wps, lat, aff = update_throughput(eng, bg, batch)
        wps, lat = float(wps), float(lat)  # device scalars -> JSON
        emit(f"{label}/{ename}", lat, f"walks_per_s={wps:.0f}")
        out[ename] = {"driver": "per_batch",
                      "walks_per_s": round(wps, 1),
                      "us_per_walk": round(lat, 2)}
    wps, lat, aff = stream_throughput(_wharf_factory(bg, DEFAULT_CFG), bg,
                                      batch, n_batches=STREAM_BATCHES)
    wps, lat = float(wps), float(lat)
    emit(f"{label}/wharf_pipelined", lat,
         f"walks_per_s={wps:.0f};n_batches={STREAM_BATCHES}")
    out["wharf_pipelined"] = {"driver": "run_stream",
                              "n_batches": STREAM_BATCHES,
                              "walks_per_s": round(wps, 1),
                              "us_per_walk": round(lat, 2)}
    return out


def _measure(mode: str, n_shards: int, log2_n: int, n_edges: int,
             n_batches: int, ins: int, dels: int, cap: int) -> dict:
    """One device-scaling row, timed in this process: the single-host
    `run_stream` reference or the sharded engine over the first `n_shards`
    devices. Returns {"dt": seconds of the second (warm) stream,
    "affected": walks re-walked}."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build_graph
    from repro.core import generate_corpus
    from repro.core.corpus import WalkConfig
    from repro.core.update import WalkEngine
    from repro.data.streams import mixed_edge_stream

    bg = BenchGraph(log2_n=log2_n, n_edges=n_edges)
    cfg = WalkConfig(n_walks_per_vertex=2, length=10)
    g = build_graph(bg)
    store = generate_corpus(jax.random.PRNGKey(1), g, cfg)
    i_s, i_d, d_s, d_d = mixed_edge_stream(
        jax.random.PRNGKey(2), n_batches, ins, dels, bg.log2_n)
    key = jax.random.PRNGKey(9)

    if mode == "single_host":
        def once():
            eng = WalkEngine(graph=jax.tree.map(jnp.array, g),
                             store=jax.tree.map(jnp.array, store), cfg=cfg,
                             rewalk_capacity=cap, max_pending=8)
            t0 = time.perf_counter()
            eng.run_stream(key, i_s, i_d, d_s, d_d)
            jax.block_until_ready(eng.state.store.code)
            dt = time.perf_counter() - t0
            assert not eng.mav_overflowed
            return dt, int(eng.total_affected)
    else:
        import dataclasses

        from repro.distr.sharded import (ShardSpec, shard_state,
                                         sharded_run_stream)
        assert jax.device_count() >= n_shards, jax.devices()
        spec = ShardSpec.create(n_shards, bg.n, store.size, g.codes.shape[0],
                                cap)
        # skew safety: rmat hubs concentrate on one shard, so bound the
        # per-shard MAV gather by T (never overflows) like the reference
        spec = dataclasses.replace(spec, mav_capacity=store.size)
        base = shard_state(g, store, spec, cap, max_pending=8)

        def once():
            stacked = jax.tree.map(jnp.array, base)  # runs donate their copy
            t0 = time.perf_counter()
            stacked, aff = sharded_run_stream(
                stacked, key, i_s, i_d, d_s, d_d, cfg=cfg, spec=spec,
                capacity=cap, max_pending=8)
            jax.block_until_ready(stacked.store.code)
            dt = time.perf_counter() - t0
            assert not bool(stacked.overflow.any()), \
                "sharded capacity overflow"
            return dt, int(stacked.total_affected[0])

    once()  # compile pass
    dt, aff = once()
    return {"dt": dt, "affected": aff}


# CPU only: one subprocess per device count, since XLA fixes the forced
# host-device count at initialization
_DEVICE_SUB = r"""
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {src!r})
import repro.core  # x64
from benchmarks.bench_scaling import _measure
print(json.dumps(_measure({mode!r}, {n_shards}, **{workload!r})))
"""


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _device_row(mode: str, n_shards: int, workload: dict) -> dict:
    if _on_tpu():
        # one process holds the chips: rows run here over jax.devices()[:n]
        out = _measure(mode, n_shards, **workload)
    else:
        code = _DEVICE_SUB.format(root=_ROOT, src=os.path.join(_ROOT, "src"),
                                  mode=mode, n_shards=n_shards,
                                  workload=workload)
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{max(n_shards, 1)}")
        # the parent runs on the CPU, so the child does too (skipping
        # accelerator plugin discovery, whose retry backoff can stall
        # subprocesses for minutes on accelerator-free hosts)
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             cwd=_ROOT, timeout=3600)
        if res.returncode != 0:
            raise RuntimeError(f"device-scaling subprocess failed "
                               f"({mode}, {n_shards}):\n{res.stderr[-2000:]}")
        out = json.loads(res.stdout.strip().splitlines()[-1])
    n_batches = workload["n_batches"]
    return {"walks_per_s": round(out["affected"] / out["dt"], 1),
            "updates_per_s": round(n_batches / out["dt"], 2),
            "us_per_walk": round(1e6 * out["dt"] / max(out["affected"], 1),
                                 2),
            "affected": out["affected"]}


def device_scaling() -> dict:
    """Sharded-engine scaling at 1/2/4/8 forced host devices vs the
    single-host driver, on a mixed insert+delete stream."""
    if common.SMOKE:
        workload = dict(log2_n=10, n_edges=4_000, n_batches=3, ins=200,
                        dels=50, cap=1024)
    else:
        # ~1M directed edge codes after both directions; T = 2.6M triplets
        workload = dict(log2_n=17, n_edges=500_000, n_batches=4, ins=10_000,
                        dels=2_000, cap=1 << 14)
    rows = {}
    ref = _device_row("single_host", 1, workload)
    emit("device_scaling/single_host", ref["us_per_walk"],
         f"walks_per_s={ref['walks_per_s']:.0f}")
    rows["single_host"] = dict(ref, devices=1, driver="run_stream")
    counts = (1, 2, 4, 8)
    if _on_tpu():
        import jax
        counts = tuple(c for c in counts if c <= jax.device_count())
    for s in counts:
        row = _device_row("sharded", s, workload)
        row["speedup_vs_single_host"] = round(
            row["walks_per_s"] / max(ref["walks_per_s"], 1e-9), 3)
        emit(f"device_scaling/shards_{s}", row["us_per_walk"],
             f"walks_per_s={row['walks_per_s']:.0f};"
             f"speedup={row['speedup_vs_single_host']}")
        rows[f"shards_{s}"] = dict(row, devices=s,
                                   driver="sharded_run_stream")
    return {
        "workload": workload,
        "caveat": (
            "forced host devices time-slice the SAME CPU cores: this curve "
            "measures the explicit partition's collective overhead "
            "(all_to_all handoff + pmin combine per step), not parallel "
            "speedup — sub-1x speedups are expected on CPU and recorded "
            "honestly; real scaling needs one accelerator per shard"),
        "rows": rows,
    }


def run():
    batches = (125, 250, 500, 1000)
    sizes = (10, 11, 12, 13)
    if common.SMOKE:
        batches = (125, 500)
        sizes = (10, 11)

    results = {"fig9_batchsize": {}, "fig10_graphsize": {}}

    # -- Fig 9: batch-size scaling on the orkut-like graph
    bg = BenchGraph(log2_n=11, n_edges=40_000)
    g, _ = build_engines(bg, DEFAULT_CFG, which=())
    floor = scratch_throughput(g, DEFAULT_CFG)
    emit("fig9_floor_scratch", 0.0, f"walks_per_s={floor:.0f}")
    results["fig9_floor_scratch_walks_per_s"] = round(floor, 1)
    for batch in batches:
        cell = _cell(bg, batch, f"fig9_batchsize/b{batch}")
        for v in cell.values():
            v["beats_scratch"] = v["walks_per_s"] > floor
        results["fig9_batchsize"][f"b{batch}"] = cell

    # -- Fig 10: graph-size scaling on er-k graphs (uniform degree)
    for log2_n in sizes:
        bg = BenchGraph(log2_n=log2_n, n_edges=2 ** log2_n * 8,
                        a=0.25, b=0.25, c=0.25, d=0.25)
        results["fig10_graphsize"][f"er{log2_n}"] = _cell(
            bg, 500, f"fig10_graphsize/er{log2_n}")

    # -- device-count scaling of the explicitly partitioned engine
    results["device_scaling"] = device_scaling()

    results["note"] = (
        "per_batch = legacy one-jitted-call-per-update driver; "
        "wharf_pipelined = run_stream scan driver (whole stream in one "
        "jitted scan, DESIGN.md §5) — the production path Fig. 9/10 claims "
        "are read from; device_scaling = shard_map engine "
        "(distr/sharded.py) at forced host-device counts, see its caveat")
    merge_json("BENCH_SCALING.json", results)
    return results


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="quick mode (results land in "
                         "BENCH_SCALING.smoke.json)")
    if ap.parse_args().smoke:
        common.SMOKE = True
    run()
