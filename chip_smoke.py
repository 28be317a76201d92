#!/usr/bin/env python3
"""Chip smoke run: the streaming walk engine end to end on one TPU chip.

One process, phases in sequence, each through the entry points a user calls
and each freeing the device state of the one before it:

  compile   every large program of the run, compiled concurrently (XLA
            compiles off the GIL; the phases then reuse them): the main
            phase's up front, the order-2 and maintainer ones while the
            main phase runs
  build     R-MAT graph (a=.5, b=c=.1, d=.3, average degree ~100) and the
            walk corpus (10 walks per vertex, length 80) at the deployment
            size
  kernels   pallas FINDNEXT and intersect outputs bit-identical to the
            xla-ref backends on the same queries (a few thousand, on the
            fresh store and graph)
  stream    WalkEngine.run_stream over batches of 10k inserts + 2k
            deletes, order 1, on-demand merges: a cold call over 8
            batches, then a warm one over 2
  validity  sampled walks valid against the final graph; divergence auditor
  serve     every WalkQueryService query kind, live and on a pin()ed
            snapshot
  order2    node2vec (p=0.5, q=2) factorized-sampler window through
            run_stream (intersect kernel)
  maintain  EmbeddingMaintainer window (SGNS kernel)

Every earlier line is one JSON object; the last line is the verdict
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}. The
script exits non-zero, without the verdict, when JAX finds no TPU or when
any check fails. Graph and streams come from --seed.

  python chip_smoke.py                  # one chip
  python chip_smoke.py --four-chips     # sharded engine on a 4-chip host vs
                                        # single-host run_stream, only
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import faulthandler
import gc
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core  # noqa: E402,F401  (turns x64 on, as the engine runs)
from repro.core import StreamingGraph, generate_corpus, packed_store  # noqa: E402
from repro.core.corpus import WalkConfig, walk_start_vertex  # noqa: E402
from repro.core import overlay as overlay_mod  # noqa: E402
from repro.core.overlay import Overlay  # noqa: E402
from repro.core.update import (EngineState, WalkEngine,  # noqa: E402
                               _run_stream_jit, _run_stream_obs_jit)
from repro.core.walkers import WalkModel, _neighbor_window  # noqa: E402
from repro.data.streams import mixed_edge_stream, rmat_edges  # noqa: E402
from repro.downstream.maintainer import (EmbeddingMaintainer,  # noqa: E402
                                         MaintainerConfig,
                                         _maintain_stream_jit,
                                         init_maintainer)
from repro.kernels import intersect, sgns  # noqa: E402
from repro.launch.compile_cache import (enable_compile_cache,  # noqa: E402
                                        raise_compiler_stack)
from repro.obs.export import summary  # noqa: E402
from repro.obs.metrics import StreamMetrics  # noqa: E402
from repro.serve import batched  # noqa: E402
from repro.serve.walk_queries import WalkQueryService  # noqa: E402

U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32

# the deployment (paper §7 defaults; only the vertex count is cut, from
# 2^20: CHANGES.md gives each cut and its reason)
LOG2_N = 14                # main phase vertex count (log2)
MAX_PENDING = 1            # pending version blocks before on-demand merge
SMALL_LOG2_N = 12          # order-2 and maintainer windows
FOUR_LOG2_N = 10           # --four-chips
N_WALKS_PER_VERTEX = 10
LENGTH = 80
BATCH_INSERTS = 10_000
BATCH_DELETES = 2_000
N_BATCHES = 8              # the main phase's cold window
WARM_BATCHES = 2           # its warm window
AVG_DEGREE = 100
SAMPLE_WALKS = 4096
SMALL_BATCHES = 1          # order-2 and maintainer windows
FOUR_BATCHES = 4           # --four-chips
O2_MODEL = WalkModel(order=2, p=0.5, q=2.0, sampler="factorized", dmax=128)


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


@contextlib.contextmanager
def timed(phase: str, **extra):
    """Wall time of a phase (blocking work inside ends in a host read)."""
    t0 = time.perf_counter()
    out = dict(extra)
    yield out
    emit(phase=phase, seconds=time.perf_counter() - t0, **out)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ------------------------------------------------- the run's programs
#
# Module-level jitted functions: the warm-up compiles these very objects,
# so the phases find them compiled.


@partial(jax.jit, static_argnames=("log2_n", "edge_capacity", "cfg"))
def build(key, log2_n: int, edge_capacity: int, cfg: WalkConfig):
    """R-MAT graph with ~AVG_DEGREE/2 undirected edges per vertex and its
    from-scratch walk corpus, made on the device from the key."""
    n = 1 << log2_n
    src, dst = rmat_edges(jax.random.fold_in(key, 1), n * AVG_DEGREE // 2,
                          log2_n)
    graph = StreamingGraph.from_edges(src, dst, n, edge_capacity)
    return graph, generate_corpus(jax.random.fold_in(key, 2), graph, cfg)


@partial(jax.jit, static_argnames=("backend",))
def traverse(view, w, backend=None):
    """Walks w ([k] u32 ids) through a store or overlay: [k, LENGTH]."""
    return view.traverse(w, walk_start_vertex(w, N_WALKS_PER_VERTEX),
                         LENGTH - 1, backend=backend)


@jax.jit
def invalid_transitions(graph, paths):
    """The tests/_walk_checks.py contract: each consecutive pair (a, b) of
    a walk is an edge of the graph, or the isolated-vertex self-loop."""
    a = paths[:, :-1].reshape(-1)
    b = paths[:, 1:].reshape(-1)
    ok = graph.has_edge(a, b) | ((a == b) & (graph.degree(a.astype(I32))
                                             == 0))
    return jnp.sum(~ok, dtype=I32)


@partial(jax.jit, static_argnames=("backend",))
def find_next(store, v, w, p, backend):
    return store.find_next(v, w, p, backend=backend)


@jax.jit
def windows(graph, v):
    return _neighbor_window(graph, v, O2_MODEL.dmax)[0]


@partial(jax.jit, static_argnames=("backend",))
def factorized(nv, np_, prev, ug, ur, backend):
    return intersect.factorized_next(nv, np_, prev, ug, ur, O2_MODEL.p,
                                     O2_MODEL.q, backend=backend)


edge_stream = jax.jit(mixed_edge_stream, static_argnames=(
    "n_batches", "n_ins", "n_del", "log2_n"))


def stream_kw(log2_n: int, n_batches: int):
    return dict(n_batches=n_batches, n_ins=BATCH_INSERTS,
                n_del=BATCH_DELETES, log2_n=log2_n)


def stream(seed: int, salt: int, log2_n: int, n_batches: int):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), salt)
    return edge_stream(key, **stream_kw(log2_n, n_batches))


# ---------------------------------------------------------- warm-up


class Deployment:
    """One engine configuration of the run: its sizes and the abstract
    shapes of what its programs take."""

    def __init__(self, log2_n: int, cfg: WalkConfig, max_pending: int):
        self.log2_n = log2_n
        self.n = 1 << log2_n
        self.cfg = cfg
        self.edge_capacity = self.n * 128
        self.max_pending = max_pending
        # every walk of an R-MAT graph at these scales crosses a touched
        # vertex in each 10k-edge batch: |MAV| = n_walks, so the rewalk
        # capacity is the whole corpus (the per-batch counts confirm it)
        self.capacity = self.n * N_WALKS_PER_VERTEX
        self.triplets = self.capacity * LENGTH

    def build_kw(self):
        return dict(log2_n=self.log2_n, edge_capacity=self.edge_capacity,
                    cfg=self.cfg)

    def stream_kw(self):
        return dict(cfg=self.cfg, capacity=self.capacity,
                    mav_capacity=self.triplets,
                    max_pending=self.max_pending, merge_policy="on-demand",
                    merge_impl="interleave", with_masks=False)

    def engine(self, graph, store):
        return WalkEngine(graph=graph, store=store, cfg=self.cfg,
                          merge_policy="on-demand",
                          rewalk_capacity=self.capacity,
                          max_pending=self.max_pending,
                          mav_capacity=self.triplets)


def shaped(tree):
    """Abstract arguments as the phases pass them: uncommitted arrays, so
    the warm-up's compiles are the ones dispatch looks up."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def stream_specs(n_batches: int):
    s = jax.ShapeDtypeStruct
    return shaped((s((n_batches, 2), U32),
                   s((n_batches, BATCH_INSERTS), U32),
                   s((n_batches, BATCH_INSERTS), U32),
                   s((n_batches, BATCH_DELETES), U32),
                   s((n_batches, BATCH_DELETES), U32)))


def stream_jobs(dep: Deployment, label: str, n_batches: int):
    """(name, jitted, args, kwargs) of a deployment's build, edge stream and
    run_stream step over n_batches; plus the abstract graph, store and
    overlay."""
    key = shaped(jax.ShapeDtypeStruct((2,), U32))
    graph, store = shaped(jax.eval_shape(partial(build, **dep.build_kw()),
                                         key))
    state = shaped(jax.eval_shape(lambda g, s: EngineState.create(
        g, s, dep.max_pending, dep.capacity * LENGTH), graph, store))
    ov = shaped(jax.eval_shape(Overlay.build, state.store, state.pending))
    if dep.cfg.metrics:
        metrics = shaped(jax.eval_shape(StreamMetrics.empty))
        step = (f"{label}/stream", _run_stream_obs_jit,
                (state, metrics, *stream_specs(n_batches)), dep.stream_kw())
    else:
        step = (f"{label}/stream", _run_stream_jit,
                (state, *stream_specs(n_batches)), dep.stream_kw())
    jobs = [(f"{label}/build", build, (key,), dep.build_kw()),
            (f"{label}/edges", edge_stream, (key,),
             stream_kw(dep.log2_n, n_batches)), step]
    return jobs, (graph, store, state, ov)


def overlay_job(state, label: str):
    """The overlay index build that every read of a live engine runs."""
    flat = jax.tree.map(lambda a: jax.ShapeDtypeStruct((a.size,), a.dtype),
                        state.pending)
    return (f"{label}/overlay", overlay_mod._build_jit,
            (state.store, flat.owner, flat.code, flat.epoch, flat.slot), {})


def walk_matrix_job(ov, label: str):
    return (f"{label}/walk_matrix", batched.walk_matrix_all, (ov,),
            dict(n_w=N_WALKS_PER_VERTEX,
                 backend=packed_store.get_default_backend()))


def warm_jobs(dep: Deployment, label: str, mcfg=None):
    """(name, jitted, args, kwargs) for a deployment's large programs."""
    n_batches = N_BATCHES if dep.cfg.metrics else SMALL_BATCHES
    jobs, (graph, store, state, ov) = stream_jobs(dep, label, n_batches)
    if mcfg is not None:
        key = jobs[0][2][0]
        mstate = shaped(jax.eval_shape(partial(init_maintainer, cfg=mcfg),
                                       key, graph, store))
        keys, *edges = stream_specs(SMALL_BATCHES)
        return jobs[:2] + [(f"{label}/stream", _maintain_stream_jit,
                            (mstate, keys, keys, *edges, mcfg,
                             mcfg.mav_capacity), {})]
    w = shaped(jax.ShapeDtypeStruct((SAMPLE_WALKS,), U32))
    jobs += [overlay_job(state, label),
             (f"{label}/traverse", traverse, (ov, w), {}),
             (f"{label}/validity", invalid_transitions,
              (graph, shaped(jax.ShapeDtypeStruct((SAMPLE_WALKS, LENGTH),
                                                  U32))), {})]
    if dep.cfg.metrics:
        warm, _ = stream_jobs(dep, f"{label}/warm", WARM_BATCHES)
        jobs += warm[1:] + [walk_matrix_job(ov, label)]
        jobs += kernel_jobs(graph, store, label) + serve_jobs(ov, dep, label)
    return jobs


def kernel_jobs(graph, store, label: str):
    """The kernels phase's programs (pallas and xla-ref twins)."""
    k = 64
    q = shaped(jax.ShapeDtypeStruct((k * (LENGTH - 1),), U32))
    w = shaped(jax.ShapeDtypeStruct((k,), U32))
    win = shaped(jax.ShapeDtypeStruct((4096, O2_MODEL.dmax), U32))
    u = shaped(jax.ShapeDtypeStruct((4096,), F32))
    v = shaped(jax.ShapeDtypeStruct((4096,), U32))
    jobs = [(f"{label}/xla-ref traverse", traverse, (store, w),
             dict(backend="xla-ref")),
            (f"{label}/windows", windows, (graph, v), {})]
    for b in ("pallas", "xla-ref"):
        jobs += [(f"{label}/{b} find_next", find_next, (store, q, q, q),
                  dict(backend=b)),
                 (f"{label}/{b} intersect", factorized,
                  (win, win, v, u, u), dict(backend=b))]
    return jobs


def serve_jobs(ov, dep: Deployment, label: str):
    """The serving phase's query programs at its bucketed batch shapes."""
    s = jax.ShapeDtypeStruct
    ids16, ids8 = s((16,), I32), s((8,), I32)
    u16 = s((16,), U32)
    wm = s((dep.capacity, LENGTH), U32)
    table = s((dep.n, 64), F32)
    return [
        (f"{label}/serve next", batched.find_next_batch, (ov, u16, u16, u16),
         dict(backend=packed_store.get_default_backend(),
              window=packed_store.get_default_window())),
        (f"{label}/serve walks_of", batched.walks_of_batch, (ov, ids16),
         dict(capacity=1024)),
        (f"{label}/serve neighborhoods", batched.neighborhoods_from_matrix,
         (wm, ids16), dict(n_w=N_WALKS_PER_VERTEX, hops=2)),
        (f"{label}/serve ppr", batched.ppr_table, (wm,),
         dict(n_vertices=dep.n, restart_prob=0.2)),
        (f"{label}/serve ppr rows", batched.gather_rows,
         (s((dep.n, dep.n), F32), ids8), {}),
        (f"{label}/serve normalize", batched.normalize_rows, (table,), {}),
        (f"{label}/serve topk", batched.embedding_topk, (table, ids16),
         dict(k=10)),
    ]


def compile_async(pool, jobs) -> dict:
    """Start compiling every job on the pool (XLA compiles off the GIL),
    the longest compiles (stream steps, corpus builds) first; returns
    name -> future of (compiled, seconds)."""
    def one(fn, args, kwargs):
        t0 = time.perf_counter()
        exe = fn.lower(*args, **kwargs).compile()
        return exe, time.perf_counter() - t0

    jobs = sorted(jobs, key=lambda j: not j[0].endswith(("/stream",
                                                         "/build",
                                                         "/sharded")))
    return {name: pool.submit(one, fn, args, kwargs)
            for name, fn, args, kwargs in jobs}


def wait_compiled(futs: dict, phase: str = "compile") -> dict:
    """Wait for compiles started by compile_async; returns name ->
    compiled. The phase's seconds are the wait; each program's are its own
    compile. A failed compile raises here."""
    with timed(phase, programs_compiled=len(futs)) as out:
        done = {name: f.result() for name, f in futs.items()}
        out["programs"] = {name: secs for name, (_, secs) in done.items()}
    return {name: exe for name, (exe, _) in done.items()}


def compile_pool():
    """One compile thread per host core but one, which the phases keep."""
    return concurrent.futures.ThreadPoolExecutor(
        max(1, (os.cpu_count() or 8) - 1))


def warm_up(jobs) -> dict:
    """Compile every job concurrently; returns name -> compiled."""
    with compile_pool() as pool:
        return wait_compiled(compile_async(pool, jobs))


# ------------------------------------------------------------------ phases


def run_window(engine, seed, salt, n_batches, label, exe=None):
    """One run_stream call over n_batches mixed batches."""
    log2_n = engine.store.n_vertices.bit_length() - 1
    ins_s, ins_d, del_s, del_d = stream(seed, salt, log2_n, n_batches)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1000 + salt)
    if exe is not None:
        text = exe.as_text()
        mem = exe.memory_analysis()
        emit(phase=f"{label}/program",
             tpu_custom_call="tpu_custom_call" in text,
             argument_bytes=int(mem.argument_size_in_bytes),
             temp_bytes=int(mem.temp_size_in_bytes))
        check("tpu_custom_call" in text,
              f"{label}: no Pallas kernel in the compiled run_stream step")
    with timed(f"{label}/run") as out:
        aff = np.asarray(engine.run_stream(key, ins_s, ins_d, del_s, del_d))
        out["affected"] = aff.tolist()
        out["overflow"] = engine.mav_overflowed
    check(not out["overflow"], f"{label}: MAV gather overflow")
    check(int(aff.max()) <= engine.rewalk_capacity,
          f"{label}: |MAV| {int(aff.max())} > rewalk_capacity "
          f"{engine.rewalk_capacity}")


def phase_kernels(graph, store, seed: int, k: int = 64) -> None:
    """pallas vs xla-ref bit-identity on the chip, at a small query count
    against the deployment's fresh store (every slot live in the base):
    FINDNEXT for every slot of k sampled walks, and the factorized-sampler
    intersect on 4096 real neighbor windows."""
    w = jax.random.randint(jax.random.PRNGKey(seed + 5), (k,), 0,
                           store.n_walks).astype(U32)
    with timed("kernels") as out:
        paths = traverse(store, w, backend="xla-ref")
        q_w = jnp.repeat(w, LENGTH - 1)
        q_p = jnp.tile(jnp.arange(LENGTH - 1, dtype=U32), k)
        q_v = paths[:, :-1].reshape(-1)
        res = {b: [np.asarray(x) for x in find_next(store, q_v, q_w, q_p,
                                                     backend=b)]
               for b in ("pallas", "xla-ref")}
        out["findnext_queries"] = int(q_v.shape[0])
        out["findnext_found"] = int(res["xla-ref"][1].sum())
        out["findnext_bit_identical"] = all(
            np.array_equal(a, b) for a, b in zip(res["pallas"],
                                                 res["xla-ref"]))

        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
        q = 4096
        v = jax.random.randint(k1, (q,), 0, store.n_vertices).astype(U32)
        prev = jax.random.randint(k2, (q,), 0, store.n_vertices).astype(U32)
        nv, np_ = windows(graph, v), windows(graph, prev)
        u = jax.random.uniform(k3, (2, q), F32)
        ix = {b: [np.asarray(x) for x in factorized(nv, np_, prev, u[0],
                                                     u[1], backend=b)]
              for b in ("pallas", "xla-ref")}
        out["intersect_queries"] = q
        out["intersect_bit_identical"] = all(
            np.array_equal(a, b) for a, b in zip(ix["pallas"],
                                                 ix["xla-ref"]))
    # every query is a slot of a stored walk of the fresh store
    check(out["findnext_found"] == out["findnext_queries"],
          "xla-ref FINDNEXT missed a stored slot")
    check(out["findnext_bit_identical"], "pallas FINDNEXT differs from "
          "xla-ref")
    check(out["intersect_bit_identical"], "pallas intersect differs from "
          "xla-ref")


def sample_paths(engine, seed: int):
    w = jax.random.randint(jax.random.PRNGKey(seed + 7), (SAMPLE_WALKS,), 0,
                           engine.store.n_walks).astype(U32)
    return traverse(engine.overlay(), w)


def phase_main(dep: Deployment, seed: int, exes: dict) -> None:
    """build -> kernels -> stream (cold, warm) -> validity -> serve."""
    n = dep.n
    with timed("build", log2_n=dep.log2_n) as out:
        graph, store = build(jax.random.PRNGKey(seed), **dep.build_kw())
        out["n_vertices"] = n
        out["directed_edges"] = int(graph.num_edges)
        out["edge_capacity"] = dep.edge_capacity
        out["triplets"] = store.size
        out["n_walks"] = store.n_walks
        out["store_bytes"] = sum(int(x.nbytes) for x in
                                 jax.tree.leaves(store))
    phase_kernels(graph, store, seed)
    engine = dep.engine(graph, store)
    del graph, store
    emit(phase="sizes", rewalk_capacity=engine.rewalk_capacity,
         max_pending=dep.max_pending, mav_capacity=engine._mav_capacity(),
         pending_bytes=sum(int(x.nbytes) for x in
                           jax.tree.leaves(engine.state.pending)),
         batch_inserts=BATCH_INSERTS, batch_deletes=BATCH_DELETES,
         n_batches=N_BATCHES, warm_batches=WARM_BATCHES)
    run_window(engine, seed, 10, N_BATCHES, "stream/cold",
               exe=exes["main/stream"])
    run_window(engine, seed, 11, WARM_BATCHES, "stream/warm")

    with timed("validity", sample_walks=SAMPLE_WALKS) as out:
        paths = sample_paths(engine, seed)
        out["invalid_transitions"] = int(invalid_transitions(engine.graph,
                                                             paths))
        out["audit_invalid"] = int(summary(engine.metrics)["staleness"]
                                   ["audit"]["invalid"])
        out["peak_bytes_in_use"] = peak_bytes()
    check(out["invalid_transitions"] == 0, "invalid sampled walks")
    check(out["audit_invalid"] == 0, "divergence auditor reports invalid")

    with timed("serve") as out:
        svc = WalkQueryService(engine)
        rng = np.random.default_rng(seed)
        b = 16
        w = rng.integers(0, engine.store.n_walks, b).astype(np.uint32)
        p = rng.integers(0, LENGTH - 1, b).astype(np.uint32)
        wm = np.asarray(svc.walk_matrix())
        v = wm[w, p]
        seeds = rng.integers(0, n, b).astype(np.int32)
        svc.set_embedding_table(jax.random.normal(
            jax.random.PRNGKey(seed + 3), (n, 64), F32))

        def reads(snap):
            return {
                "next_vertices": svc.next_vertices(v, w, p, snapshot=snap),
                "walks_of": svc.walks_of(v.astype(np.int32), 1024,
                                         snapshot=snap),
                "neighborhoods": svc.neighborhoods(seeds, hops=2,
                                                   snapshot=snap),
                "walk_matrix": svc.walk_matrix(snapshot=snap),
                "ppr_rows": svc.ppr_rows(seeds[:4], snapshot=snap),
                "embedding_neighbors": svc.embedding_neighbors(seeds, k=10),
            }

        live = jax.device_get(reads(None))
        with svc.pin() as snap:
            pinned = jax.device_get(reads(snap))
        same = {k: all(np.array_equal(a, c) for a, c in
                       zip(jax.tree.leaves(live[k]),
                           jax.tree.leaves(pinned[k])))
                for k in live}
        nxt, found = live["next_vertices"]
        out["kinds"] = sorted(live)
        out["live_equals_pinned"] = same
        out["next_matches_walk_matrix"] = bool(
            np.all(found) and np.array_equal(nxt, wm[w, p + 1]))
        # every walk id walks_of returns visits the queried vertex
        out["walks_of_sound"] = bool(all(
            (wm[wid] == v[i]).any()
            for i in range(b) for wid in live["walks_of"][i] if wid >= 0))
    check(all(same.values()), f"pinned reads differ from live: {same}")
    check(out["next_matches_walk_matrix"], "next_vertices disagrees with "
          "the walk matrix")
    check(out["walks_of_sound"], "walks_of returned a walk that does not "
          "visit the vertex")
    emit(phase="memory", peak_bytes_in_use=peak_bytes())


def phase_order2(dep: Deployment, seed: int, exes: dict) -> None:
    with timed("order2/build", log2_n=dep.log2_n) as out:
        graph, store = build(jax.random.PRNGKey(seed + 20), **dep.build_kw())
        out["triplets"] = store.size
    engine = dep.engine(graph, store)
    del graph, store
    run_window(engine, seed + 20, 30, SMALL_BATCHES, "order2",
               exe=exes["order2/stream"])
    with timed("order2/validity") as out:
        paths = sample_paths(engine, seed + 20)
        out["invalid_transitions"] = int(invalid_transitions(engine.graph,
                                                             paths))
    check(out["invalid_transitions"] == 0, "order2: invalid sampled walks")


def phase_maintain(dep: Deployment, mcfg: MaintainerConfig,
                   seed: int) -> None:
    with timed("maintain/build", log2_n=dep.log2_n) as out:
        graph, store = build(jax.random.PRNGKey(seed + 40), **dep.build_kw())
        out["triplets"] = store.size
    m = EmbeddingMaintainer(graph=graph, store=store, cfg=mcfg,
                            key=jax.random.PRNGKey(seed + 41))
    del graph, store
    ins_s, ins_d, del_s, del_d = stream(seed + 40, 50, dep.log2_n,
                                        SMALL_BATCHES)
    with timed("maintain/run") as out:
        met = m.run_stream(jax.random.PRNGKey(seed + 42), ins_s, ins_d,
                           del_s, del_d)
        emb = np.asarray(m.embeddings)
        out["pairs_trained"] = m.pairs_trained
        out["loss_per_pair"] = float(np.asarray(met.loss_sum).sum()
                                     / max(m.pairs_trained, 1))
        out["overflow"] = m.mav_overflowed
        out["finite"] = bool(np.isfinite(emb).all())
    check(out["finite"] and out["pairs_trained"] > 0,
          "maintainer produced no finite training")
    check(not out["overflow"], "maintainer: MAV gather overflow")


def four_jobs(dep: Deployment, spec):
    """The four-chip check's programs: the single-host build, stream and
    merge, and the sharded stream on the 4-device mesh."""
    from repro.core.graph import StreamingGraph as G
    from repro.core.store import WalkStore
    from repro.core.update import _merge_state_jit
    from repro.distr.sharded import _make_sharded_run, shard_mesh
    key = shaped(jax.ShapeDtypeStruct((2,), U32))
    graph, store = shaped(jax.eval_shape(partial(build, **dep.build_kw()),
                                         key))
    state = shaped(jax.eval_shape(lambda g, s: EngineState.create(
        g, s, dep.max_pending, dep.capacity * LENGTH), graph, store))
    edges = stream_specs(FOUR_BATCHES)

    def shard_shapes():   # what shard_state stacks, per shard
        sc, n = spec.store_capacity, dep.n
        z = jnp.zeros
        g = G(z((spec.edge_capacity,), jnp.uint64), z((n + 1,), I32),
              z((), I32), n)
        chunks = -(-sc // 128)
        st = WalkStore(z((sc,), U32), z((sc,), jnp.uint64), z((sc,), U32),
                       z((n + 1,), I32), z((n,), U32), z((n,), U32),
                       z((chunks, 256), U32), *[z((chunks,), U32)] * 5,
                       z((dep.triplets,), U32), LENGTH, dep.capacity, n, 128)
        one = EngineState.create(g, st, dep.max_pending,
                                 dep.capacity * LENGTH)
        return jax.tree.map(lambda *ls: jnp.stack(ls), *[one] * 4)

    stacked = shaped(jax.eval_shape(shard_shapes))
    sharded = _make_sharded_run(shard_mesh(4), dep.cfg, spec, dep.capacity,
                                dep.max_pending, "on-demand")
    return [("four/build", build, (key,), dep.build_kw()),
            ("four/edges", edge_stream, (key,),
             stream_kw(dep.log2_n, FOUR_BATCHES)),
            ("four/stream", _run_stream_jit, (state, *edges),
             dep.stream_kw()),
            ("four/merge", _merge_state_jit, (state, dep.cfg, "interleave"),
             {}),
            ("four/sharded", sharded, (stacked, *edges), {})]


def phase_four_chips(seed: int, log2_n: int) -> None:
    """Sharded engine on a 4-device 'shard' mesh vs single-host run_stream
    on the same stream: graph codes, store triplets and slot_epoch
    bit-identical."""
    from repro.core.graph import SENTINEL
    from repro.core.store import PAD_EPOCH
    from repro.distr.sharded import ShardSpec, shard_state, sharded_run_stream
    check(len(jax.devices()) >= 4, "--four-chips needs 4 devices")
    dep = Deployment(log2_n, WalkConfig(N_WALKS_PER_VERTEX, LENGTH), 2)
    spec = ShardSpec.create(4, dep.n, dep.triplets, dep.edge_capacity,
                            dep.capacity)
    warm_up(four_jobs(dep, spec))
    with timed("four/build", log2_n=log2_n) as out:
        graph, store = build(jax.random.PRNGKey(seed + 60), **dep.build_kw())
        out["triplets"] = store.size
    ins_s, ins_d, del_s, del_d = stream(seed + 60, 70, log2_n,
                                        FOUR_BATCHES)
    key = jax.random.PRNGKey(seed + 61)
    with timed("four/single_host") as out:
        eng = dep.engine(jax.tree.map(jnp.array, graph),
                         jax.tree.map(jnp.array, store))
        ref_aff = np.asarray(eng.run_stream(key, ins_s, ins_d, del_s, del_d))
        eng.merge()
        out["overflow"] = eng.mav_overflowed
        ref = jax.device_get((eng.graph.codes, eng.store.owner,
                              eng.store.code, eng.store.epoch,
                              eng.store.slot_epoch))
    check(not out["overflow"], "single-host MAV overflow")
    del eng
    gc.collect()
    with timed("four/sharded", n_shards=4,
               devices=[str(d) for d in jax.devices()[:4]]) as out:
        stacked = shard_state(graph, store, spec, dep.capacity,
                              max_pending=dep.max_pending)
        del graph, store
        stacked, aff = sharded_run_stream(
            stacked, key, ins_s, ins_d, del_s, del_d, cfg=dep.cfg, spec=spec,
            capacity=dep.capacity, max_pending=dep.max_pending)
        ovf = bool(np.any(np.asarray(stacked.overflow)))
        out["overflow"] = ovf
        # the union of the shards' live rows in the single-host layout
        # (graph codes sorted; triplets sorted by (owner, code)), as
        # distr.sharded.unshard_state assembles it, on the host
        codes = np.asarray(stacked.graph.codes).reshape(-1)
        codes = np.sort(codes[codes != np.uint64(SENTINEL)])
        owner, code, epoch = (np.asarray(a).reshape(-1) for a in (
            stacked.store.owner, stacked.store.code, stacked.store.epoch))
        live = epoch != np.uint32(PAD_EPOCH)
        order = np.lexsort((code[live], owner[live]))
        got = (codes, owner[live][order], code[live][order],
               epoch[live][order], np.asarray(stacked.store.slot_epoch[0]))
    ref = (ref[0][ref[0] != np.uint64(SENTINEL)],) + ref[1:]
    same = {name: bool(np.array_equal(a, b)) for name, a, b in zip(
        ("graph_codes", "owner", "code", "epoch", "slot_epoch"), ref, got)}
    same["affected"] = bool(np.array_equal(ref_aff, np.asarray(aff)))
    emit(phase="four/compare", bit_identical=same)
    check(not ovf, "sharded capacity overflow")
    check(all(same.values()), f"sharded differs from single-host: {same}")


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-single-host check on 4 "
                         "chips")
    args = ap.parse_args()

    faulthandler.enable()
    libtpu_args = raise_compiler_stack()   # before the backend starts
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    emit(phase="setup", compile_cache=enable_compile_cache(),
         libtpu_init_args=libtpu_args,
         jax=jax.__version__, platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), host_cpus=os.cpu_count())
    backends = {"findnext": packed_store.get_default_backend(),
                "intersect": intersect.get_default_backend(),
                "sgns": sgns.get_default_backend()}
    emit(phase="backends", **backends)
    check(all(b == "pallas" for b in backends.values()),
          f"a registry did not resolve to pallas: {backends}")

    if args.four_chips:
        phase_four_chips(args.seed, FOUR_LOG2_N)
    else:
        main_dep = Deployment(LOG2_N, WalkConfig(
            N_WALKS_PER_VERTEX, LENGTH, metrics=True, audit_k=4),
            MAX_PENDING)
        o2_dep = Deployment(SMALL_LOG2_N, WalkConfig(
            N_WALKS_PER_VERTEX, LENGTH, model=O2_MODEL), 2)
        m_dep = Deployment(SMALL_LOG2_N,
                           WalkConfig(N_WALKS_PER_VERTEX, LENGTH), 2)
        mcfg = MaintainerConfig(
            walk=m_dep.cfg, n_vertices=m_dep.n, dim=64, max_pairs=1 << 16,
            rewalk_capacity=m_dep.capacity, max_pending=m_dep.max_pending,
            mav_capacity=m_dep.triplets)
        pool = compile_pool()
        try:
            # the main phase's programs first; the order-2 and maintainer
            # programs compile on the host while the main phase runs on
            # the chip
            exes = wait_compiled(compile_async(
                pool, warm_jobs(main_dep, "main")), "compile/main")
            rest = compile_async(pool, warm_jobs(o2_dep, "order2")
                                 + warm_jobs(m_dep, "maintain", mcfg))
            phase_main(main_dep, args.seed, exes)
            gc.collect()
            exes.update(wait_compiled(rest, "compile/rest"))
        finally:
            pool.shutdown(cancel_futures=True)
        phase_order2(o2_dep, args.seed, exes)
        gc.collect()
        phase_maintain(m_dep, mcfg, args.seed)
        gc.collect()
    emit(phase="done", peak_bytes_in_use=peak_bytes())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
