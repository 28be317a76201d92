"""The system under test, built from a configuration file: the streaming
graph, the walk corpus, `WalkEngine` and `WalkQueryService` of the
program, through the entry points a user calls. Nothing here measures or
decides `correct`.

Import after the run has set the compile cache and checked the chip: this
module imports the program, which turns x64 on.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

import repro.core  # noqa: F401  (x64 on, as the engine runs)
from repro.core import StreamingGraph, generate_corpus
from repro.core.corpus import WalkConfig
from repro.core.update import WalkEngine
from repro.core.walkers import WalkModel
from repro.serve.walk_queries import WalkQueryService


def walk_config(cfg: dict) -> WalkConfig:
    m = cfg["walk_model"]
    model = WalkModel(order=m["order"], p=m.get("p", 1.0), q=m.get("q", 1.0),
                      sampler=m.get("sampler", "rejection"),
                      dmax=m.get("dmax", 128),
                      n_trials=m.get("n_trials", 8))
    return WalkConfig(n_walks_per_vertex=cfg["n_walks_per_vertex"],
                      length=cfg["length"], model=model)


def edge_capacity(cfg: dict, mix: dict, n_batches: int) -> int:
    """The configuration's directed edge slots (a fixed number, so every
    cell of one configuration runs the same compiled programs), checked to
    hold the base graph and both directions of every insert the run can
    send."""
    n = 1 << cfg["log2_n"]
    need = n * cfg["avg_degree"] + 2 * mix["batch_inserts"] * n_batches
    if need > cfg["edge_capacity"]:
        raise ValueError(f"{n_batches} batches of {mix['batch_inserts']} "
                         f"inserts can overflow edge_capacity "
                         f"{cfg['edge_capacity']} (need {need})")
    return cfg["edge_capacity"]


@partial(jax.jit, static_argnames=("n", "capacity", "wcfg"))
def build(src, dst, key, n: int, capacity: int, wcfg: WalkConfig):
    """The graph from its edges and the from-scratch corpus on it."""
    graph = StreamingGraph.from_edges(src, dst, n, capacity)
    return graph, generate_corpus(key, graph, wcfg)


def engine(cfg: dict, graph, store) -> WalkEngine:
    """WalkEngine with the configuration's merge policy; the rewalk and MAV
    capacities hold the whole corpus, since every walk is affected by a
    10k-edge batch at these sizes."""
    wcfg = walk_config(cfg)
    n_walks = (1 << cfg["log2_n"]) * cfg["n_walks_per_vertex"]
    return WalkEngine(graph=graph, store=store, cfg=wcfg,
                      merge_policy=cfg["merge_policy"],
                      rewalk_capacity=n_walks,
                      max_pending=cfg["max_pending"],
                      mav_capacity=n_walks * cfg["length"])


def host_state(eng: WalkEngine) -> dict:
    """The engine's graph, base store and pending blocks as host arrays."""
    s = eng.state
    return jax.device_get({
        "graph": {"codes": s.graph.codes, "num_edges": s.graph.num_edges,
                  "offsets": s.graph.offsets},
        "store": {"owner": s.store.owner, "code": s.store.code,
                  "epoch": s.store.epoch, "slot_epoch": s.store.slot_epoch,
                  "offsets": s.store.offsets},
        "pending": {"owner": s.pending.owner, "code": s.pending.code,
                    "epoch": s.pending.epoch, "slot": s.pending.slot},
    })


def max_segment(eng: WalkEngine) -> int:
    """Longest walks_of read any vertex needs: its base segment (live and
    superseded rows) or its pending rows, whichever is longer."""
    s = jax.device_get({"offsets": eng.state.store.offsets,
                        "owner": eng.state.pending.owner,
                        "epoch": eng.state.pending.epoch})
    base = int(np.max(np.diff(s["offsets"])))
    owners = s["owner"].ravel()[s["epoch"].ravel() != 0xFFFFFFFF]
    pend = int(np.bincount(owners.astype(np.int64)).max()) if owners.size \
        else 0
    return max(base, pend)
