"""Quickstart: maintain random walks on a streaming graph (the paper's core
loop) in ~40 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import StreamingGraph, WalkConfig, generate_corpus
from repro.core.update import WalkEngine
from repro.data.streams import rmat_edges
from repro.launch.compile_cache import (enable_compile_cache,
                                        raise_compiler_stack)

raise_compiler_stack()
enable_compile_cache()

N_VERTICES = 1 << 10      # 1024-vertex RMAT graph
LOG2_N = 10

# 1. build the initial streaming graph + walk corpus (n_w walks per vertex)
key = jax.random.PRNGKey(0)
src, dst = rmat_edges(key, 4_000, LOG2_N)
graph = StreamingGraph.from_edges(src, dst, N_VERTICES, edge_capacity=65536)
cfg = WalkConfig(n_walks_per_vertex=4, length=16)
store = generate_corpus(jax.random.PRNGKey(1), graph, cfg)
print(f"graph: {int(graph.num_edges)} directed edges; "
      f"corpus: {store.n_walks} walks x {store.length} "
      f"({store.size} encoded triplets, "
      f"{store.nbytes_packed() / 1e6:.1f} MB packed)")

# 2. stream edge updates; Wharf re-walks only the affected walks
engine = WalkEngine(graph=graph, store=store, cfg=cfg, rewalk_capacity=4096)
for step in range(5):
    k1, k2 = jax.random.split(jax.random.fold_in(key, step))
    ins_src, ins_dst = rmat_edges(k1, 200, LOG2_N)
    n_affected = engine.insert_edges(k2, ins_src, ins_dst)
    print(f"batch {step}: +200 edges -> {n_affected} affected walks "
          f"({engine.n_pending} pending version blocks)")

# 3. or consume a whole stacked stream in ONE jitted scan (the pipelined
# driver, DESIGN.md §5): no host round-trip between batches, buffers donated
from repro.data.streams import edge_batch_stream
stream_src, stream_dst = edge_batch_stream(jax.random.fold_in(key, 99),
                                           8, 200, LOG2_N)
affected = engine.run_stream(jax.random.fold_in(key, 100),
                             stream_src, stream_dst)
print(f"run_stream: 8 batches in one scan -> per-batch affected "
      f"{[int(a) for a in affected]}")

# 4. read the corpus (triggers the on-demand merge) and traverse a walk
walks = engine.walk_matrix()
print("walk 7:", walks[7])

# 5. FINDNEXT: the paper's indexed point lookup, served from the compressed
# chunks via the backend registry (Pallas kernel on TPU, XLA fallback here)
from repro.core import packed_store
print("find_next backend:", packed_store.get_default_backend())
v, w, p = walks[7][3], jnp.uint32(7), jnp.uint32(3)
nxt, found = engine.store.find_next(v, w, p)
print(f"find_next(v={int(v)}, w=7, p=3) -> {int(nxt[0])} "
      f"(found={bool(found[0])}, matches walk: {int(walks[7][4])})")

# 6. serve a BATCHED query mix from a pinned snapshot while the stream
# keeps writing (DESIGN.md §11): `pin()` stamps the current epoch and keeps
# its buffers out of donation, so the same answers come back bit-identical
# across subsequent run_stream windows — the live view moves on
from repro.serve.walk_queries import WalkQueryService

service = WalkQueryService(engine=engine)
probes = [7, 21, 99]
with service.pin() as snap:
    pinned_before = service.ppr_rows(probes, snapshot=snap)
    stream_src, stream_dst = edge_batch_stream(jax.random.fold_in(key, 300),
                                               4, 200, LOG2_N)
    engine.run_stream(jax.random.fold_in(key, 301), stream_src, stream_dst)
    pinned_after = service.ppr_rows(probes, snapshot=snap)
    stable = bool(jnp.array_equal(pinned_before, pinned_after))
    nb = service.neighborhoods(probes, hops=2, snapshot=snap)
print(f"pinned query batch over 4 stream windows: bit-identical={stable}; "
      f"neighborhoods {nb.shape}; live epoch {engine.epoch_counter} "
      f"vs pinned {snap.epoch}")

# 7. the downstream loop (DESIGN.md §7): stream MORE edges while maintaining
# SGNS embeddings in the same jitted scan — each step retrains only the
# affected walks' windows — and watch a nearest-neighbor query move
from repro.downstream import EmbeddingMaintainer, MaintainerConfig

# lr note (DESIGN.md §7): nearly every walk is affected per batch here, so
# the SUM-loss accumulation wants a small step (0.01 diverges in this regime)
# metrics=True turns on the scan-carried stream counters AND the walk-
# freshness auditor (DESIGN.md §12) — engine outputs stay bit-identical
mcfg = MaintainerConfig(walk=cfg._replace(metrics=True),
                        n_vertices=N_VERTICES, dim=32, window=3,
                        rewalk_capacity=4096, lr=0.0005)
# handoff contract for a mid-stream store (DESIGN.md §12): merge() first
# (unmerged pending rewrites live outside the base store — dropping them
# leaves their slots unreadable) and resume the epoch counter (a restarted
# counter loses every slot-epoch precedence race). The §12 divergence
# auditor catches both misses as invalid transitions.
engine.merge()
maintainer = EmbeddingMaintainer(graph=engine.graph, store=engine.store,
                                 cfg=mcfg, key=jax.random.PRNGKey(5),
                                 epoch=engine.epoch_counter)
service = WalkQueryService(engine=maintainer.engine_view())
probe = int(walks[7][0])
service.set_embedding_table(maintainer.embeddings)
before_ids, _ = service.embedding_neighbors(probe, k=5)

stream_src, stream_dst = edge_batch_stream(jax.random.fold_in(key, 200),
                                           8, 200, LOG2_N)
metrics = maintainer.run_stream(jax.random.fold_in(key, 201),
                                stream_src, stream_dst)
print(f"maintained embeddings over 8 batches: "
      f"{int(metrics.n_pairs.sum())} pairs trained on "
      f"{int(metrics.n_affected.sum())} affected walks "
      f"(loss/pair {float(metrics.loss_sum.sum() / metrics.n_pairs.sum()):.3f})")
service.set_embedding_table(maintainer.embeddings)
after_ids, _ = service.embedding_neighbors(probe, k=5)
print(f"nearest neighbors of v={probe}: "
      f"before {[int(i) for i in before_ids[0]]} -> "
      f"after {[int(i) for i in after_ids[0]]}")

# 8. how fresh are the walks the embeddings just trained on? The staleness
# counters rode the same scan (DESIGN.md §12): per-walk lag = stream
# batches since the walk was last rewritten; the divergence auditor replays
# sampled walks against the live graph (invalid transitions must be 0 on a
# maintained engine)
from repro.obs import export

stale = export.summary(maintainer.metrics)["staleness"]
print(f"freshness after the stream: lag mean {stale['lag_mean']:.2f} "
      f"batches (max {stale['lag_max']}), "
      f"stale fraction {stale['stale_fraction']:.4f}; "
      f"auditor: {stale['audit']['invalid']}/{stale['audit']['transitions']} "
      f"invalid transitions (divergence {stale['audit']['divergence_rate']})")
