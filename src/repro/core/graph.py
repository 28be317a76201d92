"""Streaming graph substrate (paper §3.1): edge-stream model, batch insert/delete.

TPU adaptation of Aspen's edge C-trees: the edge set is one flat uint64 array of
directed edge codes ((src << 32) | dst), kept sorted, capacity-padded with a
sentinel. CSR views (offsets / neighbors) are derived by searchsorted — the
vectorized analogue of the vertex-tree -> edge-tree descent. Batch updates are
sort-merge passes: the bandwidth-optimal bulk form of Aspen's MultiInsert.

All shapes are static (capacity-padded); `num_edges` tracks the live prefix.
Deletions compact the surviving codes forward (sentinels refill the tail),
insertions merge + dedup.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.obs import trace

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

SENTINEL = jnp.asarray(0xFFFFFFFFFFFFFFFF, U64)


def edge_code(src, dst):
    return (jnp.asarray(src, U64) << jnp.asarray(32, U64)) | jnp.asarray(dst, U64)


def drop_sorted(codes, drop):
    """Remove the `drop` entries of a sorted code array, keeping the order
    and refilling the tail with SENTINEL: what re-sorting after marking them
    SENTINEL gives, from one int32 prefix sum and a scatter (each TPU sort
    of u64 keys costs about a minute of compile)."""
    keep = ~drop
    pos = jnp.cumsum(keep, dtype=I32) - 1
    dest = jnp.where(keep, pos, codes.shape[0])
    return jnp.full_like(codes, SENTINEL).at[dest].set(codes, mode="drop")


def edge_endpoints(code):
    code = jnp.asarray(code, U64)
    return (code >> jnp.asarray(32, U64)).astype(U32), (
        code & jnp.asarray(0xFFFFFFFF, U64)
    ).astype(U32)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class StreamingGraph:
    """Directed multigraph-free edge set with static capacity.

    codes:     uint64[E_cap]  sorted edge codes, SENTINEL-padded tail
    offsets:   int32[N_cap+1] CSR offsets over live prefix
    num_edges: int32          live (directed) edge count
    n_vertices: static int    vertex-id capacity
    """

    codes: jax.Array
    offsets: jax.Array
    num_edges: jax.Array
    n_vertices: int = dataclasses.field(metadata=dict(static=True))

    def replace(self, **kw) -> "StreamingGraph":
        return dataclasses.replace(self, **kw)

    # -- construction -------------------------------------------------------

    @staticmethod
    def empty(n_vertices: int, edge_capacity: int) -> "StreamingGraph":
        codes = jnp.full((edge_capacity,), SENTINEL, U64)
        offsets = jnp.zeros((n_vertices + 1,), I32)
        return StreamingGraph(codes, offsets, jnp.asarray(0, I32), n_vertices)

    @staticmethod
    def from_edges(src, dst, n_vertices: int, edge_capacity: int,
                   undirected: bool = True) -> "StreamingGraph":
        g = StreamingGraph.empty(n_vertices, edge_capacity)
        return g.insert_edges(src, dst, undirected=undirected)

    # -- views ---------------------------------------------------------------

    @property
    def neighbors(self):
        """uint32[E_cap] destination of each live edge slot (sorted by src)."""
        return (self.codes & jnp.asarray(0xFFFFFFFF, U64)).astype(U32)

    def degrees(self):
        return self.offsets[1:] - self.offsets[:-1]

    def degree(self, v):
        return self.offsets[v + 1] - self.offsets[v]

    def _rebuild_offsets(self, codes, num_edges):
        srcs = (codes >> jnp.asarray(32, U64)).astype(U32)
        # live prefix only: padded tail has src = 2^32-1 >= n_vertices
        bounds = jnp.arange(self.n_vertices + 1, dtype=U32)
        offsets = jnp.searchsorted(srcs, bounds, side="left").astype(I32)
        return jnp.minimum(offsets, num_edges)

    # -- streaming updates (paper §3.1: batch of insertions + deletions) -----

    def insert_edges(self, src, dst, undirected: bool = True) -> "StreamingGraph":
        """Bulk edge insertion (dedup'd merge)."""
        if src is None or src.shape[0] == 0:
            return self
        new = edge_code(src, dst)
        if undirected:
            new = jnp.concatenate([new, edge_code(dst, src)])
        merged = jnp.sort(jnp.concatenate([self.codes, new]))
        # dedup: keep first of each run, push dups to the tail as SENTINEL
        dup = jnp.concatenate(
            [jnp.asarray([False]), merged[1:] == merged[:-1]])
        merged = drop_sorted(merged, dup)[: self.codes.shape[0]]
        num = jnp.sum(merged != SENTINEL).astype(I32)
        return StreamingGraph(
            merged, self._rebuild_offsets(merged, num), num, self.n_vertices)

    def delete_edges(self, src, dst, undirected: bool = True) -> "StreamingGraph":
        """Bulk edge deletion (match -> compact the survivors)."""
        if src is None or src.shape[0] == 0:
            return self
        gone = edge_code(src, dst)
        if undirected:
            gone = jnp.concatenate([gone, edge_code(dst, src)])
        gone = jnp.sort(gone)
        pos = jnp.searchsorted(gone, self.codes, side="left")
        pos = jnp.clip(pos, 0, gone.shape[0] - 1)
        hit = gone[pos] == self.codes
        codes = drop_sorted(self.codes, hit)
        num = jnp.sum(codes != SENTINEL).astype(I32)
        return StreamingGraph(
            codes, self._rebuild_offsets(codes, num), num, self.n_vertices)

    def apply_batch(self, ins_src, ins_dst, del_src, del_dst,
                    undirected: bool = True) -> "StreamingGraph":
        """One graph update delta-G (deletions then insertions, paper §3.1)."""
        with trace.scope(trace.GRAPH_UPDATE):
            g = self.delete_edges(del_src, del_dst, undirected=undirected)
            return g.insert_edges(ins_src, ins_dst, undirected=undirected)

    # -- queries --------------------------------------------------------------

    def has_edge(self, src, dst):
        """Vectorized membership test (binary search on sorted codes)."""
        q = edge_code(src, dst)
        pos = jnp.searchsorted(self.codes, q, side="left")
        pos = jnp.clip(pos, 0, self.codes.shape[0] - 1)
        return self.codes[pos] == q

    def sample_neighbor(self, key, v):
        """Uniform neighbor of v (DeepWalk transition); v itself if isolated."""
        v = jnp.asarray(v, U32)
        start = self.offsets[v]
        deg = self.offsets[v + jnp.asarray(1, U32)] - start
        r = jax.random.randint(key, v.shape, 0, jnp.maximum(deg, 1))
        nbr = self.neighbors[start + r.astype(I32)]
        return jnp.where(deg > 0, nbr, v)
