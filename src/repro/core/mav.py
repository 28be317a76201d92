"""Map of Affected Vertices (paper §6.1, Def. 3).

For a batch of edge updates, MAV maps each affected walk w to {v_min, p_min}:
the first affected vertex in w and its position. Both insertion and deletion of
edge (s, d) mark every walk containing s (and, undirected, d) as affected at the
position where that vertex occurs.

Two implementations, mirroring the paper's simple-vs-pruned study:
  * mav_dense   — O(T) masked scan over the whole store (the II-like fallback).
  * mav_indexed — output-sensitive: gathers only the affected vertices' segments
    via the CSR offsets (the hybrid-tree's "only search the source vertex's
    walk-tree" property), with a static gather capacity.
Both return identical results (property-tested).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pairing
from repro.core.store import WalkStore

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32


class MAV(NamedTuple):
    p_min: jax.Array   # int32[n_walks]; == l  -> walk unaffected
    v_min: jax.Array   # uint32[n_walks]; vertex at p_min (Def. 3 value)


def affected_mask(mav: MAV, length: int):
    return mav.p_min < length


def _touched_vertices(store: WalkStore, ins_src, ins_dst, del_src, del_dst):
    touched = jnp.zeros((store.n_vertices,), bool)
    for arr in (ins_src, ins_dst, del_src, del_dst):
        if arr is not None and arr.shape[0] > 0:
            touched = touched.at[jnp.asarray(arr, I32)].set(True)
    return touched


def keyed_pmin(w, p, owner, epoch, slot_epoch, touched, valid,
               length: int, n_walks: int):
    """Per-walk composite-min keys: the associative half of the MAV reduction.

    Returns int64[n_walks] keys `p * 2^32 + v_at_p` per walk, clamped to the
    miss value `length * 2^32` (a walk with no live touched entry). The key
    order is (p, owner)-lexicographic, so taking a MIN of keys — locally via
    segment_min here, or ACROSS vertex-range shards via `lax.pmin` in the
    explicitly partitioned engine (distr/sharded.py) — always selects the
    same entry, with ties broken identically everywhere. `mav_from_keyed`
    decomposes the combined keys back into (p_min, v_min)."""
    slot = jnp.clip(w * length + p, 0, n_walks * length - 1)
    live = epoch == slot_epoch[slot]
    hit = valid & live & touched
    w_safe = jnp.where(hit, w, 0)
    # composite key p * 2^32 + owner -> argmin(p) carrying v at p_min
    big = jnp.asarray(1 << 32, jnp.int64)
    miss = jnp.asarray(length, jnp.int64) * big
    keyed = jnp.where(hit, p.astype(jnp.int64) * big + owner.astype(jnp.int64),
                      miss)
    best = jax.ops.segment_min(keyed, w_safe, num_segments=n_walks)
    # walks with no entry row at all get segment_min's +inf identity: clamp
    # to the miss key so the decompose yields p_min = l, v_min = 0
    return jnp.minimum(best, miss)


def mav_from_keyed(best, length: int) -> MAV:
    """Decompose combined `keyed_pmin` keys into the MAV columns.

    The miss key `length * 2^32` decomposes to exactly (p_min=l, v_min=0) —
    the unaffected-walk convention — so no separate any-hit mask is carried
    through the (possibly cross-shard) min reduction."""
    big = jnp.asarray(1 << 32, jnp.int64)
    p_min = (best // big).astype(I32)
    v_min = jnp.where(p_min < length, (best % big).astype(U32), 0)
    return MAV(p_min=p_min, v_min=v_min)


def _pmin_from_wpo(w, p, owner, epoch, slot_epoch, touched, valid,
                   length: int, n_walks: int) -> MAV:
    """MAV reduction from already-decoded (w, p, owner) entry columns."""
    best = keyed_pmin(w, p, owner, epoch, slot_epoch, touched, valid,
                      length, n_walks)
    return mav_from_keyed(best, length)


def _pmin_from_entries(owner, code, epoch, slot_epoch, touched, valid,
                       length: int, n_walks: int) -> MAV:
    f, _ = pairing.szudzik_unpair(code)
    w = (f // jnp.asarray(length, U64)).astype(I32)
    p = (f % jnp.asarray(length, U64)).astype(I32)
    return _pmin_from_wpo(w, p, owner, epoch, slot_epoch, touched, valid,
                          length, n_walks)


def mav_dense(store: WalkStore, ins_src, ins_dst, del_src=None, del_dst=None) -> MAV:
    """O(T) masked scan (vectorized; used as oracle + II-like baseline)."""
    touched_v = _touched_vertices(store, ins_src, ins_dst, del_src, del_dst)
    touched = touched_v[store.owner.astype(I32)]
    valid = jnp.ones_like(touched)
    return _pmin_from_entries(store.owner, store.code, store.epoch,
                              store.slot_epoch, touched, valid,
                              store.length, store.n_walks)


def gather_touched_segments(store: WalkStore, touched_v, capacity: int):
    """Output-sensitive segment gather (§6.1): compact the touched vertices'
    walk-tree segments into a static `capacity`-sized buffer.

    Returns (owner, code, epoch, valid, total): gathered entry columns, a
    per-slot validity mask, and the true number of touched triplets. The
    caller must treat `total > capacity` as a gather overflow — slots past
    `capacity` are silently dropped from the gathered view.

    This is the single source of the gather used by both `mav_indexed` and
    the jitted update path (core/update.py), so the two cannot drift.
    """
    n = store.n_vertices
    seg_len = store.offsets[1:] - store.offsets[:-1]
    aff_len = jnp.where(touched_v, seg_len, 0)
    # prefix layout of gathered segments
    out_start = jnp.concatenate(
        [jnp.zeros((1,), I32), jnp.cumsum(aff_len, dtype=I32)])
    total = out_start[-1]
    # for each output slot, which vertex segment does it come from?
    slot_ids = jnp.arange(capacity, dtype=I32)
    seg_of = jnp.searchsorted(out_start[1:], slot_ids, side="right").astype(I32)
    seg_of = jnp.clip(seg_of, 0, n - 1)
    within = slot_ids - out_start[seg_of]
    src_idx = jnp.clip(store.offsets[seg_of] + within, 0, store.size - 1)
    valid = slot_ids < total
    return (store.owner[src_idx], store.code[src_idx], store.epoch[src_idx],
            valid, total)


def mav_indexed(store: WalkStore, ins_src, ins_dst, del_src=None, del_dst=None,
                gather_capacity: int | None = None) -> MAV:
    """Output-sensitive MAV: gather only affected vertices' walk-tree segments.

    gather_capacity bounds the total number of gathered triplets (static shape);
    it must be >= sum of affected segment lengths (checked by callers/tests).
    """
    touched_v = _touched_vertices(store, ins_src, ins_dst, del_src, del_dst)
    if gather_capacity is None:
        gather_capacity = store.size
    owner, code, epoch, valid, _ = gather_touched_segments(
        store, touched_v, gather_capacity)
    touched = touched_v[owner.astype(I32)] & valid
    return _pmin_from_entries(owner, code, epoch, store.slot_epoch, touched,
                              valid, store.length, store.n_walks)
