"""Explicitly partitioned walk engine: `stream_step` under `shard_map`
(DESIGN.md §4).

Where distr/engine.py lets GSPMD infer collectives from NamedSharding
annotations (implicit all-gathers on every frontier gather), this engine
partitions the state BY VERTEX RANGE and writes the collectives by hand —
the BINGO/ThunderRW locality discipline: every update stays on the shard
that owns the affected state.

Layout (shard k of S owns vertices [k*vps, (k+1)*vps)):
  * graph edge codes   — shard k holds the sorted codes whose SOURCE it
    owns (per-shard capacity, SENTINEL-padded); CSR offsets span the global
    vertex space, so `sample_neighbor` works unmodified on owned vertices.
  * triplet store      — shard k holds the (owner, code, epoch) triplets
    whose owner vertex it owns, sorted, pad rows (owner=n, SENTINEL,
    PAD_EPOCH) at the tail; packed chunks / vmin / vmax derived locally.
  * pending overlay    — each shard accumulates only the version-block
    entries its vertices own (a rewalk lane emits on the shard that owns
    its current vertex, so the partition is automatic).
  * slot_epoch + engine scalars — REPLICATED: the slot-version bump depends
    only on (affected walk ids, p_min), which every shard derives from the
    combined MAV, so it is recomputed identically everywhere with no
    collective.

Per `stream_step`, exactly two exchanges:
  1. MAV combine — a min over the int64[n_walks] composite keys
    (core/mav.py::keyed_pmin), run as two int32 `lax.pmin`s since TPUs
    all-reduce 64-bit values only as sums; (p, owner)-lexicographic keys make the
    cross-shard tie-break identical to the single-host segment_min.
  2. walk handoff — one `lax.all_to_all` of fixed-size frontier-lane slabs
    per rewalk step (distr/handoff.py): a lane whose next vertex lives on
    another shard continues there, inside the jitted scan.

Bit-identity with the single-host engine (tests/test_distr.py): PRNG draws
are replicated per lane — every shard evaluates the full [capacity]-lane
`sample_next_sharded` with the same key, and a lane's draw depends only on
(key, lane index), so the shard that owns the lane reproduces the
single-host draw exactly (core/walkers.py documents the contract). The
sharded rewalk always runs the unfused sampling scan, so it matches the
single-host engine with `megakernel="off"`/the registry default; order-2
models are rejected (N(prev) may be remote).

Capacity knobs (all static, `ShardSpec`): per-shard edge/store/MAV-gather
capacities and the handoff slab width. Overflowing any of them sets the
sticky per-shard `overflow` flag (deferred-overflow contract — check at
stream end via `unshard_state`). Per-shard pending blocks keep the
single-host [max_pending, capacity*l] allocation (content is partitioned,
the allocation is not — a fixed-lane-layout tradeoff, honest cost in
DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import pairing
from repro.core.corpus import WalkConfig
from repro.core.graph import (SENTINEL, StreamingGraph, drop_sorted,
                              edge_code)
from repro.core.mav import gather_touched_segments, keyed_pmin, mav_from_keyed
from repro.core.store import PAD_EPOCH, WalkStore
from repro.core.update import EngineState, PendingBlocks, VersionBlock
from repro.core.utils import compact_nonzero
from repro.core.walkers import sample_next_sharded
from repro.distr.handoff import exchange_frontier, shard_of_vertex

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

AXIS = "shard"


@dataclass(frozen=True)
class ShardSpec:
    """Static shape of the vertex-range partition (hashable jit key)."""

    n_shards: int
    n_vertices: int
    edge_capacity: int    # per-shard sorted-code capacity
    store_capacity: int   # per-shard triplet rows (>= owned live triplets)
    mav_capacity: int     # per-shard MAV gather capacity
    slab: int             # handoff lanes per (src, dst) shard pair per step

    @property
    def vps(self) -> int:
        """Vertices per shard (ceil; the last shard may own fewer)."""
        return -(-self.n_vertices // self.n_shards)

    @staticmethod
    def create(n_shards: int, n_vertices: int, total_triplets: int,
               total_edge_capacity: int, rewalk_capacity: int,
               headroom: float = 2.0) -> "ShardSpec":
        """Balanced default: `headroom` x the perfectly uniform share (skewed
        graphs concentrate triplets on hub-owning shards), capacities rounded
        to the 128-code packed-chunk multiple."""
        def share(total):
            per = int(total * headroom) // n_shards + 1
            return -(-per // 128) * 128
        return ShardSpec(n_shards=n_shards, n_vertices=n_vertices,
                         edge_capacity=min(share(total_edge_capacity),
                                           total_edge_capacity),
                         store_capacity=min(share(total_triplets),
                                            total_triplets),
                         mav_capacity=min(share(total_triplets),
                                          total_triplets),
                         slab=rewalk_capacity)


def _pmin_keys(best):
    """Cross-shard min of the int64 composite MAV keys `p * 2^32 + owner` as
    two int32 collectives — TPUs all-reduce 64-bit values only as sums: the
    min of the high words (p), then the min of the low words (owner) over
    the shards holding that p. Equal to `lax.pmin(best)` for owner < 2^31."""
    hi = (best >> 32).astype(I32)
    lo = (best & jnp.asarray(0xFFFFFFFF, jnp.int64)).astype(I32)
    hi_min = jax.lax.pmin(hi, AXIS)
    lo = jnp.where(hi == hi_min, lo, jnp.iinfo(jnp.int32).max)
    lo_min = jax.lax.pmin(lo, AXIS)
    return (hi_min.astype(jnp.int64) << 32) | lo_min.astype(jnp.int64)


# ------------------------------------------------------- local graph update


def _local_delete(codes, gone):
    """Match-and-sentinel deletion against the local sorted codes: the exact
    single-host `delete_edges` math — codes absent locally simply miss."""
    gone = jnp.sort(gone)
    pos = jnp.clip(jnp.searchsorted(gone, codes, side="left"), 0,
                   gone.shape[0] - 1)
    hit = gone[pos] == codes
    return drop_sorted(codes, hit)


def _local_insert(codes, new_masked, capacity: int):
    """Sorted merge + dedup + slice, mirroring `insert_edges`; `new_masked`
    already has non-owned directions replaced by SENTINEEL-equivalents.
    Returns (codes, overflow): overflow = live codes didn't fit."""
    merged = jnp.sort(jnp.concatenate([codes, new_masked]))
    dup = jnp.concatenate([jnp.asarray([False]), merged[1:] == merged[:-1]])
    merged = drop_sorted(merged, dup)
    overflow = jnp.sum(merged != SENTINEL) > capacity
    return merged[:capacity], overflow


def _local_apply_batch(graph: StreamingGraph, ins_src, ins_dst, del_src,
                       del_dst, spec: ShardSpec, my_shard):
    """Shard-local graph delta: deletions then insertions (both undirected),
    keeping only the directions whose source vertex this shard owns."""
    codes = graph.codes
    overflow = jnp.asarray(False)
    if del_src.shape[0] > 0:
        gone = jnp.concatenate([edge_code(del_src, del_dst),
                                edge_code(del_dst, del_src)])
        codes = _local_delete(codes, gone)
    if ins_src.shape[0] > 0:
        new = jnp.concatenate([edge_code(ins_src, ins_dst),
                               edge_code(ins_dst, ins_src)])
        owners = jnp.concatenate([ins_src, ins_dst])
        mine = shard_of_vertex(owners, spec.vps) == my_shard
        codes, overflow = _local_insert(codes, jnp.where(mine, new, SENTINEL),
                                        spec.edge_capacity)
    num = jnp.sum(codes != SENTINEL).astype(I32)
    return StreamingGraph(codes, graph._rebuild_offsets(codes, num), num,
                          graph.n_vertices), overflow


# -------------------------------------------------------------- local merge

PAD_CODE = SENTINEL


def _local_consolidated_store(store: WalkStore, pending: PendingBlocks):
    """Pad-aware local Merge: base + pending -> the live partition, sorted,
    pad rows normalized to (owner=n, SENTINEL, PAD_EPOCH) at the tail.

    Liveness is the global `epoch == slot_epoch[slot]` check against the
    REPLICATED slot_epoch, so a base entry superseded by a version block on
    a DIFFERENT shard still dies here — which is what keeps the union of
    the local live sets equal to the single-host merged store. Result-
    equivalent to either single-host merge_impl (both produce the identical
    live set; tests compare the unsharded triplets bit-for-bit)."""
    t = store.size
    owner = jnp.concatenate([store.owner, pending.owner.reshape(-1)])
    code = jnp.concatenate([store.code, pending.code.reshape(-1)])
    epoch = jnp.concatenate([store.epoch, pending.epoch.reshape(-1)])
    f, _ = pairing.szudzik_unpair(code)
    slot = jnp.clip(f.astype(jnp.int64), 0,
                    store.n_walks * store.length - 1).astype(I32)
    live = (epoch != PAD_EPOCH) & (epoch == store.slot_epoch[slot])
    n_live = jnp.sum(live.astype(I32))
    overflow = n_live > t
    order = jnp.lexsort((code, owner, ~live))
    is_live_row = jnp.arange(t, dtype=I32) < n_live
    owner = jnp.where(is_live_row, owner[order][:t],
                      jnp.asarray(store.n_vertices, U32))
    code = jnp.where(is_live_row, code[order][:t], PAD_CODE)
    epoch = jnp.where(is_live_row, epoch[order][:t], PAD_EPOCH)
    return WalkStore.from_sorted(owner, code, epoch, store.slot_epoch,
                                 store.length, store.n_walks,
                                 store.n_vertices, chunk_b=store.chunk_b,
                                 prev=store), overflow


def _local_merge_state(state: EngineState) -> EngineState:
    store, overflow = _local_consolidated_store(state.store, state.pending)
    return state.replace(store=store,
                         pending=PendingBlocks.empty_like(state.pending),
                         n_pending=jnp.asarray(0, I32),
                         overflow=state.overflow | overflow)


# ------------------------------------------------------------ sharded update


def _sharded_rewalk(key, graph: StreamingGraph, store: WalkStore, mav,
                    new_epoch, cfg: WalkConfig, capacity: int,
                    spec: ShardSpec, my_shard, with_obs: bool = False):
    """The single-host `_rewalk` scan with lane residency + handoff.

    The lane METADATA (affected walk ids, p_min, spawn vertex) is replicated
    — every shard computes it from the combined MAV — but each lane is LIVE
    on exactly one shard at a time: it spawns on the owner of its p_min
    vertex, emits its triplet locally (owner = current vertex is owned here
    by construction), and is re-routed through `exchange_frontier` every
    step. Draws are replicated full-lane-shape (see module docstring), so
    the emitted triplets are bit-identical to the single-host scan.

    `with_obs` (static) additionally rides this shard's handoff counters on
    the scan carry (DESIGN.md §10) and appends an obs dict to the return —
    pure reads of `dest`, so the frontier itself is untouched."""
    length = store.length
    affected = mav.p_min < length
    walk_ids, lane_valid = compact_nonzero(affected, size=capacity)
    walk_ids = walk_ids.astype(U32)
    p_min = mav.p_min[walk_ids]
    v_at_pmin = mav.v_min[walk_ids]
    spawn_here = lane_valid & (shard_of_vertex(v_at_pmin, spec.vps)
                               == my_shard)
    ps = jnp.arange(length, dtype=I32)
    w64 = walk_ids.astype(U64)
    l64 = jnp.asarray(length, U64)

    def step(carry, inp):
        if with_obs:
            cur, mine, ovf, h_sent, h_cross, h_max = carry
        else:
            cur, mine, ovf = carry
        p, kp = inp
        spawn = p == p_min
        cur = jnp.where(spawn, v_at_pmin, cur)
        mine = jnp.where(spawn, spawn_here, mine)
        # full-lane-shape draw: owned lanes match the single-host stream
        nxt = sample_next_sharded(kp, graph, cur, cfg.model)
        is_term = p == length - 1
        nxt_eff = jnp.where(is_term, cur, nxt)
        code = pairing.szudzik_pair(w64 * l64 + p.astype(U64),
                                    nxt_eff.astype(U64))
        emit = mine
        owner = cur
        cont = mine & ~is_term
        dest = jnp.where(cont, shard_of_vertex(nxt, spec.vps),
                         spec.n_shards)
        if with_obs:
            with jax.named_scope("obs_metrics"):
                load = (jnp.zeros((spec.n_shards + 1,), I32)
                        .at[dest].add(1))[:spec.n_shards]
                h_sent = h_sent + jnp.sum(load).astype(I32)
                h_cross = h_cross + jnp.sum(
                    cont & (dest != my_shard)).astype(I32)
                h_max = jnp.maximum(h_max, jnp.max(load)).astype(I32)
        cur2, mine2, of = exchange_frontier(dest, nxt, spec.n_shards,
                                            spec.slab, AXIS)
        if with_obs:
            return ((cur2, mine2, ovf | of, h_sent, h_cross, h_max),
                    (owner, code, emit))
        return (cur2, mine2, ovf | of), (owner, code, emit)

    keys = jax.random.split(key, length)
    init = (jnp.zeros((capacity,), U32), jnp.zeros((capacity,), bool),
            jnp.asarray(False))
    if with_obs:
        z = lambda: jnp.zeros((), I32)
        init = init + (z(), z(), z())
        carry_out, (owners, codes, emits) = jax.lax.scan(
            step, init, (ps, keys))
        handoff_ovf = carry_out[2]
        obs = {"handoff_sent": carry_out[3], "handoff_cross": carry_out[4],
               "handoff_max_load": carry_out[5],
               "pmin_hist": _obs_pmin_hist(p_min, lane_valid, length)}
    else:
        (_, _, handoff_ovf), (owners, codes, emits) = jax.lax.scan(
            step, init, (ps, keys))
    owners = owners.T.reshape(-1)       # [capacity * l], lane-major
    codes = codes.T.reshape(-1)
    emits = emits.T.reshape(-1)

    epoch = jnp.where(emits, new_epoch, PAD_EPOCH).astype(U32)
    owners = jnp.where(emits, owners, 0).astype(U32)
    codes = jnp.where(emits, codes, jnp.asarray(0, U64))

    # replicated slot-version bump: depends only on (walk_ids, p_min,
    # lane_valid), NOT on which shard emitted — every shard computes the
    # identical slot_epoch with no collective
    slot_w = jnp.repeat(walk_ids.astype(I32), length)
    slot_p = jnp.tile(ps, capacity)
    slots = jnp.clip(slot_w * length + slot_p, 0,
                     store.n_walks * length - 1)
    emits_meta = (jnp.repeat(lane_valid, length)
                  & (slot_p >= jnp.repeat(p_min, length)))
    slot_epoch = store.slot_epoch.at[slots].max(
        jnp.where(emits_meta, new_epoch, jnp.asarray(0, U32)))

    n_aff = jnp.sum(affected)
    block = VersionBlock(owner=owners, code=codes, epoch=epoch,
                         slot=jnp.where(emits, slots, 0).astype(I32),
                         n_new=jnp.sum(emits).astype(I32))
    if with_obs:
        return block, slot_epoch, n_aff, handoff_ovf, obs
    return block, slot_epoch, n_aff, handoff_ovf


def _obs_pmin_hist(p_min, lane_valid, length: int):
    from repro.obs.metrics import pmin_bucket_counts
    with jax.named_scope("obs_metrics"):
        return pmin_bucket_counts(p_min, lane_valid, length)


def _sharded_apply_update(state: EngineState, ins_src, ins_dst, del_src,
                          del_dst, key, cfg: WalkConfig, capacity: int,
                          spec: ShardSpec, my_shard, with_obs: bool = False):
    """Shard-local Algorithm 2: the `_apply_update` dataflow with the
    frontier gather factored into (local gather) + (pmin combine), and the
    rewalk replaced by the handoff scan.

    `with_obs` (static) returns (state, obs): the rewalk's handoff counters
    and pmin histogram plus this step's PER-SOURCE overflow flags (graph /
    MAV gather / handoff slab) — the provenance `record_sharded_step`
    stamps; the engine's own `overflow` stays the single OR as before."""
    graph, g_ovf = _local_apply_batch(state.graph, ins_src, ins_dst,
                                      del_src, del_dst, spec, my_shard)
    store, pending = state.store, state.pending
    new_epoch = state.epoch + jnp.asarray(1, U32)

    # MAV: local gather over owned segments (non-owned touched vertices have
    # empty local segments, so the full touched mask is correct as-is) ...
    touched_v = jnp.zeros((store.n_vertices,), bool)
    for arr in (ins_src, ins_dst, del_src, del_dst):
        if arr.shape[0] > 0:
            touched_v = touched_v.at[arr.astype(I32)].set(True)
    g_owner, g_code, g_epoch, g_valid, total = gather_touched_segments(
        store, touched_v, spec.mav_capacity)
    mav_ovf = total > spec.mav_capacity
    g_f, _ = pairing.szudzik_unpair(jnp.where(g_valid, g_code,
                                              jnp.zeros_like(g_code)))
    g_w = (g_f // jnp.asarray(store.length, U64)).astype(I32)
    g_p = (g_f % jnp.asarray(store.length, U64)).astype(I32)
    g_touched = touched_v[g_owner.astype(I32)] & g_valid

    p_owner = pending.owner.reshape(-1)
    p_slot = pending.slot.reshape(-1)
    p_epoch = pending.epoch.reshape(-1)
    p_valid = p_epoch != PAD_EPOCH
    p_w = p_slot // store.length
    p_p = p_slot % store.length
    p_touched = touched_v[p_owner.astype(I32)] & p_valid

    # ... then ONE pmin over the composite keys combines the shards
    best = keyed_pmin(
        jnp.concatenate([g_w, p_w]), jnp.concatenate([g_p, p_p]),
        jnp.concatenate([g_owner, p_owner]),
        jnp.concatenate([g_epoch, p_epoch]), store.slot_epoch,
        jnp.concatenate([g_touched, p_touched]),
        jnp.concatenate([g_valid, p_valid]),
        store.length, store.n_walks)
    mav = mav_from_keyed(_pmin_keys(best), store.length)

    rw = _sharded_rewalk(key, graph, store, mav, new_epoch, cfg, capacity,
                         spec, my_shard, with_obs=with_obs)
    if with_obs:
        block, slot_epoch, n_aff, h_ovf, obs = rw
        obs = dict(obs, graph_overflow=g_ovf, mav_overflow=mav_ovf,
                   handoff_overflow=h_ovf)
    else:
        block, slot_epoch, n_aff, h_ovf = rw
    pending = PendingBlocks(
        owner=jax.lax.dynamic_update_index_in_dim(
            pending.owner, block.owner, state.n_pending, 0),
        code=jax.lax.dynamic_update_index_in_dim(
            pending.code, block.code, state.n_pending, 0),
        epoch=jax.lax.dynamic_update_index_in_dim(
            pending.epoch, block.epoch, state.n_pending, 0),
        slot=jax.lax.dynamic_update_index_in_dim(
            pending.slot, block.slot, state.n_pending, 0))
    n_aff = n_aff.astype(I32)
    state = EngineState(
        graph=graph, store=store.replace(slot_epoch=slot_epoch),
        pending=pending, n_pending=state.n_pending + 1, epoch=new_epoch,
        last_affected=n_aff, total_affected=state.total_affected + n_aff,
        overflow=state.overflow | g_ovf | mav_ovf | h_ovf)
    if with_obs:
        return state, obs
    return state


def sharded_stream_step(state: EngineState, key, ins_src, ins_dst, del_src,
                        del_dst, cfg: WalkConfig, capacity: int,
                        spec: ShardSpec, my_shard, max_pending: int,
                        merge_policy: str) -> EngineState:
    """The `stream_step` twin for shard-local state: same (data-independent)
    merge cadence — n_pending is replicated, so every shard takes the same
    cond branch — with the pad-aware local consolidate as the merge."""
    state = jax.lax.cond(state.n_pending >= jnp.asarray(max_pending, I32),
                         _local_merge_state, lambda s: s, state)
    state = _sharded_apply_update(state, ins_src, ins_dst, del_src, del_dst,
                                  key, cfg, capacity, spec, my_shard)
    if merge_policy == "eager":
        state = _local_merge_state(state)
    return state


def sharded_stream_step_obs(state: EngineState, metrics, key, ins_src,
                            ins_dst, del_src, del_dst, cfg: WalkConfig,
                            capacity: int, spec: ShardSpec, my_shard,
                            max_pending: int, merge_policy: str):
    """`sharded_stream_step` + this shard's StreamMetrics fold.

    A separate function (not a flag on the OFF step) so the untracked
    driver keeps its exact pre-observability trace. Engine dataflow is
    identical; store-merge overflow provenance is recovered from the sticky
    flag's before/after diff around each in-scan consolidate."""
    from repro.obs.metrics import record_sharded_step
    forced = state.n_pending >= jnp.asarray(max_pending, I32)
    ovf0 = state.overflow
    state = jax.lax.cond(forced, _local_merge_state, lambda s: s, state)
    merge_tripped = state.overflow & ~ovf0
    state, obs = _sharded_apply_update(state, ins_src, ins_dst, del_src,
                                       del_dst, key, cfg, capacity, spec,
                                       my_shard, with_obs=True)
    if merge_policy == "eager":
        ovf1 = state.overflow
        state = _local_merge_state(state)
        merge_tripped = merge_tripped | (state.overflow & ~ovf1)
    metrics = record_sharded_step(metrics, state, obs, forced, merge_tripped,
                                  eager=merge_policy == "eager")
    return state, metrics


# ------------------------------------------------------------------- driver


def make_sharded_stream_fn(mesh, cfg: WalkConfig, spec: ShardSpec,
                           capacity: int, max_pending: int,
                           merge_policy: str):
    """The UNJITTED shard_map stream driver (launch/steps.py compiles it
    inside the dry-run's own jit; `sharded_run_stream` wraps it with
    jit + donation for execution)."""

    def run(stacked, keys, ins_src, ins_dst, del_src, del_dst):
        state = jax.tree.map(lambda leaf: leaf[0], stacked)
        my_shard = jax.lax.axis_index(AXIS)

        def body(s, xs):
            k, i_s, i_d, d_s, d_d = xs
            s = sharded_stream_step(s, k, i_s, i_d, d_s, d_d, cfg, capacity,
                                    spec, my_shard, max_pending,
                                    merge_policy)
            return s, s.last_affected

        state, affected = jax.lax.scan(
            body, state, (keys, ins_src, ins_dst, del_src, del_dst))
        # end-of-stream consolidate: the returned store is self-contained
        # (a no-op after an eager stream)
        state = _local_merge_state(state)
        stacked = jax.tree.map(lambda leaf: leaf[None], state)
        return stacked, affected[None]

    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(AXIS), P(), P(), P(), P(), P()),
        out_specs=(P(AXIS), P(AXIS)), check_vma=False)


@lru_cache(maxsize=None)
def _make_sharded_run(mesh, cfg: WalkConfig, spec: ShardSpec, capacity: int,
                      max_pending: int, merge_policy: str):
    """Jitted shard_map driver for one (mesh, static-config) combination."""
    return jax.jit(make_sharded_stream_fn(mesh, cfg, spec, capacity,
                                          max_pending, merge_policy),
                   donate_argnums=(0,))


def make_sharded_stream_obs_fn(mesh, cfg: WalkConfig, spec: ShardSpec,
                               capacity: int, max_pending: int,
                               merge_policy: str):
    """`make_sharded_stream_fn` with per-shard StreamMetrics on the carry.

    The metrics pytree enters/leaves [S, ...]-stacked with P(AXIS) specs
    like the engine state; each shard accumulates its own counters inside
    the scan (replicated ones land identical everywhere — asserted by
    tests), reduce at the end with `obs.metrics.combine_shards`."""
    from repro.obs.metrics import OVF_STORE, record_overflow

    def run(stacked, stacked_m, keys, ins_src, ins_dst, del_src, del_dst):
        state = jax.tree.map(lambda leaf: leaf[0], stacked)
        metrics = jax.tree.map(lambda leaf: leaf[0], stacked_m)
        my_shard = jax.lax.axis_index(AXIS)

        def body(carry, xs):
            s, m = carry
            k, i_s, i_d, d_s, d_d = xs
            s, m = sharded_stream_step_obs(s, m, k, i_s, i_d, d_s, d_d, cfg,
                                           capacity, spec, my_shard,
                                           max_pending, merge_policy)
            return (s, m), s.last_affected

        (state, metrics), affected = jax.lax.scan(
            body, (state, metrics), (keys, ins_src, ins_dst, del_src,
                                     del_dst))
        # end-of-stream consolidate can trip the store capacity too —
        # stamp its provenance before the flag diff is lost
        ovf0 = state.overflow
        state = _local_merge_state(state)
        metrics = record_overflow(metrics, OVF_STORE,
                                  state.overflow & ~ovf0, state.epoch)
        stacked = jax.tree.map(lambda leaf: leaf[None], state)
        stacked_m = jax.tree.map(lambda leaf: leaf[None], metrics)
        return stacked, stacked_m, affected[None]

    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(), P(), P(), P(), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS)), check_vma=False)


@lru_cache(maxsize=None)
def _make_sharded_run_obs(mesh, cfg: WalkConfig, spec: ShardSpec,
                          capacity: int, max_pending: int,
                          merge_policy: str):
    """Jitted observed driver; engine state AND metrics donated."""
    return jax.jit(make_sharded_stream_obs_fn(mesh, cfg, spec, capacity,
                                              max_pending, merge_policy),
                   donate_argnums=(0, 1))


def shard_mesh(n_shards: int) -> Mesh:
    """1-D 'shard' mesh over the first n_shards local devices."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(f"need {n_shards} devices, have {len(devs)} "
                         f"(set --xla_force_host_platform_device_count)")
    return Mesh(np.array(devs[:n_shards]), (AXIS,))


def sharded_run_stream(stacked: EngineState, key, ins_src, ins_dst,
                       del_src=None, del_dst=None, *, cfg: WalkConfig,
                       spec: ShardSpec, capacity: int, max_pending: int = 8,
                       merge_policy: str = "on-demand", mesh: Mesh = None,
                       metrics=None):
    """A whole [n_batches, batch] mixed stream on the explicit shard mesh.

    The partitioned twin of `WalkEngine.run_stream`: same per-batch key
    split, same merge cadence, bit-identical output triplets/graph/corpus
    (tests/test_distr.py). `stacked` is the [S, ...]-stacked per-shard
    EngineState from `shard_state` and is DONATED. Returns
    (stacked_state, affected int32[n_batches]).

    With `cfg.metrics` the return gains a trailing [S, ...]-stacked
    per-shard StreamMetrics (donated; pass `metrics` to continue a prior
    stream's counters) — reduce with `obs.metrics.combine_shards` /
    `obs.export.summary`."""
    if cfg.model.order != 1:
        raise NotImplementedError(
            "sharded run_stream is order-1 (DeepWalk) only — order-2 "
            "SAMPLENEXT needs remote neighbor windows (DESIGN.md §4)")
    ins_src = jnp.asarray(ins_src, U32)
    ins_dst = jnp.asarray(ins_dst, U32)
    n_batches = ins_src.shape[0]
    if del_src is None:
        del_src = jnp.zeros((n_batches, 0), U32)
        del_dst = jnp.zeros((n_batches, 0), U32)
    else:
        del_src = jnp.asarray(del_src, U32)
        del_dst = jnp.asarray(del_dst, U32)
    keys = jax.random.split(key, n_batches)
    mesh = mesh if mesh is not None else shard_mesh(spec.n_shards)
    if cfg.metrics:
        if metrics is None:
            from repro.obs.metrics import StreamMetrics
            empties = [StreamMetrics.empty() for _ in range(spec.n_shards)]
            metrics = jax.tree.map(lambda *ls: jnp.stack(ls), *empties)
        fn = _make_sharded_run_obs(mesh, cfg, spec, capacity, max_pending,
                                   merge_policy)
        stacked, metrics, affected = fn(stacked, metrics, keys, ins_src,
                                        ins_dst, del_src, del_dst)
        return stacked, affected[0], metrics
    fn = _make_sharded_run(mesh, cfg, spec, capacity, max_pending,
                           merge_policy)
    stacked, affected = fn(stacked, keys, ins_src, ins_dst, del_src,
                           del_dst)
    return stacked, affected[0]


# ------------------------------------------------- host-side (un)partition


def shard_state(graph: StreamingGraph, store: WalkStore, spec: ShardSpec,
                capacity: int, max_pending: int = 8) -> EngineState:
    """Partition a (merged) single-host engine state into the stacked
    [S, ...] per-shard EngineState the driver consumes.

    The store must be fully merged (exactly T live triplets, no pending) —
    the canonical hand-over point, same as the GSPMD engine's dict
    round-trip. Raises if any shard's owned rows exceed its capacity."""
    states = []
    src = (graph.codes >> jnp.asarray(32, U64)).astype(U32)
    g_live = graph.codes != SENTINEL
    for k in range(spec.n_shards):
        gmask = g_live & (shard_of_vertex(src, spec.vps) == k)
        n_g = int(jnp.sum(gmask))
        if n_g > spec.edge_capacity:
            raise ValueError(f"shard {k}: {n_g} edges > per-shard capacity "
                             f"{spec.edge_capacity}")
        idx, valid = compact_nonzero(gmask, spec.edge_capacity)
        codes_k = jnp.where(valid, graph.codes[idx], SENTINEL)
        num_k = jnp.asarray(n_g, I32)
        g_k = StreamingGraph(codes_k,
                             graph._rebuild_offsets(codes_k, num_k), num_k,
                             graph.n_vertices)

        smask = shard_of_vertex(store.owner, spec.vps) == k
        n_s = int(jnp.sum(smask))
        if n_s > spec.store_capacity:
            raise ValueError(f"shard {k}: {n_s} triplets > per-shard "
                             f"capacity {spec.store_capacity}")
        idx, valid = compact_nonzero(smask, spec.store_capacity)
        # compact_nonzero preserves the (owner, code) sort; pads normalized
        s_k = WalkStore.from_sorted(
            jnp.where(valid, store.owner[idx],
                      jnp.asarray(store.n_vertices, U32)),
            jnp.where(valid, store.code[idx], PAD_CODE),
            jnp.where(valid, store.epoch[idx], PAD_EPOCH),
            store.slot_epoch, store.length, store.n_walks,
            store.n_vertices, chunk_b=store.chunk_b)
        states.append(EngineState.create(
            g_k, s_k, max_pending, capacity * store.length,
            epoch=jnp.max(store.slot_epoch)))
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *states)


def unshard_state(stacked: EngineState, edge_capacity: int):
    """Gather the per-shard partitions back into global (graph, store).

    Returns (graph, store, overflow): the union of the local live sets,
    re-sorted into the canonical single-host layout (same lexsort
    `WalkStore.build` runs, so a bit-exact comparison against the
    single-host engine is meaningful). Raises if the live triplet count
    disagrees with the T-invariant — the symptom of a capacity overflow
    (also surfaced via the sticky `overflow` flag)."""
    codes = np.asarray(stacked.graph.codes).reshape(-1)
    live_codes = np.sort(codes[codes != np.uint64(0xFFFFFFFFFFFFFFFF)])
    if live_codes.size > edge_capacity:
        raise ValueError(f"{live_codes.size} live edges > edge capacity "
                         f"{edge_capacity}")
    full = np.full((edge_capacity,), np.uint64(0xFFFFFFFFFFFFFFFF))
    full[:live_codes.size] = live_codes
    codes_j = jnp.asarray(full)
    num = jnp.asarray(live_codes.size, I32)
    n_vertices = stacked.graph.n_vertices
    g_tmp = StreamingGraph.empty(n_vertices, edge_capacity)
    graph = StreamingGraph(codes_j, g_tmp._rebuild_offsets(codes_j, num),
                           num, n_vertices)

    owner = np.asarray(stacked.store.owner).reshape(-1)
    code = np.asarray(stacked.store.code).reshape(-1)
    epoch = np.asarray(stacked.store.epoch).reshape(-1)
    live = epoch != np.uint32(0xFFFFFFFF)
    t = stacked.store.n_walks * stacked.store.length
    if int(live.sum()) != t:
        raise RuntimeError(f"{int(live.sum())} live triplets != T={t} — "
                           f"per-shard store/pending capacity overflow?")
    store = WalkStore.build(jnp.asarray(owner[live]), jnp.asarray(code[live]),
                            jnp.asarray(epoch[live]),
                            stacked.store.slot_epoch[0],
                            stacked.store.length, stacked.store.n_walks,
                            n_vertices, chunk_b=stacked.store.chunk_b)
    return graph, store, bool(np.any(np.asarray(stacked.overflow)))
