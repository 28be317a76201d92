"""Batch walk update (paper §6.2, Algorithm 2) + merge policies (App. A).

The engine state is the hybrid-tree analogue, packaged as one functional
pytree (`EngineState`): a base WalkStore plus a fixed-capacity *pending
buffer* of version blocks (the paper's walk-tree versions — one row per
processed edge batch, so shapes stay static), with the epoch counter, the
pending fill level, and the MAV overflow/affected counters carried as device
scalars. One update is the pure `stream_step`: graph merge -> MAV -> re-walk
-> accumulator append (+ policy merges), shared verbatim by three drivers:

  * the legacy per-batch `WalkEngine._update` (one jitted call per batch),
  * `WalkEngine.run_stream` — a whole [n_batches, batch] edge stream inside
    ONE jitted `jax.lax.scan`, buffers donated, overflow/affected accumulated
    on device and checked once at stream end (the throughput path: no host
    sync or dispatch between batches),
  * the distributed engine (distr/engine.py), which runs the same step on
    pjit-sharded dict-of-array state.

`merge()` consolidates base + pending, evicting obsolete triplets
(epoch < slot_epoch[slot]) — the paper's Merge. Policies:

  * eager     — merge after every batch (constant memory, lower throughput)
  * on-demand — merge when pending fills; reads stay mergeless via the
    overlay view (core/overlay.py), the paper default

Statistical indistinguishability (Property 2): each affected walk is re-walked
from p_min with fresh PRNG draws against the *updated* graph, exactly the
policy of §6.2. SAMPLENEXT inside `_rewalk` dispatches on `cfg.model`
(core/walkers.py): order-2 streams run either the K-trial rejection sampler
or the exact factorized sampler (kernels/intersect.py) with NO change to
`EngineState` shapes — so all three drivers, the distributed engine, and the
downstream maintainer inherit the sampler choice from the config alone. The
order-2 chi-square harness (tests/test_walk_stats.py, `stats` tier) verifies
the contract against the exact alpha-weighted transition probabilities.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import pairing
from repro.core.corpus import WalkConfig, walk_start_vertex
from repro.core.graph import StreamingGraph
from repro.core.mav import MAV, _pmin_from_wpo, gather_touched_segments
from repro.core.overlay import Overlay
from repro.core.store import WalkStore, PAD_EPOCH
from repro.core.utils import compact_nonzero
from repro.core.walkers import sample_next
from repro.kernels import megakernel

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32


class UpdateAux(NamedTuple):
    """Per-update affected-walk identification (fixed [capacity] lanes).

    What a downstream consumer needs to retrain ONLY the walks one update
    touched (downstream/maintainer.py): the compacted affected-walk ids, the
    lane-validity mask (ids past the affected count are padding), and each
    walk's p_min — positions >= p_min were re-sampled this update, so pair
    windows entirely inside [0, p_min) are unchanged and skippable."""

    walk_ids: jax.Array    # uint32 [capacity] compacted affected walk ids
    lane_valid: jax.Array  # bool   [capacity] lanes < |affected|
    p_min: jax.Array       # int32  [capacity] first re-sampled position


class PendingBlocks(NamedTuple):
    """Fixed-capacity insertion-accumulator rows (walk-tree versions).

    `slot` (= w*l + p) is carried explicitly: the accumulator is the paper's
    pre-insertion staging area, so MAV checks over pending entries need no
    u64 unpair (the compressed base store remains codes-only)."""

    owner: jax.Array  # uint32 [P, cap*l]
    code: jax.Array   # uint64 [P, cap*l]
    epoch: jax.Array  # uint32 [P, cap*l]; PAD_EPOCH = dead entry
    slot: jax.Array   # int32  [P, cap*l]

    @staticmethod
    def empty(max_pending: int, entries: int) -> "PendingBlocks":
        return PendingBlocks(
            owner=jnp.zeros((max_pending, entries), U32),
            code=jnp.zeros((max_pending, entries), U64),
            epoch=jnp.full((max_pending, entries), PAD_EPOCH, U32),
            slot=jnp.zeros((max_pending, entries), I32))

    @staticmethod
    def empty_like(p: "PendingBlocks") -> "PendingBlocks":
        return PendingBlocks(owner=jnp.zeros_like(p.owner),
                             code=jnp.zeros_like(p.code),
                             epoch=jnp.full_like(p.epoch, PAD_EPOCH),
                             slot=jnp.zeros_like(p.slot))


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class EngineState:
    """The walk engine as one functional pytree (device-resident scalars).

    Everything the update loop touches lives here, so a whole stream of
    batches runs inside a single jitted scan with this as the carry — no
    host round-trip decides anything mid-stream. `overflow` is the sticky
    MAV gather-capacity flag (deferred-overflow contract: checked once at
    stream end, not per batch); `last_affected`/`total_affected` mirror the
    paper's |MAV| accounting without forcing a sync.
    """

    graph: StreamingGraph
    store: WalkStore
    pending: PendingBlocks
    n_pending: jax.Array       # int32  [] filled pending version blocks
    epoch: jax.Array           # uint32 [] monotone update-batch counter
    last_affected: jax.Array   # int32  [] |MAV| of the latest batch
    total_affected: jax.Array  # int32  [] cumulative |MAV| over all batches
    overflow: jax.Array        # bool   [] sticky MAV gather overflow flag

    def replace(self, **kw) -> "EngineState":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def create(graph: StreamingGraph, store: WalkStore, max_pending: int,
               entries: int, pending: Optional[PendingBlocks] = None,
               n_pending: int = 0, epoch: int = 0) -> "EngineState":
        if pending is None:
            pending = PendingBlocks.empty(max_pending, entries)
        return EngineState(
            graph=graph, store=store, pending=pending,
            n_pending=jnp.asarray(n_pending, I32),
            epoch=jnp.asarray(epoch, U32),
            last_affected=jnp.asarray(0, I32),
            total_affected=jnp.asarray(0, I32),
            overflow=jnp.asarray(False))


class WalkEngine:
    """Stateful wrapper around `EngineState`: graph + walk corpus in lockstep.

    Host-side mirrors (`n_pending`, `epoch_counter`) track the merge
    schedule, which is data-independent, so the legacy per-batch API and the
    read-path caches never force a device sync; `last_affected` /
    `mav_overflowed` are lazy properties that sync only when accessed.
    """

    def __init__(self, graph: StreamingGraph = None, store: WalkStore = None,
                 cfg: WalkConfig = None, merge_policy: str = "on-demand",
                 rewalk_capacity: int = 1024, max_pending: int = 8,
                 mav_capacity: Optional[int] = None,
                 merge_impl: str = "interleave",
                 pending: Optional[PendingBlocks] = None, n_pending: int = 0,
                 epoch: int = 0):
        self.cfg = cfg
        self.merge_policy = merge_policy    # "on-demand" | "eager"
        self.rewalk_capacity = rewalk_capacity  # max affected walks per batch
        self.max_pending = max_pending      # version blocks before forced merge
        self.mav_capacity = mav_capacity    # gathered-triplet bound (None = T)
        self.merge_impl = merge_impl        # "interleave" (O(T)) | "lexsort"
        # `epoch` resumes the monotone update counter when the store was
        # produced mid-stream elsewhere (e.g. `distr.sharded.unshard_state`):
        # its entries carry their original epochs, and a restarted counter
        # would lose every slot-epoch liveness race to them
        self.state = EngineState.create(graph, store, max_pending,
                                        rewalk_capacity * cfg.length,
                                        pending=pending, n_pending=n_pending,
                                        epoch=epoch)
        self._n_pending_host = int(n_pending)
        self._epoch_host = int(epoch)
        # outstanding read pins (serve/snapshots.py): while nonzero,
        # run_stream switches to its non-donating entry so pinned base-store
        # buffers survive the stream (DESIGN.md §11)
        self._pins = 0
        # cfg.metrics: StreamMetrics accumulated across run_stream calls
        # (device-resident; export via repro.obs.export.summary)
        if cfg is not None and cfg.metrics:
            from repro.obs.metrics import StreamMetrics
            self.metrics = StreamMetrics.empty()
        else:
            self.metrics = None

    # ----------------------------------------------------- state projections

    @property
    def graph(self) -> StreamingGraph:
        return self.state.graph

    @property
    def store(self) -> WalkStore:
        return self.state.store

    @property
    def pending(self) -> PendingBlocks:
        return self.state.pending

    @property
    def n_pending(self) -> int:
        """Filled pending blocks (host mirror — never syncs)."""
        return self._n_pending_host

    @property
    def epoch_counter(self) -> int:
        """Update-batch count (host mirror — never syncs)."""
        return self._epoch_host

    @property
    def last_affected(self) -> int:
        """|MAV| of the latest batch (lazy: syncs on access only)."""
        return int(self.state.last_affected)

    @property
    def total_affected(self) -> int:
        """Cumulative |MAV| over all batches (lazy: syncs on access only)."""
        return int(self.state.total_affected)

    @property
    def mav_overflowed(self) -> bool:
        """Sticky MAV gather-capacity flag (lazy: syncs on access only).

        Deferred-overflow contract: `run_stream` accumulates this on device
        across the whole stream; correctness requires the caller to size
        mav_capacity for its stream and check this once at stream end
        (tests/benchmarks enforce)."""
        return bool(self.state.overflow)

    # ----------------------------------------------------------- pin registry

    @property
    def pins_active(self) -> int:
        """Outstanding snapshot pins (serve/snapshots.py)."""
        return self._pins

    def pin_buffers(self) -> None:
        """Register a read pin: until the matching `unpin_buffers`, stream
        drivers run NON-donating so the current base-store buffers survive
        (the refcount half of the pin contract; the pending index copy is
        the other half — `Overlay.copy_pending`)."""
        self._pins += 1

    def unpin_buffers(self) -> None:
        """Release one read pin; donation resumes at refcount zero."""
        if self._pins <= 0:
            raise RuntimeError("unpin_buffers without a matching pin")
        self._pins -= 1

    # ------------------------------------------------------------------ API

    def insert_edges(self, key, src, dst):
        return self._update(key, src, dst, None, None)

    def delete_edges(self, key, src, dst):
        return self._update(key, None, None, src, dst)

    def update_batch(self, key, ins_src, ins_dst, del_src, del_dst):
        return self._update(key, ins_src, ins_dst, del_src, del_dst)

    def _update(self, key, ins_src, ins_dst, del_src, del_dst):
        """One graph update delta-G -> walk updates (Algorithm 2), fully
        jitted (fixed shapes via the pending buffer). Returns the affected
        count as a device scalar — no sync on the hot path."""
        e = lambda: jnp.zeros((0,), U32)
        ins_src = e() if ins_src is None else jnp.asarray(ins_src, U32)
        ins_dst = e() if ins_dst is None else jnp.asarray(ins_dst, U32)
        del_src = e() if del_src is None else jnp.asarray(del_src, U32)
        del_dst = e() if del_dst is None else jnp.asarray(del_dst, U32)

        if self._n_pending_host == self.max_pending:
            self.merge()

        s = self.state
        self.state = _update_jit(
            s.graph, s.store, s.pending, s.n_pending, s.epoch,
            s.total_affected, s.overflow,
            ins_src, ins_dst, del_src, del_dst, key,
            self.cfg, self.rewalk_capacity, self._mav_capacity())
        self._n_pending_host += 1
        self._epoch_host += 1

        if self.merge_policy == "eager":
            self.merge()
        return self.state.last_affected

    def run_stream(self, key, ins_src, ins_dst, del_src=None, del_dst=None,
                   return_masks: bool = False):
        """Consume a whole [n_batches, batch] edge stream in ONE jitted scan.

        Per scan step: graph merge -> MAV -> rewalk -> accumulator append,
        with the policy merges (pending-full / eager) folded in as
        `lax.cond` — the same `stream_step` the per-batch driver runs, so
        the resulting store is bit-identical (tests/test_stream.py). The
        carried state is donated: prior references to this engine's buffers
        (snapshots, overlays) are invalidated — unless a read pin is
        outstanding (`pin_buffers` / serve `pin()`), which switches this
        call to the non-donating entry so pinned snapshots stay valid;
        `materialize` remains the heavyweight alternative.

        `key` is split into one PRNG key per batch. Deletion streams are
        optional ([n_batches, d]; zero-width allowed). Returns the per-batch
        affected counts as an int32[n_batches] device array; MAV overflow is
        accumulated on device and surfaces once via `mav_overflowed`.

        With `return_masks=True` returns `(affected, aux)` where `aux` is a
        stacked `UpdateAux` ([n_batches, capacity] leaves): each step's
        affected-walk ids / lane validity / p_min — the per-step masks the
        downstream embedding maintainer consumes.

        With `cfg.metrics`, `self.metrics` (a StreamMetrics pytree, also
        donated) accumulates the stream's counters on device — the return
        value is unchanged; read `engine.metrics` at stream end.
        """
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

        # outstanding read pins suppress donation (pin contract, §11): the
        # pinned snapshots keep serving the pre-stream buffers bit-identically
        pinned = self._pins > 0
        if self.cfg.metrics:
            entry = (_run_stream_obs_jit_nodonate if pinned
                     else _run_stream_obs_jit)
            self.state, self.metrics, out = entry(
                self.state, self.metrics, keys, ins_src, ins_dst, del_src,
                del_dst, cfg=self.cfg, capacity=self.rewalk_capacity,
                mav_capacity=self._mav_capacity(),
                max_pending=self.max_pending,
                merge_policy=self.merge_policy, merge_impl=self.merge_impl,
                with_masks=return_masks)
        else:
            entry = _run_stream_jit_nodonate if pinned else _run_stream_jit
            self.state, out = entry(
                self.state, keys, ins_src, ins_dst, del_src, del_dst,
                cfg=self.cfg, capacity=self.rewalk_capacity,
                mav_capacity=self._mav_capacity(),
                max_pending=self.max_pending,
                merge_policy=self.merge_policy, merge_impl=self.merge_impl,
                with_masks=return_masks)

        # host mirrors: the merge schedule is data-independent
        self._n_pending_host = pending_after_stream(
            self._n_pending_host, n_batches, self.max_pending,
            self.merge_policy)
        self._epoch_host += n_batches
        return out

    def _mav_capacity(self) -> int:
        return self.mav_capacity or self.state.store.size

    def merge(self):
        """Consolidate pending version blocks into the base store (Merge).

        merge_impl="interleave" (default): O(T) searchsorted interleave
        (beyond-paper, §Perf); "lexsort": the paper-faithful bulk-sort path.
        Both produce identical stores (tested)."""
        if not self._n_pending_host:
            return
        self.state = _merge_state_jit(self.state, self.cfg, self.merge_impl)
        self._n_pending_host = 0

    def walk_matrix(self):
        """Read out the full corpus (triggers on-demand merge).

        For the mergeless (overlay) read of the same matrix, see
        serve/walk_queries.WalkQueryService.walk_matrix."""
        self.merge()
        store = self.state.store
        w = jnp.arange(store.n_walks, dtype=U32)
        start = walk_start_vertex(w, self.cfg.n_walks_per_vertex)
        return store.traverse(w, start, store.length - 1)

    def overlay(self) -> Overlay:
        """Mergeless read view over base + pending (valid until the next
        update donates the pending buffer — serving layers re-build per
        engine state, see serve/walk_queries.py)."""
        return Overlay.build(self.state.store, self.state.pending)

    # per-batch version-block views (used by benchmarks)
    @property
    def blocks(self):
        p = self.state.pending
        return [PendingBlocks(p.owner[i], p.code[i], p.epoch[i], p.slot[i])
                for i in range(self._n_pending_host)]


# ---------------------------------------------------------------- jitted core


def _apply_update(state: EngineState, ins_src, ins_dst, del_src, del_dst,
                  key, cfg: WalkConfig, capacity: int, mav_capacity: int):
    """One Algorithm-2 update appended as a pending version block (pure).

    Returns (EngineState, UpdateAux) — the aux names the affected walks so
    callers (the maintainer pipeline) can act on exactly this update's
    re-walked set without re-deriving the MAV."""
    # 1. apply the graph update (paper: MAV is built while updating)
    graph = state.graph.apply_batch(ins_src, ins_dst, del_src, del_dst)
    store, pending = state.store, state.pending
    new_epoch = state.epoch + jnp.asarray(1, U32)

    # 2. MAV — output-sensitive (paper §6.1): only the touched vertices'
    # walk-tree SEGMENTS of the base store are gathered and decoded (the
    # shared core/mav.py segment gather); pending entries carry slots
    # explicitly, so they join the reduction without a u64 unpair.
    touched_v = jnp.zeros((store.n_vertices,), bool)
    for arr in (ins_src, ins_dst, del_src, del_dst):
        if arr.shape[0] > 0:
            touched_v = touched_v.at[arr.astype(I32)].set(True)

    g_owner, g_code, g_epoch, g_valid, total = gather_touched_segments(
        store, touched_v, mav_capacity)
    overflow = total > mav_capacity
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

    mav = _pmin_from_wpo(
        jnp.concatenate([g_w, p_w]), jnp.concatenate([g_p, p_p]),
        jnp.concatenate([g_owner, p_owner]),
        jnp.concatenate([g_epoch, p_epoch]), store.slot_epoch,
        jnp.concatenate([g_touched, p_touched]),
        jnp.concatenate([g_valid, p_valid]),
        store.length, store.n_walks)

    # 3-5. re-walk affected walks into a fresh version block
    block, slot_epoch, n_aff, aux = _rewalk(key, graph, store, pending, mav,
                                            new_epoch, cfg, capacity)
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
    return EngineState(
        graph=graph, store=store.replace(slot_epoch=slot_epoch),
        pending=pending, n_pending=state.n_pending + 1, epoch=new_epoch,
        last_affected=n_aff, total_affected=state.total_affected + n_aff,
        overflow=state.overflow | overflow), aux


def _merged_store(store: WalkStore, pending: PendingBlocks,
                  merge_impl: str) -> WalkStore:
    if merge_impl == "interleave":
        return merge_interleave(store, pending.owner.reshape(-1),
                                pending.code.reshape(-1),
                                pending.epoch.reshape(-1),
                                pending.slot.reshape(-1))
    owner = jnp.concatenate([store.owner, pending.owner.reshape(-1)])
    code = jnp.concatenate([store.code, pending.code.reshape(-1)])
    epoch = jnp.concatenate([store.epoch, pending.epoch.reshape(-1)])
    return merge_consolidate(owner, code, epoch, store)


def _merge_state(state: EngineState, cfg: WalkConfig,
                 merge_impl: str) -> EngineState:
    return state.replace(
        store=_merged_store(state.store, state.pending, merge_impl),
        pending=PendingBlocks.empty_like(state.pending),
        n_pending=jnp.asarray(0, I32))


_merge_state_jit = jax.jit(_merge_state,
                           static_argnames=("cfg", "merge_impl"))


def consolidate(state: EngineState, cfg: WalkConfig,
                merge_impl: str = "interleave") -> EngineState:
    """PUBLIC merge entry point: fold every pending version block into the
    base store and reset the accumulator (the paper's Merge as a pure
    state -> state function).

    This is the API external drivers build on (distr/engine.py calls it
    after its sharded scan so the returned store is self-contained; the
    stateful `WalkEngine.merge` is the same function behind a host-side
    fill-level mirror). Merging an empty accumulator is a content no-op, so
    callers may invoke it unconditionally at stream end."""
    return _merge_state_jit(state, cfg, merge_impl)


def run_stream(state: EngineState, keys, ins_src, ins_dst, del_src, del_dst,
               *, cfg: WalkConfig, capacity: int, mav_capacity: int,
               max_pending: int, merge_policy: str = "on-demand",
               merge_impl: str = "interleave", with_masks: bool = False,
               metrics=None):
    """PUBLIC scan-pipelined driver: a whole [n_batches, batch] mixed
    insert+delete stream through `stream_step`, one jitted `lax.scan`.

    The functional twin of `WalkEngine.run_stream` for callers that manage
    `EngineState` directly (the distributed engine, notebooks): takes
    per-batch `keys` ([n_batches, 2], i.e. `jax.random.split(key,
    n_batches)`) and stacked streams, returns `(state, affected)` — or
    `(state, (affected, UpdateAux))` with `with_masks=True`. Deletion
    streams may be zero-width ([n_batches, 0]). The input `state` is DONATED
    (in-place buffer reuse across the stream): prior references to its
    buffers are invalidated.

    With `cfg.metrics` set, a `StreamMetrics` pytree rides the carry
    (donated too; pass `metrics` to continue accumulating a prior stream's
    counters, default fresh) and the return gains a trailing element:
    `(state, affected[, aux], metrics)`."""
    if cfg.metrics:
        if metrics is None:
            from repro.obs.metrics import StreamMetrics
            metrics = StreamMetrics.empty()
        state, metrics, out = _run_stream_obs_jit(
            state, metrics, keys, ins_src, ins_dst, del_src, del_dst,
            cfg=cfg, capacity=capacity, mav_capacity=mav_capacity,
            max_pending=max_pending, merge_policy=merge_policy,
            merge_impl=merge_impl, with_masks=with_masks)
        return state, out, metrics
    return _run_stream_jit(state, keys, ins_src, ins_dst, del_src, del_dst,
                           cfg=cfg, capacity=capacity,
                           mav_capacity=mav_capacity,
                           max_pending=max_pending,
                           merge_policy=merge_policy, merge_impl=merge_impl,
                           with_masks=with_masks)


def pending_after_stream(n_pending: int, n_batches: int, max_pending: int,
                         merge_policy: str) -> int:
    """Host-side pending fill level after `n_batches` `stream_step`s.

    The single closed form of stream_step's (data-independent) merge
    schedule: eager resets after every batch; on-demand merges exactly when
    the buffer is full at batch entry, then appends — so the fill level
    cycles with period `max_pending` and never rests at 0 once a batch has
    run. Keep in lockstep with stream_step's cond/eager logic."""
    if n_batches <= 0:
        return n_pending
    if merge_policy == "eager":
        return 0
    return (n_pending + n_batches - 1) % max_pending + 1


def stream_step_aux(state: EngineState, key, ins_src, ins_dst, del_src,
                    del_dst, cfg: WalkConfig, capacity: int,
                    mav_capacity: int, max_pending: int, merge_policy: str,
                    merge_impl: str, metrics=None):
    """One streaming-pipeline step (pure): policy merges + Algorithm 2.

    Returns (EngineState, UpdateAux). The aux identifies THIS step's
    affected walks — the hook the downstream maintainer co-schedules its
    incremental SGNS retraining on (downstream/maintainer.py). Note the aux
    is valid against the post-step state regardless of policy: an eager
    merge folds the pending block into the base, but the affected walk ids
    and p_min are store-layout-independent.

    With a `repro.obs.metrics.StreamMetrics` passed as `metrics` the step
    additionally folds this update into the counters and returns
    (state, aux, metrics). The metrics path only READS the engine carry
    (between the Algorithm-2 apply and any eager merge, while the fresh
    version block is still pending) — engine outputs are bit-identical and
    the default `metrics=None` path traces the exact same HLO as before
    (tests/test_obs.py)."""
    merge = partial(_merge_state, cfg=cfg, merge_impl=merge_impl)
    forced = state.n_pending >= jnp.asarray(max_pending, I32)
    overflow_before = state.overflow
    state = jax.lax.cond(forced, merge, lambda s: s, state)
    state, aux = _apply_update(state, ins_src, ins_dst, del_src, del_dst,
                               key, cfg, capacity, mav_capacity)
    if metrics is not None:
        from repro.obs.metrics import record_engine_step
        metrics = record_engine_step(metrics, state, aux,
                                     state.n_pending - 1, forced,
                                     overflow_before, cfg,
                                     eager=merge_policy == "eager",
                                     key=key)
    if merge_policy == "eager":
        state = merge(state)
    if metrics is not None:
        return state, aux, metrics
    return state, aux


def stream_step(state: EngineState, key, ins_src, ins_dst, del_src, del_dst,
                cfg: WalkConfig, capacity: int, mav_capacity: int,
                max_pending: int, merge_policy: str,
                merge_impl: str) -> EngineState:
    """THE shared update step — the per-batch driver, the `run_stream` scan,
    and the distributed engine all run this exact function, which is what
    makes the three drivers bit-identical on the same key stream."""
    state, _ = stream_step_aux(state, key, ins_src, ins_dst, del_src,
                               del_dst, cfg, capacity, mav_capacity,
                               max_pending, merge_policy, merge_impl)
    return state


@partial(jax.jit, static_argnames=("cfg", "capacity", "mav_capacity"),
         donate_argnums=(2,))
def _update_jit(graph, store, pending, n_pending, epoch, total_affected,
                overflow, ins_src, ins_dst, del_src, del_dst, key,
                cfg: WalkConfig, capacity: int,
                mav_capacity: int) -> EngineState:
    """Per-batch driver entry: donates only the pending buffer, so snapshots
    of the base store taken between batches stay valid (DESIGN.md §5)."""
    state = EngineState(graph=graph, store=store, pending=pending,
                        n_pending=n_pending, epoch=epoch,
                        last_affected=jnp.asarray(0, I32),
                        total_affected=total_affected, overflow=overflow)
    state, _ = _apply_update(state, ins_src, ins_dst, del_src, del_dst, key,
                             cfg, capacity, mav_capacity)
    return state


def _run_stream_body(state: EngineState, keys, ins_src, ins_dst, del_src,
                     del_dst, cfg: WalkConfig, capacity: int,
                     mav_capacity: int, max_pending: int, merge_policy: str,
                     merge_impl: str, with_masks: bool = False):
    """The scan-pipelined driver: n_batches updates, zero host round-trips.

    The whole EngineState is donated in the default `_run_stream_jit` entry
    (in-place buffer reuse across the stream); overflow/affected ride the
    carry as device scalars. With `with_masks` the scan also emits each
    step's UpdateAux — the per-step affected-walk sets (not just the
    end-of-stream scalar), stacked to [n_batches, capacity], for consumers
    that retrain on exactly the walks each batch touched."""

    def body(s, xs):
        k, i_s, i_d, d_s, d_d = xs
        s, aux = stream_step_aux(s, k, i_s, i_d, d_s, d_d, cfg, capacity,
                                 mav_capacity, max_pending, merge_policy,
                                 merge_impl)
        out = (s.last_affected, aux) if with_masks else s.last_affected
        return s, out

    return jax.lax.scan(body, state, (keys, ins_src, ins_dst, del_src,
                                      del_dst))


_STREAM_STATICS = ("cfg", "capacity", "mav_capacity", "max_pending",
                   "merge_policy", "merge_impl", "with_masks")

_run_stream_jit = jax.jit(_run_stream_body, static_argnames=_STREAM_STATICS,
                          donate_argnums=(0,))
# the pinned-reader variant (DESIGN.md §11): identical scan, NO donation —
# the pre-stream base-store buffers stay alive for outstanding snapshot
# pins. Selected by WalkEngine.run_stream while `pin_buffers` holds a
# nonzero refcount; costs one extra state allocation per stream call.
_run_stream_jit_nodonate = jax.jit(_run_stream_body,
                                   static_argnames=_STREAM_STATICS)


def _run_stream_obs_body(state: EngineState, metrics, keys, ins_src,
                         ins_dst, del_src, del_dst, cfg: WalkConfig,
                         capacity: int, mav_capacity: int, max_pending: int,
                         merge_policy: str, merge_impl: str,
                         with_masks: bool = False):
    """`_run_stream_body` with a StreamMetrics pytree riding the scan carry.

    A SEPARATE jit entry (not a flag on `_run_stream_jit`) so the OFF path
    keeps its exact pre-observability trace; the metrics pytree is donated
    alongside the engine carry and accumulates on device — observing a
    stream adds zero host round-trips (DESIGN.md §10)."""

    def body(carry, xs):
        s, m = carry
        k, i_s, i_d, d_s, d_d = xs
        s, aux, m = stream_step_aux(s, k, i_s, i_d, d_s, d_d, cfg, capacity,
                                    mav_capacity, max_pending, merge_policy,
                                    merge_impl, metrics=m)
        out = (s.last_affected, aux) if with_masks else s.last_affected
        return (s, m), out

    (state, metrics), out = jax.lax.scan(
        body, (state, metrics), (keys, ins_src, ins_dst, del_src, del_dst))
    return state, metrics, out


_run_stream_obs_jit = jax.jit(_run_stream_obs_body,
                              static_argnames=_STREAM_STATICS,
                              donate_argnums=(0, 1))
# pinned-reader variant: engine state NOT donated; the metrics pytree holds
# no reader-visible buffers, so it keeps its donation either way.
_run_stream_obs_jit_nodonate = jax.jit(_run_stream_obs_body,
                                       static_argnames=_STREAM_STATICS,
                                       donate_argnums=(1,))


class VersionBlock(NamedTuple):
    owner: jax.Array
    code: jax.Array
    epoch: jax.Array
    slot: jax.Array
    n_new: jax.Array


@partial(jax.jit, static_argnames=("cfg", "capacity"))
def _rewalk(key, graph: StreamingGraph, store: WalkStore,
            pending: Optional[PendingBlocks], mav: MAV, new_epoch,
            cfg: WalkConfig, capacity: int):
    """Lines 4-11 of Algorithm 2: sample new walk parts, build accumulator I.

    Re-walks up to `capacity` affected walks in parallel. For each affected
    walk the vertex AT p_min is kept (mav.v_min) and positions p_min+1..l-1
    are re-sampled; triplets at positions p_min..l-1 are re-encoded (the
    triplet at p_min changes its next-pointer; the terminal one points to
    itself).

    When `cfg.megakernel` selects a fused backend (registry default: off),
    the per-step FINDNEXT decode + intersection + sampling + write-back run
    as ONE fused dispatch per step (kernels/megakernel.py) with prefix
    traversal folded into the scan carry — emitted triplets are
    bit-identical to the unfused path on the same key
    (tests/test_megakernel.py), so every driver inherits the fusion from
    the config alone."""
    length = store.length
    affected = mav.p_min < length
    walk_ids, lane_valid = compact_nonzero(affected, size=capacity)
    walk_ids = walk_ids.astype(U32)
    p_min = mav.p_min[walk_ids]
    v_at_pmin = mav.v_min[walk_ids]
    ps = jnp.arange(length, dtype=I32)

    req = (cfg.megakernel if cfg.megakernel != "auto"
           else megakernel.default_backend_request())
    backend = megakernel.resolve_backend(req)

    if backend is not None:
        megakernel.check_supported(store, cfg, backend)
        owners, codes, emits = megakernel.fused_scan(
            key, graph, store, pending, walk_ids, lane_valid, p_min,
            v_at_pmin, cfg, backend)
    else:
        if cfg.model.order == 2:
            start = walk_start_vertex(walk_ids, cfg.n_walks_per_vertex)
            # O(p_min) FINDNEXTs per walk; paper notes the same requirement.
            # The prefix must reflect base + pending (earlier version blocks
            # may have rewritten prefix slots), so it reads through the
            # overlay — this is what lets node2vec streams run without
            # per-batch merges.
            view = (store if pending is None
                    else Overlay.build(store, pending))
            prefix = view.traverse(walk_ids, start, length - 1)
            prev0 = prefix[jnp.arange(capacity), jnp.maximum(p_min - 1, 0)]
        else:
            prev0 = v_at_pmin

        w64 = walk_ids.astype(U64)
        l64 = jnp.asarray(length, U64)

        def step(carry, inp):
            cur, prev = carry
            p, kp = inp
            cur = jnp.where(p == p_min, v_at_pmin, cur)
            nxt = sample_next(kp, graph, cur, prev, cfg.model)
            is_term = p == length - 1
            nxt_eff = jnp.where(is_term, cur, nxt)
            code = pairing.szudzik_pair(w64 * l64 + p.astype(U64),
                                        nxt_eff.astype(U64))
            emit = lane_valid & (p >= p_min)
            owner = cur
            prev_new = jnp.where(p >= p_min, cur, prev)
            cur_new = jnp.where((p >= p_min) & ~is_term, nxt, cur)
            return (cur_new, prev_new), (owner, code, emit)

        keys = jax.random.split(key, length)
        (_, _), (owners, codes, emits) = jax.lax.scan(
            step, (v_at_pmin, prev0), (ps, keys))
    owners = owners.T.reshape(-1)        # [capacity * l]
    codes = codes.T.reshape(-1)
    emits = emits.T.reshape(-1)

    epoch = jnp.where(emits, new_epoch, PAD_EPOCH).astype(U32)
    owners = jnp.where(emits, owners, 0).astype(U32)
    codes = jnp.where(emits, codes, jnp.asarray(0, U64))

    # 5. bump slot versions for all rewritten slots (w, p >= p_min)
    slot_w = jnp.repeat(walk_ids.astype(I32), length)
    slot_p = jnp.tile(ps, capacity)
    slots = jnp.clip(slot_w * length + slot_p, 0, store.n_walks * length - 1)
    # max with 0 is a no-op for non-emitting lanes, so no masking needed
    slot_epoch = store.slot_epoch.at[slots].max(
        jnp.where(emits, new_epoch, jnp.asarray(0, U32)))

    n_aff = jnp.sum(affected)
    block = VersionBlock(owner=owners, code=codes, epoch=epoch,
                         slot=jnp.where(emits, slots, 0).astype(I32),
                         n_new=jnp.sum(emits).astype(I32))
    aux = UpdateAux(walk_ids=walk_ids, lane_valid=lane_valid, p_min=p_min)
    return block, slot_epoch, n_aff, aux


def merge_interleave(base: WalkStore, acc_owner, acc_code, acc_epoch,
                     acc_slot) -> WalkStore:
    """Beyond-paper Merge (§Perf wharf-stream iteration): O(T) interleave
    instead of an O(T log T) three-key lexsort.

    The base store is ALREADY sorted by (owner, code); only the accumulator
    (|I| << T) needs sorting. Output positions:
      live base[i] -> i - dead_prefix[i] + #acc_with_pos<=i
      acc[j]       -> live_prefix[pos_j] + rank_j
    ~6 bandwidth passes over T versus ~30 for the lexsort; identical result
    (tests/test_core.py::test_merge_interleave_equals_lexsort).
    """
    t = base.size
    a = acc_owner.shape[0]
    length, n_walks = base.length, base.n_walks

    # liveness of base entries (slot-epoch check, as in the lexsort path)
    f, _ = pairing.szudzik_unpair(base.code)
    slot_b = jnp.clip(f, 0, n_walks * length - 1).astype(I32)
    live_b = base.epoch == base.slot_epoch[slot_b]
    # accumulator liveness (stale pending rows lose to newer epochs)
    live_a = (acc_epoch != PAD_EPOCH) & (
        acc_epoch == base.slot_epoch[jnp.clip(acc_slot, 0,
                                              n_walks * length - 1)])

    # sort the (small) accumulator by (owner, code); dead rows to the end
    # under an owner key no vertex id reaches (a two-key sort: each key of
    # a TPU sort costs minutes of compile)
    order_a = jnp.lexsort((acc_code, jnp.where(live_a, acc_owner,
                                               jnp.asarray(0xFFFFFFFF, U32))))
    acc_owner = acc_owner[order_a]
    acc_code = acc_code[order_a]
    acc_epoch = acc_epoch[order_a]
    live_a = live_a[order_a]

    # insertion position of each acc entry in the base (owner segment bounds
    # from the hybrid-tree offsets + in-segment binary search on code)
    from repro.core.utils import seg_searchsorted
    seg_lo = base.offsets[jnp.clip(acc_owner.astype(I32), 0,
                                   base.n_vertices - 1)]
    seg_hi = base.offsets[jnp.clip(acc_owner.astype(I32) + 1, 0,
                                   base.n_vertices)]
    pos_a = seg_searchsorted(base.code, seg_lo, seg_hi, acc_code,
                             side="left")
    pos_a = jnp.where(live_a, pos_a, t)  # dead acc rows park at the end

    live_prefix = jnp.cumsum(live_b, dtype=I32)           # live base[<=i]
    # acc entries inserted before base[i] = those with pos <= i
    pos_sorted = jnp.sort(pos_a)
    acc_before = jnp.searchsorted(pos_sorted, jnp.arange(t, dtype=I32),
                                  side="right").astype(I32)
    out_base = live_prefix - 1 + acc_before               # for live entries
    # acc is sorted by (owner, code) and pos_a is monotone in that order, so
    # the sorted index j IS the count of acc rows placed before row j
    rank_a = jnp.arange(a, dtype=I32)
    lp_at = jnp.where(pos_a > 0,
                      live_prefix[jnp.clip(pos_a - 1, 0, t - 1)], 0)
    out_acc = jnp.where(live_a, lp_at + rank_a, t)

    owner_out = jnp.zeros((t,), U32)
    code_out = jnp.zeros((t,), U64)
    epoch_out = jnp.zeros((t,), U32)
    ob = jnp.where(live_b, out_base, t)  # drop dead base rows
    owner_out = owner_out.at[ob].set(base.owner, mode="drop")
    code_out = code_out.at[ob].set(base.code, mode="drop")
    epoch_out = epoch_out.at[ob].set(base.epoch, mode="drop")
    oa = jnp.where(live_a, out_acc, t)
    owner_out = owner_out.at[oa].set(acc_owner, mode="drop")
    code_out = code_out.at[oa].set(acc_code, mode="drop")
    epoch_out = epoch_out.at[oa].set(acc_epoch, mode="drop")
    # dirty-chunk re-encode: prev=base keeps packed rows of chunks the
    # accumulator never touched bit-identical (no full-corpus round-trip)
    return WalkStore.from_sorted(owner_out, code_out, epoch_out,
                                 base.slot_epoch, length, n_walks,
                                 base.n_vertices, base.chunk_b, prev=base)


def merge_consolidate(owner, code, epoch, base: WalkStore) -> WalkStore:
    """Sort-merge eviction: keep, per corpus slot f, the max-epoch entry.

    The TPU-native MultiInsert+Merge (paper §6.2): one lexsort pass over
    base+blocks replaces per-element tree insertion — the bandwidth-optimal
    bulk form with identical semantics."""
    t = base.size
    f, _ = pairing.szudzik_unpair(code)
    slot = jnp.clip(f.astype(jnp.int64), 0, base.n_walks * base.length - 1)
    live = (epoch != PAD_EPOCH) & (epoch == base.slot_epoch[slot.astype(I32)])
    # among live entries duplicates cannot share a slot (each slot is bumped
    # once per epoch and stale epochs fail the check) -> exactly t live.
    order = jnp.lexsort((code, owner, ~live))
    owner = owner[order][:t]
    code = code[order][:t]
    epoch = epoch[order][:t]
    # the first t rows are the live set sorted by (owner, code) -> from_sorted
    # directly; prev=base re-encodes only the chunks the merge dirtied
    return WalkStore.from_sorted(owner, code, epoch, base.slot_epoch,
                                 base.length, base.n_walks, base.n_vertices,
                                 chunk_b=base.chunk_b, prev=base)
