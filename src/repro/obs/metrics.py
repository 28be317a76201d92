"""`StreamMetrics`: device-side stream counters carried through the scans.

The counters ride the jitted scan carry exactly like `EngineState`'s own
scalars (epoch, total_affected, overflow) — accumulated on device, read once
at stream end — so observing a stream costs zero mid-stream host syncs and
composes with buffer donation (the metrics pytree is donated alongside the
engine carry).

The hard contract (tests/test_obs.py):

  * metrics OFF (the `WalkConfig.metrics` default) is compiled out — the
    drivers trace the exact pre-observability HLO. Every op this module
    adds to a trace is wrapped in ``jax.named_scope("obs_metrics")`` so a
    leak into the OFF path is detectable in lowered text, and the OFF-path
    drivers never call into this module at all.
  * metrics ON leaves engine outputs bit-identical: counters only READ the
    engine carry (and consume no PRNG), never feed back into it.

Counter semantics (what the paper's rate claims need):

  * ``affected_total`` / ``affected_max`` — per-step |MAV| accounting.
  * ``pmin_hist`` — fixed-bucket histogram of the re-walked suffix
    fraction (l - p_min) / l over affected lanes: the pruning-efficiency
    distribution (bucket 0 = nearly-free updates, last bucket = full
    re-walks).
  * ``pending_hwm`` — pending-buffer fill high-water mark (post-append,
    before any eager merge).
  * ``merges_forced`` / ``merges_eager`` — in-scan merges by cause
    (pending-full `lax.cond` vs eager policy).
  * ``deg_fallback_lanes`` — order-2 factorized streams only: emitted
    non-terminal lane-steps whose CURRENT vertex degree exceeds
    `model.dmax`, i.e. sampling steps that took (at least) the rejection
    fallback via the deg(v) trigger. Computed post-hoc from the emitted
    version block, so every rewalk backend (unfused or megakernel) is
    covered without sampler plumbing; the deg(prev)-only trigger is not
    counted (documented lower bound).
  * ``findnext_queries`` / ``findnext_chunks`` — order-2 unfused rewalks
    only: the prefix traversal's FINDNEXT queries (every lane, positions
    0..l-2) and the distinct chunks the packed kernel decodes for them
    (each query's candidate-range span, at most the window). Their ratio
    is the chunks per query; computed post-hoc from the traversal's path,
    so it counts the kernel's window whatever backend ran.
  * ``handoff_sent`` / ``handoff_cross`` / ``handoff_max_load`` — sharded
    engine only, per shard: lanes routed through the `all_to_all` frontier
    exchange (sent = all continuing lanes incl. the self-slab row, cross =
    lanes leaving this shard), and the per-step max lanes aimed at one
    destination (the slab-pressure / imbalance figure).
  * ``overflow_first_epoch`` — sticky overflow provenance: the first epoch
    at which each deferred-overflow source (graph insert, store merge, MAV
    gather, handoff slab) tripped; `NEVER` if it never did. The engine's
    own `overflow` flag stays the single OR as before — this only records
    which capacity to resize.
  * ``staleness`` — nested `obs.staleness.StalenessMetrics`: the per-walk
    epoch-lag histogram, stale-walk fraction, and the K-sample divergence
    auditor (single-host drivers thread the step key in; the sharded
    driver records lag only — slot_epoch is replicated, the auditor is
    not shardable without a traversal collective).

Cross-shard counters are per-shard partial sums; `combine_shards` reduces a
[S, ...]-stacked metrics pytree (replicated counters take shard 0, handoff
counters sum/max, provenance epochs min).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.obs.staleness import StalenessMetrics, record_audit, record_lag

I32 = jnp.int32
U32 = jnp.uint32

PMIN_BUCKETS = 8
OVERFLOW_SOURCES = ("graph", "store_merge", "mav_gather", "handoff_slab")
OVF_GRAPH, OVF_STORE, OVF_MAV, OVF_SLAB = range(4)
NEVER = 0xFFFFFFFF  # u32 sentinel: overflow source never tripped


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class StreamMetrics:
    """Device counter pytree (all leaves device scalars/small vectors)."""

    n_steps: jax.Array              # i32 [] stream steps observed
    affected_total: jax.Array       # i32 [] cumulative |MAV|
    affected_max: jax.Array         # i32 [] max per-step |MAV|
    pmin_hist: jax.Array            # i32 [PMIN_BUCKETS] suffix-fraction hist
    pending_hwm: jax.Array          # i32 [] pending fill high-water mark
    merges_forced: jax.Array        # i32 [] pending-full in-scan merges
    merges_eager: jax.Array         # i32 [] eager-policy in-scan merges
    deg_fallback_lanes: jax.Array   # i32 [] deg>dmax fallback lane-steps
    findnext_queries: jax.Array     # i32 [] order-2 traversal FINDNEXTs
    findnext_chunks: jax.Array      # i32 [] chunks decoded for them
    handoff_sent: jax.Array         # i32 [] lanes routed (this shard)
    handoff_cross: jax.Array        # i32 [] lanes leaving this shard
    handoff_max_load: jax.Array     # i32 [] max lanes to one dest per step
    overflow_first_epoch: jax.Array  # u32 [4] first-trip epoch per source
    staleness: StalenessMetrics     # nested walk-freshness counters (§12)

    def replace(self, **kw) -> "StreamMetrics":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def empty() -> "StreamMetrics":
        # one DISTINCT buffer per field: the pytree is donated to the
        # stream scans, and donating one shared zero buffer twice is an
        # XLA runtime error
        z = lambda: jnp.zeros((), I32)
        return StreamMetrics(
            n_steps=z(), affected_total=z(), affected_max=z(),
            pmin_hist=jnp.zeros((PMIN_BUCKETS,), I32),
            pending_hwm=z(), merges_forced=z(), merges_eager=z(),
            deg_fallback_lanes=z(), findnext_queries=z(),
            findnext_chunks=z(), handoff_sent=z(), handoff_cross=z(),
            handoff_max_load=z(),
            overflow_first_epoch=jnp.full((len(OVERFLOW_SOURCES),), NEVER,
                                          U32),
            staleness=StalenessMetrics.empty())


def pmin_bucket_counts(p_min, lane_valid, length: int):
    """i32[PMIN_BUCKETS] counts of (l - p_min)/l over the valid lanes.

    Bucket b covers suffix fractions [b/NB, (b+1)/NB); a full re-walk
    (p_min = 0, fraction 1.0) lands in the last bucket."""
    suffix = jnp.asarray(length, I32) - jnp.asarray(p_min, I32)
    bucket = jnp.clip((suffix * PMIN_BUCKETS) // length, 0, PMIN_BUCKETS - 1)
    return (jnp.zeros((PMIN_BUCKETS,), I32)
            .at[bucket].add(jnp.asarray(lane_valid, I32)))


def deg_fallback_count(graph, block_owner, block_epoch, length: int, model):
    """deg>dmax fallback lane-steps of one emitted version block.

    `block_owner`/`block_epoch` are the lane-major [capacity * l] columns of
    the block just appended: entry i belongs to position i % l, its owner is
    the vertex the step sampled FROM, and a PAD_EPOCH entry was never
    emitted. Only non-terminal positions sample. Static zero for models
    without a factorized fallback (order 1, rejection sampler)."""
    if model.order != 2 or model.sampler != "factorized":
        return jnp.asarray(0, I32)
    from repro.core.store import PAD_EPOCH
    n = block_owner.shape[0]
    p = jnp.arange(n, dtype=I32) % length
    emitted = (block_epoch != PAD_EPOCH) & (p < length - 1)
    deg = graph.degree(jnp.clip(block_owner.astype(I32), 0,
                                graph.n_vertices - 1))
    return jnp.sum(emitted & (deg > model.dmax)).astype(I32)


def findnext_engagement(store, walk_ids, prefix):
    """(queries, chunks) of one order-2 prefix traversal, i32 scalars.

    `prefix` is the [capacity, l] path `store.traverse` (or the overlay's)
    returned for `walk_ids`: step p asked FINDNEXT (prefix[:, p], w, p)
    for p in 0..l-2, and the base store's packed kernel decoded
    `store.findnext_chunks` distinct chunks for each."""
    with jax.named_scope("obs_metrics"):
        cap, length = prefix.shape
        v = prefix[:, :-1].reshape(-1)
        w = jnp.repeat(walk_ids, length - 1)
        p = jnp.tile(jnp.arange(length - 1, dtype=U32), cap)
        chunks = jnp.sum(store.findnext_chunks(v, w, p), dtype=I32)
        return jnp.asarray(v.shape[0], I32), chunks


def record_overflow(m: StreamMetrics, source: int, tripped, epoch
                    ) -> StreamMetrics:
    """Stamp `epoch` as `source`'s first-trip epoch if it tripped now and
    never had before (sticky-first semantics)."""
    with jax.named_scope("obs_metrics"):
        first = m.overflow_first_epoch
        hit = tripped & (first[source] == jnp.asarray(NEVER, U32))
        first = first.at[source].set(
            jnp.where(hit, jnp.asarray(epoch, U32), first[source]))
        return m.replace(overflow_first_epoch=first)


def record_engine_step(m: StreamMetrics, state, aux, block_row, forced_merge,
                       overflow_before, cfg, eager: bool,
                       key=None) -> StreamMetrics:
    """Fold one single-host `stream_step` into the counters.

    Called between the Algorithm-2 apply and any eager merge (so the
    just-appended version block at `block_row` is still in the pending
    buffer); `state` is the post-apply engine carry, `aux` its UpdateAux.
    The only single-host deferred-overflow source is the MAV gather.
    `key` is the STEP key (already consumed by the rewalk): the divergence
    auditor folds an independent sample stream off it, so passing it keeps
    engine outputs bit-identical; `key=None` skips the auditor."""
    with jax.named_scope("obs_metrics"):
        length = state.store.length
        owner = jax.lax.dynamic_index_in_dim(state.pending.owner, block_row,
                                             0, keepdims=False)
        epoch_col = jax.lax.dynamic_index_in_dim(state.pending.epoch,
                                                 block_row, 0,
                                                 keepdims=False)
        one = jnp.asarray(1, I32)
        st = record_lag(m.staleness, state)
        if key is not None:
            st = record_audit(st, state, key, cfg)
        m = m.replace(
            n_steps=m.n_steps + one,
            affected_total=m.affected_total + state.last_affected,
            affected_max=jnp.maximum(m.affected_max, state.last_affected),
            pmin_hist=m.pmin_hist + pmin_bucket_counts(
                aux.p_min, aux.lane_valid, length),
            pending_hwm=jnp.maximum(m.pending_hwm, state.n_pending),
            merges_forced=m.merges_forced + forced_merge.astype(I32),
            merges_eager=m.merges_eager + (one if eager else 0),
            deg_fallback_lanes=m.deg_fallback_lanes + deg_fallback_count(
                state.graph, owner, epoch_col, length, cfg.model),
            staleness=st)
        if aux.findnext_queries is not None:
            m = m.replace(
                findnext_queries=m.findnext_queries + aux.findnext_queries,
                findnext_chunks=m.findnext_chunks + aux.findnext_chunks)
    return record_overflow(m, OVF_MAV, state.overflow & ~overflow_before,
                           state.epoch)


def record_sharded_step(m: StreamMetrics, state, obs: dict, forced_merge,
                        merge_tripped, eager: bool) -> StreamMetrics:
    """Fold one sharded `stream_step` into this shard's counters.

    `obs` is the per-step observation dict `_sharded_apply_update` returns
    with `with_obs=True`: the replicated pmin histogram plus this shard's
    handoff volumes and per-source overflow flags. Walk lag records too
    (slot_epoch is replicated); the divergence auditor does not — a
    sharded replay would need a cross-shard traversal collective, so the
    audit counters stay 0 on sharded runs."""
    with jax.named_scope("obs_metrics"):
        one = jnp.asarray(1, I32)
        m = m.replace(
            staleness=record_lag(m.staleness, state),
            n_steps=m.n_steps + one,
            affected_total=m.affected_total + state.last_affected,
            affected_max=jnp.maximum(m.affected_max, state.last_affected),
            pmin_hist=m.pmin_hist + obs["pmin_hist"],
            pending_hwm=jnp.maximum(m.pending_hwm, state.n_pending),
            merges_forced=m.merges_forced + forced_merge.astype(I32),
            merges_eager=m.merges_eager + (one if eager else 0),
            handoff_sent=m.handoff_sent + obs["handoff_sent"],
            handoff_cross=m.handoff_cross + obs["handoff_cross"],
            handoff_max_load=jnp.maximum(m.handoff_max_load,
                                         obs["handoff_max_load"]))
    epoch = state.epoch
    m = record_overflow(m, OVF_GRAPH, obs["graph_overflow"], epoch)
    m = record_overflow(m, OVF_STORE, merge_tripped, epoch)
    m = record_overflow(m, OVF_MAV, obs["mav_overflow"], epoch)
    return record_overflow(m, OVF_SLAB, obs["handoff_overflow"], epoch)


def combine_shards(stacked: StreamMetrics) -> StreamMetrics:
    """Reduce a [S, ...]-stacked per-shard metrics pytree to global totals.

    Replicated counters (steps, |MAV|, histogram, pending, merges, deg
    fallback) are identical on every shard — take shard 0; per-shard
    handoff volumes sum (max-load takes the max); provenance epochs take
    the earliest trip."""
    first = jax.tree.map(lambda leaf: leaf[0], stacked)
    return first.replace(
        handoff_sent=jnp.sum(stacked.handoff_sent).astype(I32),
        handoff_cross=jnp.sum(stacked.handoff_cross).astype(I32),
        handoff_max_load=jnp.max(stacked.handoff_max_load).astype(I32),
        overflow_first_epoch=jnp.min(stacked.overflow_first_epoch, axis=0))
