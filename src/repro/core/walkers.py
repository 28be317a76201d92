"""Random-walk models (paper §3.2): DeepWalk (1st order) and node2vec (2nd order).

DeepWalk: uniform over current neighbors. node2vec(p, q) weighs each neighbor
x of the current vertex v by the second-order bias

    alpha(prev, x) = 1/p  if x == prev
                     1    if x is a neighbor of prev
                     1/q  otherwise

and two SAMPLENEXT backends implement it (selected by `WalkModel.sampler`;
DESIGN.md §8, statistical contract tested in tests/test_walk_stats.py):

  * "rejection" (default; the MH/alias-free scheme used by KnightKing and
    cited in paper Alg. 2's SAMPLENEXT note): propose a uniform neighbor,
    accept with probability alpha / alpha_max. On TPU a data-dependent
    while_loop per lane would serialize the VPU, so we run a FIXED number of
    vectorized trials (accept-first) with the last proposal as fallback.
    APPROXIMATE: with K trials the residual total-variation bias is bounded
    by (1 - alpha_min/alpha_max)^K — real and measurable for sharp (p, q)
    (the order-2 chi-square harness in tests/test_walk_stats.py rejects this
    sampler's distribution at small K and asserts the bound at K=8).

  * "factorized" — EXACT, BINGO-style (PAPERS.md): alpha takes only three
    constant values, so the three groups {x == prev}, {x in N(v) ∩ N(prev)},
    {rest} are sampled by aggregate mass (count x weight) and then uniformly
    within the chosen group. Group counts come from one neighbor-window
    intersection |N(v) ∩ N(prev)| + membership-rank select — the Pallas
    kernel in kernels/intersect.py (four-backend registry, CPU-validated).
    Two uniform draws, no rejection loop in the hot stream_step path.
    Windows are `dmax` wide: lanes where deg(v) or deg(prev) exceed dmax
    fall back to the rejection sampler. The fallback draws with PER-LANE
    keys (fold_in(key, lane_id)), so its selections depend only on
    (key, lane_id) — never on how many other lanes overflowed — and the
    overflowed lanes can be compacted into a small side-batch
    (`rejection_fallback`) whose cost is proportional to the overflow
    count, bit-identical to re-running the whole batch.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.utils import compact_nonzero
from repro.kernels import intersect
from repro.obs import trace

U32 = jnp.uint32
I32 = jnp.int32

# side-batch rows per batch row: the compacted fallback handles up to
# ceil(b / _FALLBACK_SIDE_DIV) overflowed lanes before degrading to the
# whole-batch re-run (still per-lane keyed, so results stay identical)
_FALLBACK_SIDE_DIV = 8


class WalkModel(NamedTuple):
    """order=1 -> DeepWalk; order=2 -> node2vec(p, q).

    sampler: order-2 SAMPLENEXT backend — "rejection" (K-trial, residual
    bias < (1-amin/amax)^K) or "factorized" (exact group factorization).
    dmax: factorized neighbor-window width; lanes with deg > dmax fall back
    to rejection (128 = one VPU lane tile, the kernel-native width)."""

    order: int = 1
    p: float = 1.0
    q: float = 1.0
    n_trials: int = 8  # rejection trials for 2nd-order sampling
    sampler: str = "rejection"   # "rejection" | "factorized"
    dmax: int = 128              # factorized window width (neighbors)


DEEPWALK = WalkModel(order=1)


def deepwalk_step(key, graph, v):
    """v: uint32[B] current vertices -> uint32[B] next vertices."""
    return graph.sample_neighbor(key, v)


@partial(jax.jit, static_argnames=("n_trials",))
def _node2vec_step(key, graph, v, prev, p, q, n_trials):
    b = v.shape[0]
    inv_p = 1.0 / p
    inv_q = 1.0 / q
    a_max = jnp.maximum(jnp.maximum(inv_p, 1.0), inv_q)

    def trial(carry, k):
        chosen, done = carry
        k1, k2 = jax.random.split(k)
        x = graph.sample_neighbor(k1, v)
        alpha = jnp.where(
            x == prev, inv_p,
            jnp.where(graph.has_edge(prev, x), 1.0, inv_q))
        accept = jax.random.uniform(k2, (b,)) * a_max <= alpha
        # first accepted proposal wins; last proposal is the fallback
        chosen = jnp.where(done, chosen, x)
        return (chosen, done | accept), None

    keys = jax.random.split(key, n_trials)
    (chosen, _), _ = jax.lax.scan(trial, (v, jnp.zeros((b,), bool)), keys)
    return chosen


@partial(jax.jit, static_argnames=("n_trials",))
def _node2vec_step_perlane(key, graph, v, prev, p, q, n_trials, lane_ids):
    """Rejection sampling with draws keyed by (key, lane_id) alone.

    Unlike `_node2vec_step` (whose split(key, n_trials) draws depend on
    batch shape and lane position), every draw here comes from
    fold_in(key, lane_id): a lane's selection is invariant under batch
    compaction, which is what lets `rejection_fallback` run overflowed
    lanes in a side-batch bit-identically to a whole-batch re-run."""
    inv_p = 1.0 / p
    inv_q = 1.0 / q
    a_max = jnp.maximum(jnp.maximum(inv_p, 1.0), inv_q)
    lane_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(lane_ids)

    def lane(lk, vv, pv):
        def trial(carry, k):
            chosen, done = carry
            k1, k2 = jax.random.split(k)
            x = graph.sample_neighbor(k1, vv)
            alpha = jnp.where(
                x == pv, inv_p,
                jnp.where(graph.has_edge(pv, x), 1.0, inv_q))
            accept = jax.random.uniform(k2, ()) * a_max <= alpha
            chosen = jnp.where(done, chosen, x)
            return (chosen, done | accept), None

        keys = jax.random.split(lk, n_trials)
        (chosen, _), _ = jax.lax.scan(
            trial, (vv, jnp.asarray(False)), keys)
        return chosen

    return jax.vmap(lane)(lane_keys, v, prev)


def rejection_fallback(key, graph, v, prev, overflow, nxt, p, q, n_trials,
                       side_rows: int | None = None):
    """Replace `nxt` on overflowed lanes with per-lane rejection samples.

    Three-tier switch: no overflow -> identity; overflow count fits the
    side-batch -> compact the overflowed lanes into `side_rows` lanes and
    scatter the samples back; otherwise re-run every lane. All tiers use
    `_node2vec_step_perlane`, whose draws depend only on (key, lane index),
    so the tiers are bit-identical wherever overflow is True."""
    b = v.shape[0]
    side = side_rows if side_rows is not None else max(1, -(-b // _FALLBACK_SIDE_DIV))
    side = min(side, b)
    lane_ids = jnp.arange(b, dtype=I32)
    n_over = jnp.sum(overflow)

    def side_batch(_):
        idx, valid = compact_nonzero(overflow, side)
        rej = _node2vec_step_perlane(key, graph, v[idx], prev[idx], p, q,
                                     n_trials, lane_ids[idx])
        # padding rows (valid=False) carry lane 0's data; route them to an
        # out-of-range index so mode="drop" discards them
        scatter_idx = jnp.where(valid, idx, b)
        return nxt.at[scatter_idx].set(jnp.where(valid, rej, 0), mode="drop")

    def whole_batch(_):
        rej = _node2vec_step_perlane(key, graph, v, prev, p, q, n_trials,
                                     lane_ids)
        return jnp.where(overflow, rej, nxt)

    # one three-way switch (TPU's compiler aborts on the nested-cond form)
    if side >= b:
        branch = jnp.where(n_over > 0, 2, 0)
    else:
        branch = jnp.where(n_over > 0, jnp.where(n_over <= side, 1, 2), 0)
    return jax.lax.switch(branch.astype(I32),
                          (lambda _: nxt, side_batch, whole_batch), None)


def _neighbor_window(graph, v, dmax: int):
    """Sentinel-padded neighbor window: (nbrs u32 [B, dmax], deg i32 [B]).

    The first min(deg, dmax) CSR neighbors of each vertex (code-sorted, so
    each row is sorted — the contract `intersect.member_sorted` needs)."""
    v = jnp.asarray(v, I32)
    start = graph.offsets[v]
    deg = graph.offsets[v + 1] - start
    idx = start[:, None] + jnp.arange(dmax, dtype=I32)[None]
    nbrs = graph.neighbors[jnp.clip(idx, 0, graph.codes.shape[0] - 1)]
    in_win = jnp.arange(dmax, dtype=I32)[None] < jnp.minimum(deg, dmax)[:, None]
    return jnp.where(in_win, nbrs, intersect.SENT), deg


@partial(jax.jit, static_argnames=("p", "q", "n_trials", "dmax", "backend"))
def _node2vec_factorized_step(key, graph, v, prev, p, q, n_trials, dmax,
                              backend):
    """Exact order-2 transition via bias factorization (kernels/intersect).

    Draw discipline: the two factorization uniforms come from one split of
    `key` and the rejection fallback consumes a DIFFERENT split, so the
    factorized selection is identical across backends and unperturbed by
    whether any lane overflowed the window."""
    b = v.shape[0]
    k_u, k_fb = jax.random.split(key)
    u = jax.random.uniform(k_u, (b, 2), dtype=jnp.float32)
    nbrs_v, deg_v = _neighbor_window(graph, v, dmax)
    nbrs_p, deg_p = _neighbor_window(graph, prev, dmax)
    with trace.scope(trace.INTERSECT):
        nxt, found = intersect.factorized_next(
            nbrs_v, nbrs_p, jnp.asarray(prev, U32), u[:, 0], u[:, 1], p, q,
            backend=backend)
    nxt = jnp.where(found, nxt, v)  # isolated vertices stay in place
    overflow = (deg_v > dmax) | (deg_p > dmax)
    return rejection_fallback(k_fb, graph, v, prev, overflow, nxt, p, q,
                              n_trials)


def sample_next_sharded(key, graph, v, model: WalkModel):
    """SAMPLENEXT over the FULL lane vector against a vertex-range-local
    graph — the per-shard half of the explicitly partitioned rewalk
    (distr/sharded.py), bit-identical to the single-host stream.

    Contract (what makes cross-shard draws line up): `deepwalk_step`'s one
    uniform draw per lane is `randint(key, v.shape, 0, max(deg, 1))`, and
    counter-based PRNG bits depend only on (key, shape, lane index) — NOT on
    other lanes' maxval. So every shard calls this with the SAME key and the
    SAME [capacity] lane shape as the single-host `_rewalk` scan; a shard
    has correct `deg`/CSR data only for lanes whose current vertex it owns,
    and exactly those lanes come out bit-identical to the single-host draw
    (non-owned lanes produce garbage that the caller masks out). No
    per-shard fold_in is needed — folding the shard id in would CHANGE the
    single-host stream, which is the one thing the sharded engine must not
    do.

    Order-2 models need the previous vertex's neighbor segment, which may
    live on another shard; until a remote-window exchange exists the sharded
    engine is order-1 only."""
    if model.order != 1:
        raise NotImplementedError(
            "sharded SAMPLENEXT is order-1 (DeepWalk) only: order-2 biases "
            "need N(prev), which may be owned by another shard")
    return deepwalk_step(key, graph, v)


def sample_next(key, graph, v, prev, model: WalkModel):
    """SAMPLENEXT (paper Alg. 2 line 8), vectorized over a batch of walkers.

    Order-2 dispatch is static (model is concrete at trace time): the
    "factorized" sampler resolves its intersect backend from the registry
    once per trace (configs/wharf_stream installs the process default)."""
    if model.order == 1:
        return deepwalk_step(key, graph, v)
    if model.sampler == "factorized":
        # forward the RAW registry request (None = auto), not the resolved
        # backend: an auto pick must keep its shape-aware kernel->interpret
        # fallback inside factorized_next, while an explicitly installed
        # kernel backend still raises off-tile
        backend = intersect.default_backend_request()
        return _node2vec_factorized_step(key, graph, v, prev, model.p,
                                         model.q, model.n_trials,
                                         model.dmax, backend)
    if model.sampler != "rejection":
        raise ValueError(f"unknown order-2 sampler {model.sampler!r}; "
                         f"expected 'rejection' or 'factorized'")
    return _node2vec_step(key, graph, v, prev, model.p, model.q,
                          model.n_trials)
